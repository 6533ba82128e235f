"""Plain reference of ``tiny_lm_lora.json``: the system's ``tiny_lm``
decoder, fine-tuned with LoRA over a frozen base.

The decoder, from ``models/transformer.py``'s equations: token embedding;
per layer, RMSNorm (``x / rms(x) * (1 + scale)``), causal softmax attention
with rotary embeddings on q and k (the two halves of a head rotated
against each other, base ``rope_theta``), scaled by ``1/sqrt(head_dim)``,
a residual; RMSNorm, a SwiGLU feed-forward (``(silu(x Wg) * x Wu) Wd``), a
residual; a final RMSNorm and the output projection.  The loss is the
cross-entropy of each position's logits against the next token.

LoRA, as the configuration's ``"program"`` object sets it (``lora_rank``
``r``, ``lora_alpha``, ``lora_targets``): each adapted weight ``W`` is
used as ``W + (alpha/r) * (A @ B)``, ``A @ B`` reshaped to ``W``'s shape,
with ``W`` matricized between its
input and output axes (``(d, heads*head_dim)`` for ``wq``,
``(heads*head_dim, d)`` for ``wo``) and a leading layer axis kept.  ``A``
starts lecun-normal, ``B`` at zero, so round 0 starts from the base.

Departures, none of them in the mathematics: the base is random, drawn
from the seed (lecun-normal matrices, N(0, 0.02) embeddings, N(0, 0.1)
norm scales); ``A @ B`` is a matmul here and a sum of rank-1 terms in the
system.  Straight ``jax.numpy``; the caller sets the precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _base_shapes(cfg):
    d, h, kv, hd, ff, n = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
                           cfg["d_ff"], cfg["n_layers"])
    return {"embed": (cfg["vocab"], d), "final_norm": {"scale": (d,)},
            "unembed": (d, cfg["vocab"]),
            "segments": [{"norm1": {"scale": (n, d)}, "norm2": {"scale": (n, d)},
                          "attn": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                                   "wv": (n, d, kv, hd), "wo": (n, h, hd, d)},
                          "mlp": {"w_gate": (n, d, ff), "w_up": (n, d, ff),
                                  "w_down": (n, ff, d)}}]}


def _split(shape):
    """A layer-stacked weight's (layers, fan-in, fan-out): the split of its
    other axes where fan-in plus fan-out is least."""
    dims = shape[1:]
    i = min(range(1, len(dims)), key=lambda i: math.prod(dims[:i]) + math.prod(dims[i:]))
    return shape[0], math.prod(dims[:i]), math.prod(dims[i:])


def _leaf(tree, path):
    for part in path.split("/"):
        tree = tree[int(part)] if isinstance(tree, list) else tree[part]
    return tree


def _path(keys) -> str:
    """A key path as the system names it: ``segments/0/attn/wq``."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in keys)


def init_frozen(key, cfg):
    """The base, in the configuration's dtype."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        _base_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(flat)), flat):
        name = _path(path)
        std = (0.1 if "norm" in name else 0.02 if "embed" in name
               else 1.0 / math.sqrt(_split(shape)[1]))
        out.append((std * jax.random.normal(k, shape, jnp.float32)).astype(cfg["dtype"]))
    return jax.tree_util.tree_unflatten(treedef, out)


def _lora(cfg):
    return cfg["program"]["client"]


def adapted(cfg):
    """The paths of the adapted weights, as ``lora_targets`` selects them:
    a weight with two axes or more besides the layer axis whose path holds
    one of the targets."""
    flat = jax.tree_util.tree_flatten_with_path(
        _base_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))[0]
    out = []
    for keys, shape in flat:
        path = _path(keys)
        axes = len(shape) - (1 if path.startswith("segments/") else 0)
        if axes >= 2 and any(t in path for t in _lora(cfg)["lora_targets"]):
            out.append(path)
    return out


def init(key, cfg):
    """The adapters, keyed by the adapted weight's path."""
    base, r = _base_shapes(cfg), _lora(cfg)["lora_rank"]
    paths = adapted(cfg)
    out = {}
    for k, path in zip(jax.random.split(key, len(paths)), paths):
        n, fan_in, fan_out = _split(_leaf(base, path))
        a = jax.random.normal(k, (n, fan_in, r), jnp.float32) / math.sqrt(fan_in)
        out[path] = {"a": a.astype(cfg["dtype"]), "b": jnp.zeros((n, r, fan_out), cfg["dtype"])}
    return out


def merge(adapters, base, cfg):
    """The base with every adapter added: ``W + (alpha/r) * A @ B``."""
    scale = _lora(cfg)["lora_alpha"] / _lora(cfg)["lora_rank"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(base)
    out = []
    for path, w in flat:
        ab = adapters.get(_path(path))
        if ab is not None:
            w = w + scale * jnp.matmul(ab["a"], ab["b"]).reshape(w.shape).astype(w.dtype)
        out.append(w)
    return jax.tree_util.tree_unflatten(treedef, out)


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """x: (B, S, H, D), positions 0 .. S-1."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def forward(base, x, cfg):
    """Logits (B, S, vocab) of tokens ``x`` (B, S) under weights ``base``."""
    eps, hd = cfg["norm_eps"], cfg["head_dim"]
    group = cfg["n_heads"] // cfg["n_kv_heads"]
    h = base["embed"][x]
    s = x.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    seg = base["segments"][0]
    for layer in range(cfg["n_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[layer], seg)
        a = _rms(h, p["norm1"]["scale"], eps)
        q = _rope(jnp.einsum("bsd,dhf->bshf", a, p["attn"]["wq"]), cfg["rope_theta"])
        k = _rope(jnp.einsum("bsd,dhf->bshf", a, p["attn"]["wk"]), cfg["rope_theta"])
        v = jnp.einsum("bsd,dhf->bshf", a, p["attn"]["wv"])
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        scores = jnp.einsum("bqhf,bkhf->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bhqk,bkhf->bqhf", probs.astype(v.dtype), v)
        h = h + jnp.einsum("bshf,hfd->bsd", ctx, p["attn"]["wo"])
        a = _rms(h, p["norm2"]["scale"], eps)
        ff = jax.nn.silu(a @ p["mlp"]["w_gate"]) * (a @ p["mlp"]["w_up"])
        h = h + ff @ p["mlp"]["w_down"]
    h = _rms(h, base["final_norm"]["scale"], eps)
    return h @ base["unembed"]


def loss(adapters, x, y, cfg, frozen):
    """Mean next-token cross-entropy: position t predicts token t + 1."""
    logits = forward(merge(adapters, frozen, cfg), x, cfg)[:, :-1].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, x[:, 1:, None], axis=-1))

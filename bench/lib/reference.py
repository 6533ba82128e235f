"""Plain reference of one federated round, and the comparison that decides
``correct``.

The round, as EasyFL defines it: every client of the cohort starts from
the global weights and runs its local SGD steps (momentum from zero each
round) over its own shuffled batches; its update is its weights' change;
with STC the update plus the client's stored residual is sparsified tile
by tile (about the top ``keep`` share of each 8192-element tile, kept
entries set to the sign times the mean kept magnitude) and the residual
keeps what was not sent; FedAvg weighs the sent updates by the clients' sample counts;
the server adds the weighted sum to the global weights.

Nothing of the program is imported.  The model comes from the
configuration's own reference file, the weights from the seed, the data
and cohorts from ``traffic``.  Precision ``"f32"`` runs every matmul and
convolution at ``highest``; ``"bf16"`` is the control: the clients'
training in bfloat16.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import traffic as tr

# STC's tile: the system's kernel thresholds each (8, 1024) tile of a
# leaf's flat update on its own; tensors under 64 elements stay dense.
STC_TILE = 8 * 1024
STC_HALVINGS = 16
DENSE_MIN_ELEMS = 64


def init_weights(ref, cfg, seed):
    """The weights a run starts from, made on the device in one call."""
    return jax.jit(lambda k: ref.init(k, cfg))(tr.jax_key(seed, tr.STREAM_WEIGHTS))


def _loss(ref, cfg, params, x, y):
    logits = ref.forward(params, x, cfg).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def _local_train(ref, cfg, lr, momentum, dtype, params, x, y, idx):
    p0 = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    x = x.astype(dtype)

    def step(carry, bidx):
        p, m = carry
        loss, g = jax.value_and_grad(partial(_loss, ref, cfg))(p, x[bidx], y[bidx])
        m = jax.tree_util.tree_map(lambda mi, gi: momentum * mi + gi, m, g)
        p = jax.tree_util.tree_map(lambda pi, mi: pi - lr * mi, p, m)
        return (p, m), loss

    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    (p, _), losses = jax.lax.scan(step, (p0, zeros), idx)
    update = jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0)
    return update, jnp.mean(losses)


def stc_leaf(x, keep: float):
    """Tile-wise sparse ternary compression of one flat leaf.  Each tile's
    threshold is found as EasyFL's STC finds it: 16 halvings of
    ``[0, max|x|]`` toward the largest threshold that keeps more than
    ``round(keep * n)`` entries, then the midpoint; entries above it are
    kept."""
    n = x.size
    tiles = -(-n // STC_TILE)
    real = np.clip(n - np.arange(tiles) * STC_TILE, 0, STC_TILE)
    target = jnp.maximum(jnp.round(jnp.float32(keep) * jnp.asarray(real, jnp.float32)), 1.0)
    t = jnp.pad(x, (0, tiles * STC_TILE - n)).reshape(tiles, STC_TILE)
    ax = jnp.abs(t)
    lo, hi = jnp.zeros((tiles,), jnp.float32), jnp.max(ax, axis=1) + 1e-12
    for _ in range(STC_HALVINGS):
        mid = 0.5 * (lo + hi)
        more = jnp.sum(ax > mid[:, None], axis=1).astype(jnp.float32) > target
        lo, hi = jnp.where(more, mid, lo), jnp.where(more, hi, mid)
    mask = ax > (0.5 * (lo + hi))[:, None]
    mu = jnp.sum(jnp.where(mask, ax, 0.0), axis=1) / jnp.maximum(jnp.sum(mask, axis=1), 1)
    out = jnp.where(mask, jnp.sign(t) * mu[:, None], 0.0)
    return out.reshape(-1)[:n]


def _stc_tree(keep, update, residual):
    def one(u, r):
        c = (u + r).reshape(-1)
        sent = c if c.size < DENSE_MIN_ELEMS else stc_leaf(c, keep)
        return sent.reshape(u.shape), (c - sent).reshape(u.shape)

    pairs = jax.tree_util.tree_map(one, update, residual)
    sent = jax.tree_util.tree_map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
    res = jax.tree_util.tree_map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
    return sent, res


def run_reference(ref, cfg, fed: tr.Federation, traffic: Dict[str, Any], seed: int,
                  rounds: int, precision: str = "f32") -> Dict[str, Any]:
    """Rounds ``0 .. rounds-1`` of the plain reference.

    Returns the weighted train loss of each round and the weights on the
    host before round 0 and after every round (``params[r]`` is the
    weights round ``r`` starts from)."""
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[precision]
    opt = traffic["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"reference trains with SGD only, not {opt['name']!r}")
    stc = traffic["compression"] == "stc"
    if traffic["compression"] not in ("stc", "none"):
        raise ValueError(f"reference has no {traffic['compression']!r} uplink")
    with jax.default_matmul_precision("highest" if precision == "f32" else "default"):
        local = jax.jit(partial(_local_train, ref, cfg, float(opt["lr"]),
                                float(opt["momentum"]), dtype))
        compress = jax.jit(partial(_stc_tree, float(traffic["stc_keep"])))
        add = jax.jit(lambda acc, w, s: jax.tree_util.tree_map(
            lambda a, b: a + w * b, acc, s))
        params = init_weights(ref, cfg, seed)
        out: Dict[str, Any] = {"loss": [], "params": [jax.device_get(params)]}
        residual: Dict[int, Any] = {}
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        for r in range(rounds):
            cohort = fed.cohort_of(r)
            sizes = np.asarray([fed.size(i) for i in cohort], np.float64)
            weights = sizes / sizes.sum()
            delta, loss = zeros, 0.0
            for w, i in zip(weights, cohort):
                x, y = fed.data(i)
                update, client_loss = local(params, x, y, tr.client_schedule(fed, i, r))
                if stc:
                    update, residual[i] = compress(update, residual.get(i, zeros))
                delta = add(delta, jnp.float32(w), update)
                loss += float(w) * float(client_loss)
            params = jax.tree_util.tree_map(jnp.add, params, delta)
            out["loss"].append(loss)
            out["params"].append(jax.device_get(params))
    return out


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def _leaf_norms(a: Any, b: Any) -> np.ndarray:
    """Per-leaf L2 norm of ``a - b`` (host trees, float64)."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return np.asarray([np.linalg.norm(np.asarray(x, np.float64) - np.asarray(y, np.float64))
                       for x, y in zip(la, lb)])


def leaf_gaps(prog_norms: np.ndarray, ref_norms: np.ndarray) -> np.ndarray:
    """Each leaf's gap between the program's norm and the reference's, as a
    share of the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    scale = np.maximum(ref_norms, np.median(ref_norms))
    return np.abs(prog_norms - ref_norms) / scale


def _whole(norms: np.ndarray) -> float:
    return float(np.sqrt(np.sum(np.square(norms))))


def _changed(a: Any, b: Any) -> int:
    """Entries of the weights that differ between ``a`` and ``b``."""
    return sum(int(np.count_nonzero(np.asarray(x) != np.asarray(y)))
               for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def _update_numbers(prog_a, prog_b, ref_a, ref_b, moved=None) -> Dict[str, float]:
    """How the program's update ``prog_b - prog_a`` departs from the
    reference's ``ref_b - ref_a``: ``gap``, the worst leaf's gap of norms
    (over the ``moved`` leaves); ``median``, the median leaf's gap;
    ``diff``, the norm of the difference of the two updates over the
    norm of the reference's; ``support``, how far the count of weights
    the update changes differs from the reference's, as a share of it."""
    want, got = _leaf_norms(ref_b, ref_a), _leaf_norms(prog_b, prog_a)
    gaps = leaf_gaps(got, want)
    support = _changed(ref_b, ref_a)
    diff = _leaf_norms(jax.tree_util.tree_map(np.subtract, prog_b, prog_a),
                       jax.tree_util.tree_map(np.subtract, ref_b, ref_a))
    return {"gap": np.max(gaps if moved is None else gaps[moved]),
            "median": np.median(gaps),
            "diff": _whole(diff) / _whole(want),
            "support": abs(_changed(prog_b, prog_a) - support) / max(support, 1)}


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """Every number the check can compare; ``limits/<workload>.json`` names
    the ones a cell is held to, and the rest are logged beside them.

    ``loss_gap``: the worst round's train-loss gap, as a share of the
    reference's loss.  Then three updates, each read as
    :func:`_update_numbers` reads it (``<update>_gap``, ``_median``,
    ``_diff``, ``_support``): ``grad``, the first round's aggregated
    update (the server's gradient, read from its weights after one round);
    ``change``, the weights' change over all compared rounds, its worst
    leaf leaving out leaves whose first-round update in the reference is
    under a thousandth of the median leaf's (their gradient is nought to
    rounding: a bias that a normalisation removes); ``last``, the last
    compared round's own update, the round in which clients come back to
    their stored residuals and data rows, or the data pool evicts."""
    n = len(ref["loss"])
    lp, lr = np.asarray(prog["loss"][:n], np.float64), np.asarray(ref["loss"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr))) if np.all(np.isfinite(lp)) else float("inf")
    p0 = ref["params"][0]
    at = [p0 if x is None else x for x in prog["params"][: n + 1]]
    g_ref = _leaf_norms(ref["params"][1], p0)
    moved = g_ref >= 1e-3 * np.median(g_ref)
    out = {"loss_gap": loss_gap}
    for what, (a, b, keep) in {"grad": (0, 1, None), "change": (0, n, moved),
                               "last": (n - 1, n, None)}.items():
        for k, v in _update_numbers(at[a], at[b], ref["params"][a], ref["params"][b],
                                    keep).items():
            out[f"{what}_{k}"] = v
    return {k: (float(v) if np.isfinite(v) else float("inf")) for k, v in out.items()}


def leaf_table(prog: Dict[str, Any], ref: Dict[str, Any]) -> List[List[float]]:
    """Per leaf: its size, then for the first round and for the last
    compared round the reference's and the program's change from the start
    and the norm of their difference: what a new number is read from."""
    n = len(ref["loss"])
    p0 = ref["params"][0]
    cols = [np.asarray([np.size(a) for a in jax.tree_util.tree_leaves(p0)], np.float64)]
    for r in (1, n):
        cols += [_leaf_norms(ref["params"][r], p0), _leaf_norms(prog["params"][r], p0),
                 _leaf_norms(prog["params"][r], ref["params"][r])]
    return np.stack(cols, axis=1).tolist()


def worst_leaves(prog: Dict[str, Any], ref: Dict[str, Any], k: int = 3) -> List[str]:
    """The ``k`` leaves farthest apart in each norm comparison, with both
    norms and the median leaf's: what a failing check is read by."""
    n = len(ref["loss"])
    p0 = ref["params"][0]
    flat = jax.tree_util.tree_flatten_with_path(p0)[0]
    paths = [f"{jax.tree_util.keystr(p)} ({np.size(a)})" for p, a in flat]
    lines = []
    for what, r in (("grad", 1), ("change", n)):
        want, got = _leaf_norms(ref["params"][r], p0), _leaf_norms(prog["params"][r], p0)
        for i in np.argsort(-leaf_gaps(got, want))[:k]:
            lines.append(f"{what} {paths[i]} program {got[i]:.6e} reference {want[i]:.6e} "
                         f"median {np.median(want):.6e}")
    return lines

"""Plain reference of ``resnet18_cifar10.json``: ResNet-18, CIFAR variant.

He et al., arXiv:1512.03385, as EasyFL (arXiv:2105.07603) trains it on
CIFAR-10: a 3x3 stem of ``stem_width`` channels with no max-pool, four
stages of two basic blocks at ``stage_widths`` (the first block of stages
2-4 strides by 2 and projects the shortcut with a 1x1 convolution), global
average pooling and one dense layer.  Departure, as the system has it:
GroupNorm of ``norm_groups`` groups in place of BatchNorm, after every
convolution, so the model's state is its parameters alone.

Straight ``jax.numpy``; the caller sets the precision.  The parameter tree
is named as the system names it, so the two can be handed the same weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _conv_shape(k, cin, cout):
    return {"w": (k, k, cin, cout), "b": (cout,)}


def _gn_shape(c):
    return {"scale": (c,), "bias": (c,)}


def shapes(cfg):
    """The parameter tree's shapes, from the configuration's widths."""
    stem = cfg["stem_width"]
    tree = {"stem": _conv_shape(3, cfg["input_shape"][2], stem),
            "stem_gn": _gn_shape(stem),
            "fc": {"w": (cfg["stage_widths"][-1], cfg["num_classes"]),
                   "b": (cfg["num_classes"],)}}
    cin = stem
    for si, w in enumerate(cfg["stage_widths"]):
        for bi in range(cfg["blocks_per_stage"]):
            stride = 2 if (si > 0 and bi == 0) else 1
            blk = {"conv1": _conv_shape(3, cin, w), "gn1": _gn_shape(w),
                   "conv2": _conv_shape(3, w, w), "gn2": _gn_shape(w)}
            if stride != 1 or cin != w:
                blk["down"] = _conv_shape(1, cin, w)
                blk["down_gn"] = _gn_shape(w)
            tree[f"b{si}{bi}"] = blk
            cin = w
    return tree


def init(key, cfg, dtype=jnp.float32):
    """He-normal convolutions, LeCun-normal dense weights, zero biases,
    unit GroupNorm scales."""
    tree = shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda s: isinstance(s, tuple))
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda s: isinstance(s, tuple))[0]]
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, path, shape in zip(keys, paths, leaves):
        last = path[-1].key
        if last == "scale":
            out.append(jnp.ones(shape, dtype))
        elif last in ("b", "bias"):
            out.append(jnp.zeros(shape, dtype))
        elif len(shape) == 4:
            fan_in = shape[0] * shape[1] * shape[2]
            out.append((jax.random.normal(k, shape) * (2.0 / fan_in) ** 0.5).astype(dtype))
        else:
            out.append((jax.random.normal(k, shape) * shape[0] ** -0.5).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _conv(x, p, stride):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _gn(x, p, groups, eps):
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups).astype(jnp.float32)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + eps)
    return (xg.reshape(b, h, w, c) * p["scale"] + p["bias"]).astype(x.dtype)


def forward(params, x, cfg):
    """Logits of a batch ``x`` of shape (B, H, W, C)."""
    g, eps = cfg["norm_groups"], cfg["norm_eps"]
    x = x.reshape((x.shape[0],) + tuple(cfg["input_shape"])).astype(params["stem"]["w"].dtype)
    x = jax.nn.relu(_gn(_conv(x, params["stem"], 1), params["stem_gn"], g, eps))
    for si in range(len(cfg["stage_widths"])):
        for bi in range(cfg["blocks_per_stage"]):
            p = params[f"b{si}{bi}"]
            stride = 2 if (si > 0 and bi == 0) else 1
            y = jax.nn.relu(_gn(_conv(x, p["conv1"], stride), p["gn1"], g, eps))
            y = _gn(_conv(y, p["conv2"], 1), p["gn2"], g, eps)
            if "down" in p:
                x = _gn(_conv(x, p["down"], stride), p["down_gn"], g, eps)
            x = jax.nn.relu(x + y)
    x = x.mean(axis=(1, 2))
    return x @ params["fc"]["w"] + params["fc"]["b"]

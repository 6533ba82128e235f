"""Plain reference of ``femnist_cnn_leaf.json``: LEAF's FEMNIST CNN.

Caldas et al., arXiv:1812.01097: 5x5 convolution of ``conv_widths[0]``
channels, ReLU, 2x2 max-pool, 5x5 convolution of ``conv_widths[1]``,
ReLU, 2x2 max-pool, a dense layer of ``dense_width`` with ReLU, and the
62-way output layer.  Convolutions pad to keep their size ("SAME").

Straight ``jax.numpy``; the caller sets the precision.  The parameter tree
is named as the system names it, so the two can be handed the same weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def shapes(cfg):
    k = cfg["kernel_size"]
    c1, c2 = cfg["conv_widths"]
    cin = cfg["input_shape"][2]
    h = cfg["input_shape"][0] // 4
    return {"conv1": {"w": (k, k, cin, c1), "b": (c1,)},
            "conv2": {"w": (k, k, c1, c2), "b": (c2,)},
            "fc1": {"w": (h * h * c2, cfg["dense_width"]), "b": (cfg["dense_width"],)},
            "fc2": {"w": (cfg["dense_width"], cfg["num_classes"]),
                    "b": (cfg["num_classes"],)}}


def init(key, cfg, dtype=jnp.float32):
    """He-normal convolutions, LeCun-normal dense weights, zero biases."""
    tree = shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, shape in zip(keys, leaves):
        if len(shape) == 1:
            out.append(jnp.zeros(shape, dtype))
        elif len(shape) == 4:
            fan_in = shape[0] * shape[1] * shape[2]
            out.append((jax.random.normal(k, shape) * (2.0 / fan_in) ** 0.5).astype(dtype))
        else:
            out.append((jax.random.normal(k, shape) * shape[0] ** -0.5).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _conv(x, p):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(params, x, cfg):
    """Logits of a batch ``x`` of shape (B, 28, 28, 1)."""
    x = x.reshape((x.shape[0],) + tuple(cfg["input_shape"])).astype(params["conv1"]["w"].dtype)
    x = _pool(jax.nn.relu(_conv(x, params["conv1"])))
    x = _pool(jax.nn.relu(_conv(x, params["conv2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    return x @ params["fc2"]["w"] + params["fc2"]["b"]

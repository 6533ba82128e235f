"""Plain reference of the system's ``linear`` test model: one dense layer."""
import jax
import jax.numpy as jnp


def init(key, cfg, dtype=jnp.float32):
    d, c = cfg["input_shape"][0], cfg["num_classes"]
    return {"fc": {"w": (jax.random.normal(key, (d, c)) * d ** -0.5).astype(dtype),
                   "b": jnp.zeros((c,), dtype)}}


def forward(params, x, cfg):
    x = x.reshape(x.shape[0], -1).astype(params["fc"]["w"].dtype)
    return x @ params["fc"]["w"] + params["fc"]["b"]

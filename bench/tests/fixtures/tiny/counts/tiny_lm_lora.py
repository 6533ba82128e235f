"""FLOPs of one sequence of ``tiny_lm_lora``, 2 per multiply-add, from the
configuration's shapes.  Norms, rotary embeddings, activations, the
softmax and the loss are left out (elementwise).  Attention counts the
causal half: query ``t`` meets ``t + 1`` keys."""


def _per_layer(cfg):
    d, h, kv, hd, ff = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"], cfg["d_ff"]
    s = cfg["seq_len"]
    weights = s * (2 * d * (h + 2 * kv) * hd + 2 * h * hd * d + 3 * 2 * d * ff)
    attention = 2 * 2 * h * hd * s * (s + 1) // 2
    return weights, attention


def _adapters(cfg):
    """The adapters' own multiply-adds, ``x A`` then ``(x A) B``, per
    sequence and layer."""
    d, h, hd, ff = cfg["d_model"], cfg["n_heads"], cfg["head_dim"], cfg["d_ff"]
    lora = cfg["program"]["client"]
    shapes = {"attn/wq": (d, h * hd), "attn/wo": (h * hd, d), "mlp/w_down": (ff, d)}
    total = 0
    for target in lora["lora_targets"]:
        d_in, d_out = shapes[target]
        total += 2 * lora["lora_rank"] * (d_in + d_out)
    return cfg["seq_len"] * total


def forward_flops(cfg) -> float:
    weights, attention = _per_layer(cfg)
    unembed = cfg["seq_len"] * 2 * cfg["d_model"] * cfg["vocab"]
    return float(cfg["n_layers"] * (weights + attention + _adapters(cfg)) + unembed)


def train_flops(cfg) -> float:
    """Training under LoRA: the frozen weights' matmuls run forward and
    back to their inputs but compute no weight gradient (2 x); attention
    has no weights and back-propagates to both its operands, and the
    adapters compute both gradients (3 x)."""
    weights, attention = _per_layer(cfg)
    unembed = cfg["seq_len"] * 2 * cfg["d_model"] * cfg["vocab"]
    return float(2 * (cfg["n_layers"] * weights + unembed)
                 + 3 * cfg["n_layers"] * (attention + _adapters(cfg)))

"""Forward FLOPs of one sample of ``resnet18_cifar10``: 2 per multiply-add
of every convolution and of the dense layer, from the configuration's
shapes.  A convolution counts only the taps that land inside its input:
"SAME" padding adds zeros that the algorithm does not need.
Normalisation, activations, pooling and the loss are left out: they are
elementwise and about 1% of the total."""


def taps(n_in: int, k: int, stride: int) -> int:
    """Sum over one axis's outputs of the kernel taps inside the input,
    for "SAME" padding (low side ``pad // 2``)."""
    n_out = -(-n_in // stride)
    lo = max((n_out - 1) * stride + k - n_in, 0) // 2
    return sum(1 for o in range(n_out) for t in range(k) if 0 <= o * stride - lo + t < n_in)


def _conv(h, w, k, stride, cin, cout):
    return 2 * taps(h, k, stride) * taps(w, k, stride) * cin * cout


def forward_flops(cfg) -> float:
    h, w, cin = cfg["input_shape"]
    stem = cfg["stem_width"]
    total = _conv(h, w, 3, 1, cin, stem)
    cin = stem
    for si, width in enumerate(cfg["stage_widths"]):
        for bi in range(cfg["blocks_per_stage"]):
            stride = 2 if (si > 0 and bi == 0) else 1
            total += _conv(h, w, 3, stride, cin, width)
            if stride != 1 or cin != width:
                total += _conv(h, w, 1, stride, cin, width)
            h, w = -(-h // stride), -(-w // stride)
            total += _conv(h, w, 3, 1, width, width)
            cin = width
    return float(total + 2 * cin * cfg["num_classes"])

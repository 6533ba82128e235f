"""Forward FLOPs of one sample of ``femnist_cnn_leaf``: 2 per multiply-add
of both convolutions and both dense layers, from the configuration's
shapes.  A convolution counts only the taps that land inside its input
("SAME" padding adds zeros the algorithm does not need).  Biases, ReLU
and pooling are left out: elementwise, under 1%."""


def taps(n_in: int, k: int) -> int:
    lo = (k - 1) // 2
    return sum(1 for o in range(n_in) for t in range(k) if 0 <= o - lo + t < n_in)


def forward_flops(cfg) -> float:
    h, w, cin = cfg["input_shape"]
    k = cfg["kernel_size"]
    c1, c2 = cfg["conv_widths"]
    conv1 = 2 * taps(h, k) * taps(w, k) * cin * c1
    conv2 = 2 * taps(h // 2, k) * taps(w // 2, k) * c1 * c2
    flat = (h // 4) * (w // 4) * c2
    dense = 2 * flat * cfg["dense_width"] + 2 * cfg["dense_width"] * cfg["num_classes"]
    return float(conv1 + conv2 + dense)

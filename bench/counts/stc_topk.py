"""Least HBM bytes of one round's STC compression: each real client's
update of every compressed leaf read once as float32, and the kept share
written as a float32 value and an int32 index.  Leaves under 64 elements
stay dense and are not compressed."""

DENSE_MIN_ELEMS = 64


def bytes_per_round(n_clients: int, leaf_sizes, keep: float) -> float:
    d = sum(s for s in leaf_sizes if s >= DENSE_MIN_ELEMS)
    return float(n_clients * d * 4 + n_clients * d * keep * 8)

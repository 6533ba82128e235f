"""Least HBM bytes of one round's FedAvg: the real clients' float32
updates (N x D) and their N weights read once, the float32 sum (D)
written once.  Its 2·N·D FLOPs take far less time at peak than the bytes."""


def bytes_per_round(n_clients: int, d: int) -> float:
    return float(4 * n_clients * d + 4 * n_clients + 4 * d)


def flops_per_round(n_clients: int, d: int) -> float:
    return float(2 * n_clients * d)

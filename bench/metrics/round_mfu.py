"""The whole round's share of the chips' bf16 peak, in percent: forward
plus backward FLOPs of the real samples trained (three forwards per
sample, from the configuration's shapes; no padded step, no recomputation)
at the traced window's samples per second."""
from peaks import peaks


def read(ctx):
    flops = ctx["bench"].count(ctx["workload"]["config"]).forward_flops(ctx["config"])
    peak = peaks(ctx["device_kind"])["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * 3.0 * flops * ctx["samples_per_s"] / peak

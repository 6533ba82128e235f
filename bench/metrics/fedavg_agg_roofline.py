"""FedAvg kernel's share of its roofline, in percent: the least time its
bytes take at the chip's HBM bandwidth (its FLOPs take less) over its
device time in the trace.  Nothing when the trace holds no such kernel."""
import devtrace
from peaks import peaks

# the kernel's operations as the TPU trace names them
KERNEL = r"^%_aggregate_padded[.0-9]* = .*custom-call"


def read(ctx):
    ns = sum(devtrace.op_time_ns(ctx["events"], KERNEL, ctx["lo"], ctx["hi"], d)
             for d in ctx["trace_chips"])
    if ns <= 0:
        return None
    n = ctx["traffic"]["clients_per_round"]
    d = sum(ctx["update_sizes"])
    count = ctx["bench"].count("fedavg_agg")
    pk = peaks(ctx["device_kind"])
    least_s = max(count.bytes_per_round(n, d) / pk["hbm_bytes_per_s"],
                  count.flops_per_round(n, d) / pk["bf16_flops_per_s"]) * ctx["rounds"]
    return 100.0 * least_s / (ns / 1e9)

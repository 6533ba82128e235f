"""STC compression kernel's share of its roofline, in percent: the least
time its bytes take at the chip's HBM bandwidth over its device time in
the trace.  Bound by bytes: its compares and selects run on the vector
unit and its bytes take longer.  Nothing when the trace holds no such
kernel."""
import devtrace
from peaks import peaks

# the kernel's operations as the TPU trace names them
KERNEL = r"^%_stc_batched_padded[.0-9]* = .*custom-call"


def read(ctx):
    if ctx["traffic"]["compression"] != "stc":
        return None
    ns = sum(devtrace.op_time_ns(ctx["events"], KERNEL, ctx["lo"], ctx["hi"], d)
             for d in ctx["trace_chips"])
    if ns <= 0:
        return None
    n = ctx["traffic"]["clients_per_round"]
    b = ctx["bench"].count("stc_topk").bytes_per_round(
        n, ctx["update_sizes"], ctx["traffic"]["stc_keep"]) * ctx["rounds"]
    least_s = b / peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)

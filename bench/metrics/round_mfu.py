"""The whole round's share of the chips' bf16 peak, in percent: the FLOPs
of training the real samples (no padded step, no recomputation) at the
traced window's samples per second.  A sample's training FLOPs are the
configuration's count file's ``train_flops`` where it gives one (such as
LoRA over a frozen base, which computes no weight gradient of the base),
and otherwise three forwards (``forward_flops``: forward plus backward)."""
from peaks import peaks


def read(ctx):
    count = ctx["bench"].count(ctx["workload"]["config"])
    train = (count.train_flops(ctx["config"]) if hasattr(count, "train_flops")
             else 3.0 * count.forward_flops(ctx["config"]))
    peak = peaks(ctx["device_kind"])["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * train * ctx["samples_per_s"] / peak

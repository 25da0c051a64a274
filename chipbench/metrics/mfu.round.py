"""The whole round's share of the chips' bf16 peak: the operations the
rounds of the traced window need (``chipbench.cost.call_flops``, the
participants' unpadded work and scheduled evaluation) over the window's
length, the number of chips and each chip's peak."""
from chipbench import cost, spec


def read(rec):
    window_s = rec["trace"].window_s
    if window_s <= 0 or rec["calls"] == 0:
        return None
    flops = rec["calls"] * cost.call_flops(rec["config"], rec["traffic"],
                                           root=rec.get("root", spec.REPO))
    return 100.0 * flops / (window_s * rec["chips"] * rec["peak"]["bf16_flops_per_s"])

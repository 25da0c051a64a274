"""The fused round kernel's share of its roofline: the least time the
chip could take for the kernel's needed work (the larger of operations
over peak and bytes over HBM bandwidth, ``chipbench.cost.fused_round_cost``
per round) over the summed device time of the kernel's operations."""
from chipbench import cost
from chipbench import trace as tr

# the kernel's HLO instructions are named after its jitted entry point
KERNEL_PREFIX = "fused_round"


def read(rec):
    if not rec["traffic"].get("fused_round"):
        return None
    kernel_s, count = tr.op_time_s(
        rec["trace"], lambda text: tr.op_name(text).startswith(KERNEL_PREFIX))
    if count == 0 or kernel_s <= 0:
        return None
    need = cost.fused_round_cost(rec["config"], rec["traffic"])
    peak = rec["peak"]
    least = max(need["flops"] / peak["bf16_flops_per_s"],
                need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least * rec["rounds"] / kernel_s

"""The client-distillation kernel's share of its roofline: the least time
the chip could take for the work client distillation needs (every
participant's ``distill_steps`` training steps on the round's public
rows, operations over the bf16 peak, or its parameters read and written
once with the public rows and the teacher, bytes over HBM bandwidth,
whichever is larger) over the summed device time of the kernel's
operations.  Unpadded shapes only, from ``chipbench.cost``."""
from chipbench import cost
from chipbench import trace as tr

# the kernel's HLO instructions are named after its jitted entry point
KERNEL_PREFIX = "mlp_distill"


def read(rec):
    kernel_s, count = tr.op_time_s(
        rec["trace"], lambda text: tr.op_name(text).startswith(KERNEL_PREFIX))
    if count == 0 or kernel_s <= 0:
        return None
    config = rec["config"]
    dims = cost.mlp_dims(config)
    clients = cost.participants(config, rec["traffic"])
    rows = config["public_per_round"]
    flops = clients * config["distill_steps"] * rows * cost.train_step_flops(dims)
    nbytes = 4 * (2 * clients * cost.param_count(dims)
                  + rows * (dims[0] + dims[-1]))
    peak = rec["peak"]
    least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least * rec["rounds"] / kernel_s

"""The client-distillation kernel's share of its roofline: the least time
the chip could take for the work client distillation needs (every
participant's ``distill_steps`` training steps on the round's public
rows, operations over the bf16 peak, or its parameters read and written
once with the public rows and the teacher, bytes over HBM bandwidth,
whichever is larger) over the summed device time of the kernel's
operations.  Unpadded shapes only: participants from ``chipbench.cost``,
the MLP's counts from ``chipbench/families/mlp.py``."""
from chipbench import cost, spec
from chipbench import trace as tr

# the kernel's HLO instructions are named after its jitted entry point
KERNEL_PREFIX = "mlp_distill"


def need(config, traffic, root=spec.REPO):
    """One round's client distillation: operations and bytes."""
    mlp = spec.part("family", "mlp", root)
    dims = mlp.dims(config)
    clients = cost.participants(config, traffic)
    rows = config["public_per_round"]
    return {"flops": clients * config["distill_steps"] * rows * mlp.train_step_flops(config),
            "bytes": 4 * (2 * clients * mlp.param_count(config)
                          + rows * (dims[0] + dims[-1]))}


def read(rec):
    kernel_s, count = tr.op_time_s(
        rec["trace"], lambda text: tr.op_name(text).startswith(KERNEL_PREFIX))
    if count == 0 or kernel_s <= 0:
        return None
    n = need(rec["config"], rec["traffic"], rec.get("root", spec.REPO))
    peak = rec["peak"]
    least = max(n["flops"] / peak["bf16_flops_per_s"], n["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least * rec["rounds"] / kernel_s

"""The client-distillation kernel's roofline reader on a synthesized
trace: the least time of the cell's unpadded distillation work over the
device time of the ``mlp_distill`` instructions, and nothing where the
kernel did not run."""
import pytest

from chipbench import spec
from chipbench import trace as tr


def _op(name, start, end):
    return (f"%{name} = f32[8,128]{{1,0}} custom-call(f32[8,128]{{1,0}} %x)",
            start, end)


def test_roofline_mlp_distill_reads_the_kernel_instructions():
    cell = spec.load_cell("mnist-iid-scan-full")
    ops = [_op("mlp_distill.7", 0, 40), _op("fusion.1", 40, 60),
           _op("mlp_distill.7", 60, 100)]
    rec = {"trace": tr.Trace({"/device:TPU:0": ops}, [], (0, 100)),
           "config": cell.config, "traffic": cell.traffic, "calls": 1,
           "rounds": 20, "chips": 1, "peak": spec.peaks("TPU v5 lite")}
    # 100 clients x 5 steps x 1,000 rows x 879,200 operations: 439.6 GFLOP
    # a round, which bounds it (2.23 ms at 197 TFLOP/s against 0.19 ms for
    # the 159 MB of parameters read and written once)
    flops = 100 * 5 * 1000 * spec.part("family", "mlp").train_step_flops(cell.config)
    assert flops == pytest.approx(439.6e9)
    want = 100 * (flops / 197e12) * 20 / 80e-9
    assert spec.metric_reader("roofline.mlp_distill")(rec) == pytest.approx(want)
    rec["trace"] = tr.Trace({"/device:TPU:0": [_op("fusion.1", 0, 5)]}, [], (0, 10))
    assert spec.metric_reader("roofline.mlp_distill")(rec) is None

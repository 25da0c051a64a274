"""CPU tests of the stage and host-span reduction (``chipbench.stages``)
on small synthesized traces, and of the accepted readers' values on the
synthetic trace they were written against.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
import pytest

from chipbench import cost, spec, stages
from chipbench import trace as tr
from test_chipbench import _op, _trace


BODY = "jit(<lambda>)/while/body/closed_call"


def test_innermost_stage_of_an_op_path():
    assert stages.stage_of_path(f"{BODY}/fl.aggregate/jit(fused_round)/mul") == "aggregate"
    # nested scopes: the innermost one wins; names that are no stage are skipped
    assert stages.stage_of_path(f"{BODY}/fl.predict/fl.cache/gather") == "cache"
    assert stages.stage_of_path(f"{BODY}/fl.cache/fl.codec/add") == "cache"
    assert stages.stage_of_path(f"{BODY}/copy") == stages.UNSCOPED


def test_stages_from_the_optimized_hlo_by_instruction_name():
    hlo = (f'  %fusion.7 = f32[4]{{0}} fusion(%p), kind=kLoop, calls=%fc.7, '
           f'metadata={{op_name="{BODY}/fl.local_train/dot_general"}}\n'
           f'  ROOT %fused_round.3 = f32[16,128]{{1,0}} custom-call(%a), '
           f'metadata={{op_name="{BODY}/fl.aggregate/jit(fused_round)/pallas_call"}}\n'
           f'  %copy.2 = f32[4]{{0}} copy(%q)\n'
           f'  %add.9 = f32[4]{{0}} add(%q, %q), metadata={{op_name="jit(add)/add"}}\n')
    assert stages.hlo_stages(hlo) == {"fusion.7": "local_train",
                                      "fused_round.3": "aggregate",
                                      "add.9": stages.UNSCOPED}


STAGE_OF = {"f.1": "client_distill", "f.2": "local_train",
            "fused_round.3": "aggregate", "copy.4": stages.UNSCOPED}


def _stage_trace():
    # device 0: distillation 0..30 (10 ns outside the window), training
    # 30..50, the kernel 50..55, a carry copy 60..70; device 1: training
    # 10..50.  Window 10..100 ns, 2 rounds.
    dev0 = [_op("f.1", "fusion", 0, 30), _op("f.2", "fusion", 30, 50),
            _op("fused_round.3", "custom-call", 50, 55), _op("copy.4", "copy", 60, 70)]
    dev1 = [_op("f.2", "fusion", 10, 50)]
    return tr.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                    [("call", 10, 100)], (10, 100))


def test_stage_ms_averages_over_devices_and_clips_to_the_window():
    ms = stages.stage_ms(_stage_trace(), 2, STAGE_OF)
    # ns summed over both devices
    summed = {"client_distill": 20, "local_train": 20 + 40, "aggregate": 5,
              stages.UNSCOPED: 10}
    for k in stages.STAGES + (stages.UNSCOPED,):
        assert ms[k] == pytest.approx(summed.get(k, 0) / 2 / 2 * 1e-6), k


def test_stage_identity_sums_to_the_window_op_time():
    t = _stage_trace()
    ms = stages.stage_ms(t, 2, STAGE_OF)
    op_s, _ = tr.op_time_s(t, lambda _: True)
    assert sum(ms.values()) * 2 * 1e-3 == pytest.approx(op_s / len(t.devices))
    # an op the map does not know is unscoped
    ms = stages.stage_ms(t, 2, {"f.1": "client_distill"})
    assert sum(ms.values()) * 2 * 1e-3 == pytest.approx(op_s / len(t.devices))
    assert ms[stages.UNSCOPED] == pytest.approx((20 + 40 + 5 + 10) / 2 / 2 * 1e-6)


def test_stage_ms_is_none_for_a_program_without_scopes():
    assert stages.stage_ms(_trace(), 20, {}) is None
    assert stages.stage_ms(_stage_trace(), 2, {"copy.4": stages.UNSCOPED}) is None
    assert stages.stage_ms(_stage_trace(), 0, STAGE_OF) is None


SPANS = [("engine.run", 0, 50), ("engine.prepare", 0, 5),
         ("engine.dispatch", 5, 10), ("engine.wait", 10, 45),
         ("engine.finish", 45, 50),
         ("engine.run", 60, 110), ("engine.prepare", 60, 65),
         ("engine.dispatch", 65, 70), ("engine.wait", 70, 105),
         ("engine.finish", 105, 110)]


def _idle_trace():
    # one device, busy 8..40 and 66..100; window 0..110 ns
    ops = [_op("f.1", "fusion", 8, 40), _op("f.2", "fusion", 66, 100)]
    return tr.Trace({"/device:TPU:0": ops}, [], (0, 110))


def test_idle_goes_to_the_innermost_host_span():
    idle = stages.idle_seconds(_idle_trace(), SPANS)
    want = {"engine.prepare": 5 + 5, "engine.dispatch": 3 + 1,
            "engine.wait": 5 + 5, "engine.finish": 5 + 5,
            "engine.run": 0, stages.OUTSIDE: 10}
    assert idle == {k: pytest.approx(v * 1e-9) for k, v in want.items()}


def test_idle_identity_parts_sum_to_idle_share():
    t = _idle_trace()
    share = {k: 100 * v / t.window_s for k, v in stages.idle_seconds(t, SPANS).items()}
    total = 100 * tr.idle_share(t)
    assert sum(share.values()) == pytest.approx(total)
    parts = sum(share[n] for n in ("engine.prepare", "engine.dispatch", "engine.finish"))
    assert parts <= total
    rest = total - parts - share["engine.wait"] - share["engine.run"]
    assert rest == pytest.approx(share[stages.OUTSIDE], abs=0.1)


def test_idle_without_program_spans_is_all_outside():
    t = _idle_trace()
    assert stages.idle_seconds(t, []) == {
        stages.OUTSIDE: pytest.approx(t.window_s * tr.idle_share(t))}


def test_wait_lag_from_the_last_device_op_of_each_call():
    lags = stages.wait_lag_ms(_idle_trace(), SPANS)
    assert lags == [pytest.approx(5e-6), pytest.approx(5e-6)]
    # an op the next call's prepare runs does not count for this call;
    # a device op still running when the wait ended gives a negative lag
    t = _idle_trace()
    t.devices["/device:TPU:0"] += [_op("iota.5", "iota", 55, 56),
                                   _op("f.3", "fusion", 104, 107)]
    assert stages.wait_lag_ms(t, SPANS) == [pytest.approx(5e-6),
                                            pytest.approx(-2e-6)]


def test_accepted_readers_pinned_on_the_synthetic_trace():
    cell = spec.load_cell("mnist-iid-scan-full")
    peak = spec.peaks("TPU v5 lite")
    rec = {"trace": _trace(), "config": cell.config, "traffic": cell.traffic,
           "calls": 1, "rounds": 20, "chips": 1, "peak": peak}
    assert spec.metric_reader("idle_share")(rec) == pytest.approx(40.0)
    flops = cost.call_flops(cell.config, cell.traffic)
    assert spec.metric_reader("mfu.round")(rec) == pytest.approx(
        100 * flops / (100e-9 * peak["bf16_flops_per_s"]))
    need = cost.fused_round_cost(cell.config, cell.traffic)
    assert spec.metric_reader("roofline.fused_round")(rec) == pytest.approx(
        100 * need["bytes"] / peak["hbm_bytes_per_s"] * 20 / 10e-9)

"""CPU tests of the benchmark: trace reduction, cost counts, loading by
name, the refusal without a chip, and the correctness comparison (the
program agrees with the plain reference, the bfloat16 control and the
planted faults do not).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import compare, cost, spec
from chipbench import trace as tr

REPO = spec.REPO


# -- trace reduction -----------------------------------------------------

def _op(name, opcode, start, end):
    return (f"%{name} = f32[8,128]{{1,0}} {opcode}(f32[8,128]{{1,0}} %x)", start, end)


def _trace():
    # device 0: two computations overlapping, a kernel, an all-reduce half
    # hidden behind compute; device 1: one op.  Window 0..100 ns.
    dev0 = [_op("fusion.1", "fusion", 0, 20), _op("fusion.2", "fusion", 10, 30),
            _op("fused_round.3", "custom-call", 40, 50),
            _op("all-reduce.4", "all-reduce", 45, 70)]
    dev1 = [_op("fusion.1", "fusion", 0, 60)]
    spans = [("call", 0, 55), ("call", 60, 100)]
    return tr.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, spans, (0, 100))


def test_busy_union_and_idle_share():
    t = _trace()
    assert tr.union([(0, 20), (10, 30), (40, 50), (45, 70)]) == [(0, 30), (40, 70)]
    # device 0 busy 60 ns, device 1 busy 60 ns, of 100
    assert tr.busy_s(t) == pytest.approx(60e-9)
    assert tr.idle_share(t) == pytest.approx(0.4)


def test_kernel_time_by_instruction_name():
    t = _trace()
    secs, n = tr.op_time_s(t, lambda text: tr.op_name(text).startswith("fused_round"))
    assert (secs, n) == (pytest.approx(10e-9), 1)
    assert tr.opcode(t.devices["/device:TPU:0"][2][0]) == "custom-call"


def test_interval_subtraction_and_clipping():
    assert tr.subtract([(0, 100)], [(10, 30), (90, 120)]) == [(0, 10), (30, 90)]
    assert tr.clip([(-5, 5), (50, 60), (95, 130)], (0, 100)) == [(0, 5), (50, 60), (95, 100)]
    assert tr.length([(0, 10), (30, 90)]) == 70


def test_idle_gaps_named_by_host_span():
    gaps = tr.idle_gaps(_trace())
    assert gaps[0] == ["call", pytest.approx(30e-9)]       # 70..100, inside a call
    assert ["call", pytest.approx(10e-9)] in gaps          # 30..40
    top = tr.top_ops(_trace(), 2)
    assert top[0][0] == "fusion.1 fusion"


def test_metric_readers_on_a_synthesized_trace():
    cell = spec.load_cell("mnist-iid-scan-full")
    rec = {"trace": _trace(), "config": cell.config, "traffic": cell.traffic,
           "calls": 1, "rounds": 20, "chips": 1, "peak": spec.peaks("TPU v5 lite")}
    assert spec.metric_reader("idle_share")(rec) == pytest.approx(40.0)
    need = cost.fused_round_cost(cell.config, cell.traffic)
    least = need["bytes"] / 819e9 * 20
    assert spec.metric_reader("roofline.fused_round")(rec) == pytest.approx(100 * least / 10e-9)
    rec["trace"] = tr.Trace({"/device:TPU:0": [_op("f", "fusion", 0, 5)]}, [], (0, 10))
    assert spec.metric_reader("roofline.fused_round")(rec) is None


def test_collective_ms_counts_only_exposed_collective_time():
    # device 0: an async all-reduce pair around a fusion that hides all of
    # the start's interval but 2 ns and the done's but 5 ns; a plain
    # all-gather, alone, 4 ns; device 1: an all-reduce half under a fusion.
    # Window 0..100 ns, 2 rounds.
    dev0 = [_op("all-reduce-start.1", "all-reduce-start", 8, 14),
            _op("fusion.2", "fusion", 10, 40),
            _op("all-reduce-done.1", "all-reduce-done", 35, 45),
            _op("all-gather.3", "all-gather", 50, 54),
            _op("fusion.4", "fusion", 60, 90)]
    dev1 = [_op("fusion.1", "fusion", 0, 20), _op("all-reduce.5", "all-reduce", 10, 30)]
    rec = {"trace": tr.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, [], (0, 100)),
           "rounds": 2}
    read = spec.metric_reader("collective_ms.shard")
    # (2 + 5 + 4) ns on device 0, 10 ns on device 1: their mean over 2 rounds
    assert read(rec) == pytest.approx((11 + 10) / 2 / 2 * 1e-6)
    # a collective outside the window counts nothing; fusions alone give none
    rec["trace"] = tr.Trace({"/device:TPU:0": dev0}, [], (60, 100))
    assert read(rec) == 0.0
    rec["trace"] = tr.Trace({"/device:TPU:0": [_op("fusion.1", "fusion", 0, 9)]}, [], (0, 10))
    assert read(rec) is None


# -- cost, by hand ---------------------------------------------------------

def test_cost_of_the_2nn_by_hand():
    c = spec.load_cell("mnist-iid-scan-full").config
    mlp = spec.part("family", c["family"])
    assert mlp.dims(c) == [784, 200, 200, 10]
    assert mlp.param_count(c) == 199_210                # FedAvg's MNIST 2NN
    macs = 784 * 200 + 200 * 200 + 200 * 10             # 198,800
    assert mlp.forward_flops(c) == 2 * macs
    # forward + weight gradients + input gradients of layers 2 and 3
    assert mlp.train_step_flops(c) == 2 * macs + 2 * macs + 2 * (200 * 200 + 200 * 10)


def _files(config: str, traffic: str):
    """A configuration and a traffic mix by file name, benchmarked or not."""
    c = json.loads((REPO / "chipbench/configs" / f"{config}.json").read_text())
    t = json.loads((REPO / "chipbench/traffic" / f"{traffic}.json").read_text())
    return c, t


def _c10():
    """The configuration under FedAvg's C=0.1: 10 of 100 clients a round."""
    return _files("fedavg-mnist-iid-2nn", "scan-c10")


def test_round_cost_of_both_configurations_by_hand():
    full = spec.load_cell("mnist-iid-scan-full")
    step, fwd = 879_200, 397_600
    # 100 clients x 5 local steps x 450 train rows (90% of 500), 5 distill
    # steps on 1,000 rows, 1,000 predictions; the server's 5 distill steps
    want = 100 * 5 * 450 * step + 100 * 5 * 1000 * step + 100 * 1000 * fwd + 5 * 1000 * step
    assert cost.round_flops(full.config, full.traffic, eval_round=False) == want
    # eval: server and client accuracy on 10,000 test rows, 5,000 validation
    # rows, 100 clients and the server on the 1,000-row public validation split
    ev = (10_000 + 10_000 + 5_000 + 100 * 1000 + 1000) * fwd
    assert cost.round_flops(full.config, full.traffic, eval_round=True) == want + ev
    assert cost.call_flops(full.config, full.traffic) == 18 * want + 2 * (want + ev)

    c10_config, c10_traffic = _c10()
    want10 = 10 * 5 * 450 * step + 10 * 5 * 1000 * step + 10 * 1000 * fwd + 5 * 1000 * step
    assert cost.round_flops(c10_config, c10_traffic, eval_round=False) == want10

    # a cross-device population of LEAF FEMNIST's shape (arXiv 1812.01097):
    # 805,263 samples over 3,548 clients, 62 classes, 100 clients a round
    fem_config, fem_traffic = _files("leaf-femnist-2nn", "shard-m100")
    assert {k: v for k, v in fem_config.items() if v != c10_config.get(k)}.keys() == {
        "name", "source", "deployment", "assumed", "reduced", "reduced_why",
        "n_clients", "n_classes", "private_size"}
    assert (fem_config["n_clients"], fem_config["n_classes"],
            fem_config["private_size"]) == (3548, 62, 805_263)
    assert cost.participants(fem_config, fem_traffic) == 100
    assert spec.part("family", "mlp").param_count(fem_config) == 209_662
    rows = spec.part("partition", "uniform").rows(805_263, 3548)
    assert rows.sum() == 805_263 and set(rows) == {226, 227}
    assert set(cost.train_rows(rows)) == {203, 204}            # int(0.9 n)
    fstep = 2 * (156_800 + 40_000 + 12_400) * 2 + 2 * (40_000 + 12_400)
    got = cost.round_flops(fem_config, fem_traffic, eval_round=False)
    mean_train = cost.train_rows(rows).mean()
    want_f = (100 * 5 * mean_train * fstep + 100 * 5 * 1000 * fstep
              + 100 * 1000 * 2 * 209_200 + 5 * 1000 * fstep)
    assert got == pytest.approx(want_f, rel=1e-12)


def test_fused_round_bytes_count_the_unpadded_stack():
    need = cost.fused_round_cost(*_c10())
    # 10 participants x 1,000 rows x 10 classes, their weights, base and output
    assert need["bytes"] == 4 * (10 * 1000 * 10 + 10 + 2 * 1000 * 10)


# -- loading by name -------------------------------------------------------

def test_every_config_mix_metric_and_cell_loads_by_name():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert set(cell.limits) == set(compare.NAMES)
        assert {m["name"] for m in cell.end_to_end} == {"round_ms", "peak_hbm_gb", "setup_s"}
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
        assert cell.config["name"] == w["config"]
        parts = spec.parts(cell.config)
        assert callable(parts["family"].logits) and callable(parts["inputs"].make)
        assert callable(parts["partition"].shards)
    for c in bench["configs"]:
        assert set(c["reduced"]) <= set(json.loads((REPO / c["file"]).read_text()))
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


def _copy_bench(dst: Path) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return dst


def test_a_cell_from_new_files_only(tmp_path):
    """A later PR adds a configuration, a mix, limits, a metric reader and
    a cell by adding files and entries; no file that exists changes."""
    root = _copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*") if p.is_file()}
    cfg = json.loads((root / "chipbench/configs/fedavg-mnist-iid-2nn.json").read_text())
    cfg.update(name="new-model", n_clients=50)
    (root / "chipbench/configs/new-model.json").write_text(json.dumps(cfg))
    (root / "chipbench/traffic/new-mix.json").write_text(json.dumps(
        {"engine": "scan", "participants": 5, "rounds_per_call": 10, "eval_every": 10,
         "fused_round": False}))
    (root / "chipbench/limits/new-cell.json").write_text(json.dumps(
        dict.fromkeys(compare.NAMES, 0.0)))
    (root / "chipbench/metrics/new_metric.py").write_text("def read(rec):\n    return 1.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "new-model", "source": "x", "reduced": [], "why": "x",
                             "file": "chipbench/configs/new-model.json"})
    bench["workloads"].append({"name": "new-cell", "config": "new-model",
                               "traffic": "new-mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%", "better": "higher",
                               "source": "device_trace", "layer": "device",
                               "moves": "round_ms", "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("new-cell", root)
    assert cell.config["n_clients"] == 50 and cell.traffic["participants"] == 5
    assert [m["name"] for m in cell.per_layer] == ["idle_share", "mfu.round", "new_metric"]
    assert spec.metric_reader("new_metric", root)({}) == 1.0
    assert cost.participants(cell.config, cell.traffic) == 5
    for p, data in before.items():
        assert p.read_bytes() == data, p


# a client model over int32 token ids: the mean of their embedding rows,
# then one linear layer; ``SEEN`` keeps the dtypes ``logits`` was given
BAG_FAMILY = '''
import jax
import jax.numpy as jnp

SEEN = set()


def init(key, config, dtype):
    k1, k2 = jax.random.split(key)
    v, w, n = config["vocab"], config["width"], config["n_classes"]
    return {"emb": (jax.random.normal(k1, (v, w)) * 0.5).astype(dtype),
            "w": (jax.random.normal(k2, (w, n)) * 0.5).astype(dtype),
            "b": jnp.zeros((n,), dtype)}


def logits(p, x):
    if not jnp.issubdtype(x.dtype, jnp.integer):
        raise TypeError(f"token ids arrived as {x.dtype}")
    SEEN.add(str(x.dtype))
    return jnp.mean(p["emb"][x], axis=-2) @ p["w"] + p["b"]


def forward_flops(config):
    return 2 * config["width"] * config["n_classes"]


def train_step_flops(config):
    return 3 * forward_flops(config)
'''

# token ids whose range says the class
TOKEN_INPUTS = '''
import numpy as np


def n_test(config):
    return max(config["private_size"] // 5, 20)


def make(config, seed):
    rng = np.random.default_rng(seed)
    n, per = config["n_classes"], config["vocab"] // config["n_classes"]

    def draw(count):
        y = rng.integers(0, n, size=count).astype(np.int32)
        x = rng.integers(0, per, size=(count, config["seq_len"])) + y[:, None] * per
        return x.astype(np.int32), y

    xp, yp = draw(config["private_size"])
    xu, _ = draw(config["public_size"])
    xt, yt = draw(n_test(config))
    return {"x_private": xp, "y_private": yp, "x_public": xu, "x_test": xt, "y_test": yt}
'''

# client k holds a share of the samples in proportion to k + 1
UNEVEN_PARTITION = '''
import numpy as np


def rows(n_samples, n_clients):
    w = np.arange(1, n_clients + 1)
    r = (n_samples * w) // w.sum()
    r[-1] += n_samples - r.sum()
    return r.astype(np.int64)


def shards(x, y, n_clients):
    r = rows(len(y), n_clients)
    n_max = int(r.max())
    xs = np.zeros((n_clients, n_max) + x.shape[1:], x.dtype)
    ys = np.zeros((n_clients, n_max), y.dtype)
    valid = np.zeros((n_clients, n_max), bool)
    lo = 0
    for k, n in enumerate(r):
        xs[k, :n], ys[k, :n], valid[k, :n] = x[lo:lo + n], y[lo:lo + n], True
        lo += n
    return xs, ys, valid
'''


def test_a_model_from_new_files_only(tmp_path):
    """A later PR adds a client family, an inputs generator and a partition
    by adding files: the reference trains the new model on integer token
    ids, the FLOP count takes the family's per-sample counts and the
    partition's rows, and no file that exists changes."""
    from chipbench import reference, system
    root = _copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*") if p.is_file()}
    (root / "chipbench/families/bag.py").write_text(BAG_FAMILY)
    (root / "chipbench/inputs/tokens.py").write_text(TOKEN_INPUTS)
    (root / "chipbench/partitions/uneven.py").write_text(UNEVEN_PARTITION)
    cfg = json.loads((root / "chipbench/configs/fedavg-mnist-iid-2nn.json").read_text())
    for key in ("dim", "hidden", "mlp_depth", "cluster_scale", "noise"):
        del cfg[key]
    cfg.update(name="bag-tokens", family="bag", inputs="tokens", partition="uneven",
               vocab=64, width=16, seq_len=12, n_classes=4, n_clients=6,
               private_size=600, public_size=200, public_per_round=50)
    (root / "chipbench/configs/bag-tokens.json").write_text(json.dumps(cfg))
    (root / "chipbench/traffic/bag-mix.json").write_text(json.dumps(
        {"engine": "scan", "participants": 3, "rounds_per_call": 2, "eval_every": 2,
         "fused_round": False}))
    (root / "chipbench/limits/bag-cell.json").write_text(json.dumps(
        dict.fromkeys(compare.NAMES, 0.0)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "bag-tokens", "source": "x", "reduced": [], "why": "x",
                             "file": "chipbench/configs/bag-tokens.json"})
    bench["workloads"].append({"name": "bag-cell", "config": "bag-tokens",
                               "traffic": "bag-mix", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("bag-cell", root)
    ref = reference.Reference(system.reference_setting(cell.config, cell.traffic),
                              seed=7, root=root)
    assert ref.xs.dtype == np.int32 and ref.x_pub.dtype == np.int32
    res = ref.run(2)
    for change in (res.server_change, res.client_change):
        assert set(change) == {"emb", "w", "b"}
        assert all(np.isfinite(v) and v > 0 for v in change.values()), change
    assert spec.part("family", "bag", root).SEEN == {"int32"}

    # 3 of 6 clients, 5 local steps on 90% of each client's uneven rows,
    # 5 distillation steps on 50 public rows, 50 predictions, the server's
    # 5 steps; evaluation on the inputs file's 120 test rows
    rows = np.array([28, 57, 85, 114, 142, 174])
    assert list(spec.part("partition", "uneven", root).rows(600, 6)) == list(rows)
    train = np.maximum((rows * 0.9).astype(int), 1)
    fwd, step = 2 * 16 * 4, 3 * 2 * 16 * 4
    want = 3 * 5 * train.mean() * step + 3 * 5 * 50 * step + 3 * 50 * fwd + 5 * 50 * step
    got = cost.round_flops(cell.config, cell.traffic, eval_round=False, root=root)
    assert got == pytest.approx(want, rel=1e-12)
    ev = (120 + 120 + (600 - train.sum()) + 6 * 20 + 20) * fwd
    got = cost.round_flops(cell.config, cell.traffic, eval_round=True, root=root)
    assert got == pytest.approx(want + ev, rel=1e-12)
    # a call of 2 rounds evaluates on its second; the run's readers get the root
    call = cost.call_flops(cell.config, cell.traffic, root=root)
    assert call == pytest.approx(2 * want + ev, rel=1e-12)
    peak = spec.peaks("TPU v5 lite", root)
    rec = {"trace": _trace(), "config": cell.config, "traffic": cell.traffic, "calls": 1,
           "rounds": 2, "chips": 1, "peak": peak, "root": root}
    assert spec.metric_reader("mfu.round", root)(rec) == pytest.approx(
        100 * call / (100e-9 * peak["bf16_flops_per_s"]))
    for p, data in before.items():
        assert p.read_bytes() == data, p

    unknown = dict(cell.config, family="no-such-family")
    with pytest.raises(FileNotFoundError, match=str(root / "chipbench/families/no-such-family.py")):
        reference.Reference(system.reference_setting(unknown, cell.traffic), seed=7, root=root)
    lacking = {k: v for k, v in cell.config.items() if k != "inputs"}
    (root / "chipbench/configs/bag-tokens.json").write_text(json.dumps(lacking))
    with pytest.raises(KeyError, match="'inputs'"):
        spec.load_cell("bag-cell", root)


# -- no chip ---------------------------------------------------------------

def test_no_tpu_exits_non_zero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    p = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload",
                        "mnist-iid-scan-full", "--seed", "2147483701", "--seconds", "1"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


# -- correctness: program, control, faults -----------------------------------

TINY = dict(n_clients=8, private_size=2400, public_size=1000, public_per_round=100)


# cells whose files are in place but that ``BENCHMARK.json`` holds back
# (PERF.md, section 7): name -> (configuration, traffic mix, chips)
HELD_BACK = {"femnist-shard-4chip": ("leaf-femnist-2nn", "shard-m100", 4)}


def _load_cell(name: str) -> spec.Cell:
    if name not in HELD_BACK:
        return spec.load_cell(name)
    config, traffic, chips = HELD_BACK[name]
    c, t = _files(config, traffic)
    limits = json.loads((REPO / "chipbench/limits" / f"{name}.json").read_text())
    return spec.Cell(name=name, chips=chips, config_name=config, config=c,
                     traffic_name=traffic, traffic=t, limits=limits, end_to_end=[],
                     per_layer=[])


def _tiny_cell(name: str, **traffic) -> spec.Cell:
    """The named cell at a size the CPU holds: its widths, codec and limits,
    with a small population and few rounds per call."""
    cell = _load_cell(name)
    cell.config = dict(cell.config, **TINY)
    cell.traffic = dict(cell.traffic, rounds_per_call=4, eval_every=2, **traffic)
    if cell.traffic["participants"] != "all":
        cell.traffic["participants"] = 3
    return cell


def _run(cell, hook=None):
    from chipbench import run
    out, lines = run.measure(cell, 2_147_483_999, 0.2, False, t_start=time.perf_counter(),
                             require_chip=False, engine_hook=hook)
    return out


CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]] + list(HELD_BACK)
SHARD_CELLS = [n for n in CELLS if _load_cell(n).traffic["engine"] == "shard"]


@pytest.mark.parametrize("name", CELLS)
def test_program_matches_the_reference(name):
    out = _run(_tiny_cell(name))
    assert out["correct"], out["checks"]
    assert out["checks"]["ledger"]["value"] == 0.0
    assert out["checks"]["cache_state"]["value"] == 0.0


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_fails_the_limits(name):
    from chipbench import calibrate, run
    cell = _tiny_cell(name)
    ref = run.reference_result(cell, 5)
    ctl = run.reference_result(cell, 5, dtype="bfloat16")
    ok, table, _ = compare.judge(compare.readings(calibrate._ref_as_record(ctl), ref),
                                 cell.limits)
    assert not ok, table


def _returns_carry(inner):
    def body(carry, *args):
        _, ys = inner(carry, *args)
        return carry, ys
    return body


def _state_unchanged(engine):
    """Every round hands back the state it was given: the round body, the
    scan engine's or the shard engine's, returns its carry."""
    for name in ("_round_device", "_round_device_sharded"):
        if hasattr(engine, name):
            setattr(engine, name, _returns_carry(getattr(engine, name)))


def _half_batch(engine):
    """Aggregation over the participants among the first half of the
    clients only (of each shard's clients, on the shard engine), in each
    of the strategy's aggregation steps: SCARLET's single-device
    ``aggregate_masked(_fused)`` do not call the partial steps that the
    shard engine calls before its psum."""
    s = engine.strategy

    def halve(part):
        k = part.shape[0]
        return part * (np.arange(k) < k // 2)
    fused, plain = s.aggregate_masked_fused, s.aggregate_masked
    pfused, pplain = s.partial_aggregate_fused, s.partial_aggregate
    s.aggregate_masked_fused = lambda z, part, spec_, base, t: fused(z, halve(part), spec_,
                                                                     base, t)
    s.aggregate_masked = lambda z, part, um, t: plain(z, halve(part), um, t)
    s.partial_aggregate_fused = lambda z, part, spec_, base, t: pfused(z, halve(part), spec_,
                                                                       base, t)
    s.partial_aggregate = lambda z, part, um, t: pplain(z, halve(part), um, t)


def _answer_altered(engine):
    """The first round's uplink charged for one participant fewer, where
    the ledger is made."""
    from repro.core import comm
    run_ = engine.run
    n = max(round(engine.cfg.participation * engine.cfg.n_clients), 1)

    def run(rounds=None):
        hist = run_(rounds)
        first = hist.ledger.rounds[0]
        hist.ledger.rounds[0] = comm.RoundCost(first.uplink * (n - 1) / n, first.downlink)
        return hist
    engine.run = run


def _no_exchange(engine):
    """Each shard sharpens its own participants' partial sums: the psum of
    the aggregation's moments between chips is left out.  The program's
    other psums stay."""
    import jax
    psum, program = jax.lax.psum, engine._program

    def local(x, axis_name, **kw):
        return x if isinstance(x, dict) and "zsum" in x else psum(x, axis_name, **kw)

    def patched():
        fn = program()

        def call(*args):
            jax.lax.psum = local  # seen while the first call traces
            try:
                return fn(*args)
            finally:
                jax.lax.psum = psum
        return call
    engine._program = patched


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}
SHARD_FAULTS = {"no_exchange": _no_exchange}


@pytest.mark.parametrize("name,fault", [(n, f) for n in CELLS for f in FAULTS]
                         + [(n, f) for n in SHARD_CELLS for f in SHARD_FAULTS])
def test_planted_fault_is_not_correct(name, fault):
    out = _run(_tiny_cell(name), {**FAULTS, **SHARD_FAULTS}[fault])
    assert not out["correct"], out["checks"]
    if fault == "state_unchanged":
        assert out["checks"]["client_step"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", SHARD_CELLS)
def test_shard_engine_mesh_is_the_cells_devices(name):
    """The shard engine partitions its clients over exactly the devices it
    is given, as many as the cell's chips, not over all of JAX's."""
    import jax
    from chipbench import system
    cell = _tiny_cell(name)
    devs = jax.devices()[-cell.chips:]
    assert devs != jax.devices()[:cell.chips]
    engine = system.build_engine(cell.config, cell.traffic, 7, devs)
    assert list(engine.mesh.devices.flat) == devs
    assert engine.mesh.axis_names == ("data",) and engine.n_shards == cell.chips
    engine.run(1)
    (stack,) = engine.client_params
    assert {d for leaf in stack.values() for d in leaf.devices()} == set(devs)

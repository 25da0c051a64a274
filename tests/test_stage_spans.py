"""Stage scopes and host spans on the profiler's clock (``repro.obs.trace``).

Every scan-family round program names each stage of a round
(``fl.<stage>`` in its ops' metadata), ``run()`` marks its host work with
``engine.*`` spans nested in one ``engine.run``, and ``SpanTracer`` spans
reach a profiler trace once jax is loaded (and never load jax
themselves)."""
import glob
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import repro
from repro.fl.async_engine import AsyncFederatedDistillation
from repro.fl.config import FLConfig
from repro.fl.scan_engine import ScannedFederatedDistillation
from repro.fl.shard_engine import ShardedFederatedDistillation
from repro.fl.strategies import STRATEGIES
from repro.obs import SpanTracer
from repro.obs.trace import STAGES, stage

CFG = dict(n_clients=8, rounds=2, public_size=32, public_per_round=8,
           n_classes=4, dim=8, hidden=8, private_size=32, local_steps=1,
           distill_steps=1, seed=0, uplink_codec="cache_delta+quant8")

ENGINES = {
    "scan": lambda cfg, s: ScannedFederatedDistillation(cfg, s,
                                                        cache_duration=2),
    "async": lambda cfg, s: AsyncFederatedDistillation(cfg, s,
                                                       cache_duration=2),
    "shard": lambda cfg, s: ShardedFederatedDistillation(
        cfg, s, cache_duration=2, mesh="2x4"),
}
RUN_CHILDREN = ["engine.prepare", "engine.dispatch", "engine.wait",
                "engine.finish"]


def _engine(name, fused=False):
    return ENGINES[name](FLConfig(fused_round=fused, **CFG),
                         STRATEGIES["scarlet"](beta=1.5))


def _stages_named(hlo_text):
    """The ``fl.<stage>`` components of every op path in a program."""
    return {part[3:] for path in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in path.split("/") if part.startswith("fl.")}


def _host_events(logdir):
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    prof = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in prof.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("fused", [False, True], ids=["per_op", "fused"])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_round_program_names_every_stage(engine, fused):
    text = _engine(engine, fused).aot_lower(1).compile().as_text()
    assert _stages_named(text) == set(STAGES)


def test_stage_rejects_unknown_names():
    with pytest.raises(ValueError, match="unknown stage"):
        stage("codec")


def test_run_spans_nest_in_order_on_the_profiler_clock(tmp_path):
    eng = _engine("scan")
    eng.run(2)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        eng.run(3)
    spans = [e for e in _host_events(tmp_path) if e[0].startswith("engine.")]
    (run,) = [e for e in spans if e[0] == "engine.run"]
    assert (run[3]["first_round"], run[3]["rounds"]) == (3, 3)
    kids = sorted((e for e in spans if e[0] != "engine.run"),
                  key=lambda e: e[1])
    assert [k[0] for k in kids] == RUN_CHILDREN
    assert all(run[1] <= k[1] <= k[2] <= run[2] for k in kids)
    assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    # the stacked per-round outputs read back: per round six float32
    # scalars, one cohort's accuracy (float32) and one bool
    assert kids[-1][3]["bytes"] == 3 * (6 * 4 + 4 + 1)


def test_span_tracer_spans_reach_the_profiler(tmp_path):
    tracer = SpanTracer("t")
    with jax.profiler.trace(str(tmp_path)):
        with tracer.span("outer", engine="scan"):
            with tracer.span("inner"):
                pass
    names = [e[0] for e in _host_events(tmp_path)]
    assert "outer" in names and "inner" in names
    assert [s.name for s in tracer.spans] == ["inner", "outer"]


def test_span_tracer_leaves_jax_unloaded():
    code = ("import sys\n"
            "from repro.obs import SpanTracer\n"
            "t = SpanTracer('launch')\n"
            "with t.span('setup'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "assert [s.name for s in t.spans] == ['setup']\n")
    src = str(Path(repro.__file__).resolve().parents[1])
    env = dict(os.environ, PYTHONPATH=src)
    subprocess.run([sys.executable, "-c", code], check=True, env=env)

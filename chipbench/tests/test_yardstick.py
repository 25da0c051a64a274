"""The yardstick of ``mnist-iid-scan-full`` is pinned: the plain
reference's result at the tests' size, the cell's FLOP and byte counts
and the program's ``FLConfig`` equal, exactly, what the harness gave
before a configuration named its family, inputs and partition as files
(``yardstick/``).

XLA on the CPU splits its reductions by the number of cores it may use
and by the host devices it is told to make, and a persistent compilation
cache hands back programs split for another process.  So the reference
runs in a child process held to one core, with neither set, as it did
when the fixture was made.
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np

from chipbench import cost, spec, system

HERE = spec.REPO / "chipbench" / "tests" / "yardstick"
PINNED = json.loads((HERE / "yardstick.json").read_text())
CELL = spec.load_cell("mnist-iid-scan-full")

# the child holds itself to one core before it loads XLA
REFERENCE = """
import json, os, sys
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
import numpy as np
from chipbench import spec, system
from chipbench.reference import Reference
pin = json.loads(sys.argv[1])
cell = spec.load_cell("mnist-iid-scan-full")
config = dict(cell.config, **pin["tiny"])
traffic = dict(cell.traffic, rounds_per_call=pin["rounds"], eval_every=pin["eval_every"])
res = Reference(system.reference_setting(config, traffic),
                system.engine_seed(pin["seed"])).run(pin["rounds"])
np.savez(sys.argv[2], cache_values=res.cache_values, cache_ts=res.cache_ts,
         cache_present=res.cache_present)
print(json.dumps({"uplink": res.uplink, "downlink": res.downlink,
                  "evals": {str(t): row for t, row in res.evals.items()},
                  "server_change": res.server_change,
                  "client_change": res.client_change}))
"""


def test_reference_result_is_the_pinned_one_bit_for_bit(tmp_path):
    pin = PINNED["reference_tiny"]
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH=os.pathsep.join([str(spec.REPO), str(spec.REPO / "src")]))
    arrays = tmp_path / "cache.npz"
    p = subprocess.run([sys.executable, "-c", REFERENCE, json.dumps(pin), str(arrays)],
                       cwd=spec.REPO, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    for key in ("uplink", "downlink", "evals", "server_change", "client_change"):
        assert got[key] == pin[key], key
    want, have = np.load(HERE / "reference-tiny.npz"), np.load(arrays)
    for key in ("cache_values", "cache_ts", "cache_present"):
        assert have[key].dtype == want[key].dtype, key
        assert np.array_equal(have[key], want[key]), key


def test_flop_and_byte_counts_are_the_pinned_ones():
    pin = PINNED["counts_full"]
    c, t = CELL.config, CELL.traffic
    assert cost.round_flops(c, t, eval_round=True) == pin["round_flops"]["eval_round"]
    assert cost.round_flops(c, t, eval_round=False) == pin["round_flops"]["no_eval"]
    assert (cost.round_flops(c, t, eval_round=False, distill=False)
            == pin["round_flops"]["first_round"])
    assert cost.call_flops(c, t) == pin["call_flops"]
    assert cost.fused_round_cost(c, t) == pin["fused_round_cost"]
    assert spec.metric_module("roofline.mlp_distill").need(c, t) == pin["mlp_distill"]


def test_engine_config_is_the_pinned_one_field_for_field():
    pin = PINNED["fl_config"]
    cfg = system.engine_config(CELL.config, CELL.traffic, pin["seed"])
    assert dataclasses.asdict(cfg) == pin["fields"]

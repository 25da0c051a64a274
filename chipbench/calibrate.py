"""Readings behind a cell's correctness limits.

    python3 -m chipbench.calibrate --workload <cell> --seeds 1 2 ... --control-seeds 1 2 3

For every seed the program makes the cell's first call (as a run's
set-up does) and the float32 reference replays it: those readings give
each number's lower end.  On the control seeds the bfloat16 reference
stands in the program's place against the same float32 reference: its
readings give the upper end.  One JSON line per reading; no window is
measured.  Limits are set from these by hand (``chipbench/limits``).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from typing import List, Optional

from chipbench import compare, spec


def _ref_as_record(res):
    return {"uplink": res.uplink, "downlink": res.downlink, "evals": res.evals,
            "server_change": res.server_change, "client_change": res.client_change,
            "cache_values": res.cache_values, "cache_ts": res.cache_ts,
            "cache_present": res.cache_present}


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = p.parse_args(argv)
    sys.path.insert(0, str(spec.REPO / "src"))
    from chipbench import run, system

    cell = spec.load_cell(args.workload)
    devs = run.devices_for(cell.chips, require_chip=True)
    run._enable_compile_cache()
    r = int(cell.traffic["rounds_per_call"])
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        row = {"cell": cell.name, "seed": seed}
        if seed in args.seeds:
            t0 = time.perf_counter()
            engine = system.build_engine(cell.config, cell.traffic, seed, devs)
            first, _ = run.first_call(engine, r)
            del engine
            gc.collect()
            row["program_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref = run.reference_result(cell, seed)
        row["reference_s"] = time.perf_counter() - t0
        if seed in args.seeds:
            row["program"] = compare.readings(first, ref)
            row["evals"] = {"program": first["evals"], "reference": ref.evals}
        if seed in args.control_seeds:
            gc.collect()  # the float32 reference's device arrays, before the control's
            t0 = time.perf_counter()
            ctl = run.reference_result(cell, seed, dtype="bfloat16")
            row["control_s"] = time.perf_counter() - t0
            row["control"] = compare.readings(_ref_as_record(ctl), ref)
        print(json.dumps(row), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

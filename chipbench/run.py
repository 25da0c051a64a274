"""Run one benchmark cell once.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's engine from the seed, makes its first call,
``run(R)``, and calls again until a call compiles nothing; the window
then makes whole calls of the same ``R`` until ``--seconds`` have passed.
``--trace 0`` prints the end-to-end metrics, ``--trace 1`` traces the
window with the profiler and prints the per-layer metrics.  Either way,
once the window has closed and the program's state is freed, the plain
reference replays the first call and ``correct`` says whether the two
agree within the cell's limits.  The last line of standard output is one
JSON object.  Without a TPU, or with fewer chips than the cell asks for,
the run prints no result and exits non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from chipbench import compare, spec

EXIT_NO_CHIP = 3
MAX_WARMUP_CALLS = 3


class NoChip(RuntimeError):
    pass


def devices_for(chips: int, require_chip: bool):
    """The cell's first ``chips`` devices; TPUs unless ``require_chip`` is
    false (the CPU tests)."""
    import jax
    devs = jax.devices()
    if len(devs) < chips or (require_chip and devs[0].platform != "tpu"):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def _enable_compile_cache() -> str:
    import jax
    from repro import compile_cache
    path = compile_cache.enable()
    # every program of the cell, however quick to compile, is kept
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _CompileCounter:
    """Counts backend compilations (a window must have none)."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if "backend_compile" in name:
            self.count += 1


def first_call(engine, rounds: int) -> Tuple[Dict[str, Any], float]:
    """Make the engine's first ``run(rounds)`` call and keep what the
    comparison reads: its ledger and evaluations, the change of every
    parameter leaf over the call, and the cache it left.  Returns the
    record and the seconds spent on the comparison's bookkeeping."""
    from chipbench import system
    t0 = time.perf_counter()
    clients0 = system.client_leaves(engine, copy=True)
    server0 = system.server_leaves(engine)
    bookkeeping = time.perf_counter() - t0
    hist = engine.run(rounds)
    t1 = time.perf_counter()
    rec = system.history_record(hist)
    rec["client_change"] = compare.leaf_change(system.client_leaves(engine), clients0)
    rec["server_change"] = compare.leaf_change(system.server_leaves(engine), server0)
    rec["cache_values"], rec["cache_ts"], rec["cache_present"] = system.cache_arrays(engine)
    del clients0, server0
    return rec, bookkeeping + time.perf_counter() - t1


def reference_result(cell: spec.Cell, seed: int, dtype: str = "float32",
                     root: Path = spec.REPO):
    from chipbench import reference, system
    ref = reference.Reference(system.reference_setting(cell.config, cell.traffic),
                              system.engine_seed(seed), dtype=dtype, root=root)
    return ref.run(cell.traffic["rounds_per_call"])


def measure(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
            t_start: float, require_chip: bool = True, root: Path = spec.REPO,
            engine_hook=None) -> Tuple[Dict[str, Any], List[str]]:
    """One run of one cell; returns the result object and the check lines.
    ``engine_hook(engine)`` lets a test break the timed path underneath."""
    import jax
    from chipbench import system
    from chipbench import trace as tr

    devs = devices_for(cell.chips, require_chip)
    _enable_compile_cache()
    compiles = _CompileCounter()
    r = int(cell.traffic["rounds_per_call"])

    t_build = time.perf_counter()
    engine = system.build_engine(cell.config, cell.traffic, seed, devs)
    if engine_hook is not None:
        engine_hook(engine)
    t_first = time.perf_counter()
    first, bookkeeping = first_call(engine, r)
    t_warm = time.perf_counter()
    # a chained call may differ from the first in what it compiles (the
    # arguments then come from the previous call); warm up until a call
    # compiles nothing
    warmup = 0
    for warmup in range(1, MAX_WARMUP_CALLS + 1):
        before = compiles.count
        engine.run(r)
        if compiles.count == before:
            break
    t_end = time.perf_counter()
    setup_s = t_end - t_start - bookkeeping
    timing = (f"set-up: start {t_build - t_start:.3f} s, build {t_first - t_build:.3f} s, "
              f"first call {t_warm - t_first - bookkeeping:.3f} s (+{bookkeeping:.3f} s "
              f"kept for the check), {warmup} more call(s) {t_end - t_warm:.3f} s")

    logdir = root / ".chipbench_trace" / f"{cell.name}-{seed}"
    if trace:
        shutil.rmtree(logdir, ignore_errors=True)
    prof = jax.profiler.trace(str(logdir)) if trace else contextlib.nullcontext()
    compiles_before = compiles.count
    calls = 0
    with prof:
        with jax.profiler.TraceAnnotation("window"):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("call"):
                    engine.run(r)
                calls += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            t1 = time.perf_counter()
    window_compiles = compiles.count - compiles_before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    del engine
    gc.collect()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        t = tr.load(str(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        rec = {"trace": t, "config": cell.config, "traffic": cell.traffic,
               "calls": calls, "rounds": calls * r, "chips": len(devs),
               "peak": spec.peaks(devs[0].device_kind, root), "root": root}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"], root)(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s(t)
        device["window_s"] = t.window_s
        breakdown = {"device_ops": tr.top_ops(t), "idle_gaps": tr.idle_gaps(t)}
    else:
        values = {"round_ms": 1e3 * (t1 - t0) / (calls * r),
                  "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    t_ref = time.perf_counter()
    ref = reference_result(cell, seed, root=root)
    correct, table, lines = compare.judge(compare.readings(first, ref), cell.limits)
    lines[:0] = [timing, f"window: {calls} calls of {r} rounds in {t1 - t0!r} s, "
                         f"{window_compiles} compilations; set-up {setup_s!r} s; "
                         f"reference {time.perf_counter() - t_ref:.3f} s"]
    out = {"correct": bool(correct), "attempted": calls * r, "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = table
    return out, lines


def main(argv: Optional[List[str]] = None) -> int:
    t_start = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, str(spec.REPO / "src"))
    try:
        cell = spec.load_cell(args.workload)
        out, lines = measure(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    print(f"wall: {time.perf_counter() - t_start:.3f} s from the process's start to the result",
          file=sys.stderr)
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

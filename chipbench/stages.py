"""Device time per round stage, and idle time per host span, from a trace.

The program names each stage of a round body with a ``jax.named_scope``
(``fl.<stage>``) and marks its host work in ``run()`` with ``engine.*``
spans (``jax.profiler.TraceAnnotation``) on the profiler's clock.  This
module reduces a traced window to

- the device time of each stage: an operation belongs to the innermost
  ``fl.<stage>`` component of its op path (``unscoped`` where it has
  none: the scan's carry copies, output stacking, loop control);
- the device's idle time under each host span: an idle instant belongs
  to the innermost ``engine.*`` span open at that instant (``outside``
  where none is: the benchmark's own loop between calls).

On a TPU the op path is in neither the op event's stats nor its
instruction text; the trace keeps it only in the XPlane's per-op
metadata (``tf_op``), which ``jax.profiler.ProfileData`` does not
expose.  So the path comes from the traced program's optimized HLO
(``Compiled.as_text()``, ``metadata={op_name="..."}``), matched to the
trace's ops by instruction name.

    python3 -m chipbench.stages --workload <cell> --seed <n> --seconds <s>

traces one window of the cell on the chip and prints, as one JSON line,
the per-stage and per-span numbers with the identities that check them.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence

from chipbench import trace as tr

# the program's stage names (repro.obs.trace.STAGES), kept here so that
# the yardstick does not move with the program
STAGES = ("client_distill", "local_train", "predict", "aggregate", "cache",
          "server_distill", "eval")
UNSCOPED = "unscoped"
SCOPE_PREFIX = "fl."
SPAN_PREFIX = "engine."
OUTSIDE = "outside"

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"')


def stage_of_path(path: str) -> str:
    """The innermost ``fl.<stage>`` component of an op path."""
    for part in reversed(path.split("/")):
        if part.startswith(SCOPE_PREFIX) and part[len(SCOPE_PREFIX):] in STAGES:
            return part[len(SCOPE_PREFIX):]
    return UNSCOPED


def hlo_stages(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> stage, for every instruction of an optimized
    HLO module whose metadata names an op path."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = stage_of_path(m.group(2))
    return out


def stage_ms(trace: tr.Trace, rounds: int,
             stage_of: Dict[str, str]) -> Optional[Dict[str, float]]:
    """Device ms per round of each stage and of ``unscoped``: the op time
    clipped to the window, averaged over the devices, over the window's
    rounds.  None where no op of the window belongs to a stage (a
    program without scopes)."""
    tot = dict.fromkeys(STAGES + (UNSCOPED,), 0.0)
    for ops in trace.devices.values():
        for text, s, e in ops:
            for a, b in tr.clip([(s, e)], trace.window):
                tot[stage_of.get(tr.op_name(text), UNSCOPED)] += b - a
    if rounds <= 0 or all(tot[s] == 0 for s in STAGES):
        return None
    nd = max(len(trace.devices), 1)
    return {k: v / nd * 1e-6 / rounds for k, v in tot.items()}


# -- host spans ------------------------------------------------------------

def load_spans(logdir: str) -> List[tr.Op]:
    """The ``engine.*`` host events of the newest ``.xplane.pb`` under
    ``logdir``, on the device events' clock, in order of start."""
    import jax

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    spans = []
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return sorted(spans, key=lambda s: s[1])


def _innermost(spans: Sequence[tr.Op], t: float) -> str:
    """The innermost (latest-starting) span open at ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else OUTSIDE


class _Busy:
    """Merged busy intervals with prefix sums: busy time in ``[a, b)``."""

    def __init__(self, intervals: Iterable[tr.Interval]):
        self.iv = tr.union(intervals)
        self.starts = [a for a, _ in self.iv]
        self.cum = [0.0]
        for a, b in self.iv:
            self.cum.append(self.cum[-1] + b - a)

    def _upto(self, x: float) -> float:
        i = bisect.bisect_right(self.starts, x)
        if i == 0:
            return 0.0
        a, b = self.iv[i - 1]
        return self.cum[i - 1] + min(x, b) - a

    def within(self, a: float, b: float) -> float:
        return self._upto(b) - self._upto(a)


def idle_seconds(trace: tr.Trace, spans: Sequence[tr.Op]) -> Dict[str, float]:
    """Device-idle seconds inside the window, averaged over the devices,
    by the innermost span open at each instant (``outside`` where none
    is).  The values sum to the window's idle time."""
    lo, hi = trace.window
    cuts = sorted({lo, hi} | {x for _, s, e in spans for x in (s, e)
                              if lo < x < hi})
    segments = [(a, b, _innermost(spans, (a + b) / 2))
                for a, b in zip(cuts, cuts[1:])]
    out = dict.fromkeys([OUTSIDE] + [name for name, _, _ in spans], 0.0)
    nd = max(len(trace.devices), 1)
    for ops in trace.devices.values():
        busy = _Busy(tr.busy(ops, trace.window))
        for a, b, name in segments:
            out[name] += (b - a - busy.within(a, b)) / nd * 1e-9
    return out


def wait_lag_ms(trace: tr.Trace, spans: Sequence[tr.Op]) -> List[float]:
    """For each ``engine.wait`` inside the window: ms from the end of the
    last device op that started between its call's ``engine.dispatch``
    and the wait's end, to the wait's end.  On one clock every lag is at
    least 0; a negative lag is a wait that ended before the device."""
    dispatch = [s for name, s, _ in spans if name == "engine.dispatch"]
    ops = sorted((s, e) for dev in trace.devices.values() for _, s, e in dev)
    starts = [s for s, _ in ops]
    out = []
    for name, s, e in spans:
        if name != "engine.wait" or s < trace.window[0] or e > trace.window[1]:
            continue
        since = max((d for d in dispatch if d <= s), default=s)
        ran = ops[bisect.bisect_left(starts, since):bisect.bisect_left(starts, e)]
        if ran:
            out.append((e - max(end for _, end in ran)) * 1e-6)
    return out


# -- one traced window on the chip -----------------------------------------

def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import sys
    import time

    from chipbench import run, spec, system

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, str(spec.REPO / "src"))
    import jax
    import numpy as np

    cell = spec.load_cell(args.workload)
    try:
        run.devices_for(cell.chips, require_chip=True)
    except run.NoChip as e:
        print(f"chipbench.stages: {e}", file=sys.stderr)
        return run.EXIT_NO_CHIP
    run._enable_compile_cache()
    r = int(cell.traffic["rounds_per_call"])
    engine = system.build_engine(cell.config, cell.traffic, args.seed)
    for _ in range(1 + run.MAX_WARMUP_CALLS):
        engine.run(r)
    logdir = spec.REPO / ".chipbench_trace" / f"stages-{cell.name}-{args.seed}"
    shutil.rmtree(logdir, ignore_errors=True)
    calls = 0
    with jax.profiler.trace(str(logdir)):
        with jax.profiler.TraceAnnotation("window"):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("call"):
                    engine.run(r)
                calls += 1
                if time.perf_counter() - t0 >= args.seconds:
                    break
            t1 = time.perf_counter()
    t = tr.load(str(logdir))
    spans = load_spans(str(logdir))
    shutil.rmtree(logdir, ignore_errors=True)
    # the traced program's optimized HLO (compiled already: a cache hit)
    do_eval = np.array([x % engine.cfg.eval_every == 0 or x == r
                        for x in range(1, r + 1)])
    stage_of = hlo_stages(engine._program().lower(
        *engine._call_args(do_eval)).compile().as_text())
    rounds = calls * r
    ms = stage_ms(t, rounds, stage_of) or {}
    op_ms = 1e3 * tr.op_time_s(t, lambda _: True)[0] / max(len(t.devices), 1) / rounds
    idle = {k: 100 * v / t.window_s for k, v in idle_seconds(t, spans).items()}
    unscoped: Dict[str, float] = {}
    for ops in t.devices.values():
        for text, s, e in ops:
            if stage_of.get(tr.op_name(text), UNSCOPED) == UNSCOPED:
                for a, b in tr.clip([(s, e)], t.window):
                    key = text[:160]
                    unscoped[key] = unscoped.get(key, 0.0) + (b - a) * 1e-6 / rounds
    lags = wait_lag_ms(t, spans)
    print(json.dumps({
        "calls": calls, "rounds": rounds,
        "window_ms_per_round": 1e3 * (t1 - t0) / rounds,
        "stage_ms": ms,
        "stage_identity": {"sum_stage_ms": sum(ms.values()), "op_ms": op_ms},
        "idle_share": 100 * (tr.idle_share(t) or 0.0),
        "idle_share_by_span": idle,
        "wait_lag_ms": {"min": min(lags, default=None),
                        "max": max(lags, default=None), "n": len(lags)},
        "unscoped_top": sorted(unscoped.items(), key=lambda kv: -kv[1])[:8],
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

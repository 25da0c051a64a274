"""Telemetry quickstart: both observability planes in ~60 lines.

Device plane: ``telemetry=True`` threads a ``RoundTelemetry`` pytree
through the round body — participation, cache hit/miss/expiry, catch-up
and wire bytes, teacher-entropy/beta/codec-error gauges — computed
on device (inside the single-compilation ``lax.scan`` on the scanned
engine: no host callbacks) and returned as ``History.telemetry``.

Host plane: ``SpanTracer`` wraps the run in wall-clock spans and
exports a Chrome trace (load in chrome://tracing or Perfetto), a spans
JSONL, and a ``run_record.json`` that ``python -m repro.obs render``
turns into a report.

  PYTHONPATH=src python examples/telemetry_quickstart.py

REPRO_EXAMPLES_QUICK=1 shrinks the runs to CI-smoke size (same code
path, toy rounds — tests/test_examples.py runs every example this way).
"""
import os

import numpy as np

from repro.fl import FLConfig, run_method
from repro.obs import SpanTracer, device as obs_device
from repro.obs.export import write_chrome_trace, write_run_record, \
    write_spans_jsonl
from repro.obs.report import render

QUICK = bool(os.environ.get("REPRO_EXAMPLES_QUICK"))
OUT = os.path.join("experiments", "obs_demo")


def main():
    cfg = FLConfig(
        n_clients=8, n_classes=10, dim=16, rounds=6 if QUICK else 40,
        public_size=800, public_per_round=100, private_size=1000,
        alpha=0.05, cluster_scale=2.0, noise=2.5,
        eval_every=3 if QUICK else 10, seed=0,
    )
    kw = dict(cache_duration=5, use_cache=True, beta=1.5,
              codec="cache_delta+quant8", telemetry=True)

    tracer = SpanTracer("telemetry_quickstart", meta={"quick": QUICK})
    with tracer.span("run", engine="scan"):
        hist = run_method("scarlet", cfg, engine="scan", **kw)
    with tracer.span("run", engine="host"):
        hist_host = run_method("scarlet", cfg, engine="host",
                               rng_backend="jax", **kw)

    # the parity contract: host and scan emit the SAME counter stacks
    for f in obs_device.EXACT_FIELDS:
        a, b = hist.telemetry.stacks()[f], hist_host.telemetry.stacks()[f]
        assert np.array_equal(a, b), f"host/scan telemetry diverged on {f}"
    print("host/scan telemetry parity: OK "
          f"({len(obs_device.EXACT_FIELDS)} exact counter stacks equal)")

    import jax
    if jax.device_count() > 1:  # shard engine needs a real client mesh
        with tracer.span("run", engine="shard"):
            hist_shard = run_method("scarlet", cfg, engine="shard", **kw)
        for f in obs_device.EXACT_FIELDS:
            assert np.array_equal(hist.telemetry.stacks()[f],
                                  hist_shard.telemetry.stacks()[f])
        print(f"shard telemetry parity: OK ({jax.device_count()} devices)")

    os.makedirs(OUT, exist_ok=True)
    write_chrome_trace(os.path.join(OUT, "trace.json"), tracer)
    write_spans_jsonl(os.path.join(OUT, "spans.jsonl"), tracer)
    write_run_record(os.path.join(OUT, "run_record.json"),
                     name="telemetry_quickstart", config=cfg, history=hist,
                     tracer=tracer)
    print(f"wrote {OUT}/trace.json, spans.jsonl, run_record.json\n")

    import json
    record = json.load(open(os.path.join(OUT, "run_record.json")))
    print(render(record, fmt="text"))


if __name__ == "__main__":
    main()

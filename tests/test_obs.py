"""Unit tests for the ``repro.obs`` subsystem against hand-computed
values: device-plane counter/gauge math, the ``TelemetryLog``
container, the host-plane span tracer, the exporters, the report
renderer, and the ``python -m repro.obs`` CLI."""
import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import SpanTracer, device as obs_device
from repro.obs.__main__ import main as obs_main
from repro.obs.__main__ import validate_trace
from repro.obs.device import RoundTelemetry, TelemetryLog
from repro.obs.export import (
    run_record,
    telemetry_summary,
    write_chrome_trace,
    write_run_record,
    write_spans_jsonl,
)
from repro.obs.report import render


# ---------------------------------------------------------------------------
# device-plane counter math (hand-computed)
# ---------------------------------------------------------------------------

def test_cache_signal_counts():
    present = jnp.asarray([True, False, True, False])
    miss = jnp.asarray([True, True, False, False])
    hits, new, expired = obs_device.cache_signal_counts(present, miss)
    # non-miss rows 2,3 -> 2 hits; miss & never-present row 1 -> 1 new;
    # miss & was-present row 0 -> 1 expired
    assert (int(hits), int(new), int(expired)) == (2, 1, 1)


def test_cache_signal_counts_cache_off_all_new():
    present = jnp.zeros(5, bool)
    miss = jnp.ones(5, bool)
    hits, new, expired = obs_device.cache_signal_counts(present, miss)
    assert (int(hits), int(new), int(expired)) == (0, 5, 0)


def test_staleness_histogram_and_returning():
    # t=5: participant last_sync 4 -> bucket 0 (present last round),
    # 0 -> bucket 4, 2 -> bucket 2; client 3 absent -> not counted
    part = jnp.asarray([True, True, True, False])
    last_sync = jnp.asarray([4, 0, 2, 4])
    hist = np.asarray(obs_device.staleness_histogram(part, last_sync, 5))
    want = np.zeros(obs_device.STALENESS_BUCKETS, np.int32)
    want[0], want[4], want[2] = 1, 1, 1
    assert np.array_equal(hist, want)
    # returning = participating with last_sync < t-1: clients 1 and 2
    assert int(obs_device.returning_client_count(part, last_sync, 5)) == 2


def test_staleness_histogram_clips_top_bucket():
    part = jnp.asarray([True])
    last_sync = jnp.asarray([-1])  # never synced, t=100 -> clipped
    hist = np.asarray(obs_device.staleness_histogram(part, last_sync, 100))
    assert hist[obs_device.STALENESS_BUCKETS - 1] == 1 and hist.sum() == 1


def test_participants_per_cohort():
    part = jnp.asarray([1, 0, 1, 1, 0, 1], bool)
    counts = obs_device.participants_per_cohort(part, (0, 2, 5), (2, 3, 1))
    assert np.array_equal(np.asarray(counts), [1, 2, 1])


def test_participant_mean_and_entropy():
    z = jnp.asarray([[[1.0, 0.0]], [[0.0, 1.0]], [[0.5, 0.5]]])
    part_f = jnp.asarray([1.0, 0.0, 1.0])
    zbar = np.asarray(obs_device.participant_mean(z, part_f, 2))
    assert np.allclose(zbar, [[0.75, 0.25]])
    # uniform over 4 classes -> ln 4 nats
    u = jnp.full((3, 4), 0.25)
    assert float(obs_device.mean_entropy(u)) == pytest.approx(
        math.log(4.0), abs=1e-6)
    # n_part=0 guards the denominator
    assert np.allclose(obs_device.participant_mean(z, jnp.zeros(3), 0), 0.0)


def test_codec_error_mean():
    z_pre = jnp.asarray([[[0.5, 0.5]], [[1.0, 0.0]]])
    z_post = jnp.asarray([[[0.25, 0.75]], [[9.0, 9.0]]])  # client 1 masked
    err = obs_device.codec_error_mean(z_post, z_pre,
                                      jnp.asarray([1.0, 0.0]), 1)
    assert float(err) == pytest.approx(0.25, abs=1e-6)


def test_gate_and_accumulate():
    row = obs_device.zeros(2)._replace(
        cache_hits=jnp.asarray(3, jnp.int32),
        uplink_bytes=jnp.asarray(10.0, jnp.float32))
    gated = obs_device.gate(row, jnp.asarray(False))
    assert int(gated.cache_hits) == 0 and float(gated.uplink_bytes) == 0.0
    kept = obs_device.gate(row, jnp.asarray(True))
    assert int(kept.cache_hits) == 3


def test_field_partition_covers_all_fields():
    assert (set(obs_device.EXACT_FIELDS) | set(obs_device.GAUGE_FIELDS)
            == set(RoundTelemetry._fields))
    assert not set(obs_device.EXACT_FIELDS) & set(obs_device.GAUGE_FIELDS)


# ---------------------------------------------------------------------------
# TelemetryLog
# ---------------------------------------------------------------------------

def _row(n_cohorts=1, **kw):
    row = obs_device.zeros(n_cohorts)
    return row._replace(**{k: jnp.asarray(v) for k, v in kw.items()})


def test_telemetry_log_roundtrip_and_summary():
    log = TelemetryLog()
    log.append(_row(participants=jnp.asarray([2], jnp.int32),
                    cache_hits=jnp.asarray(3, jnp.int32),
                    cache_miss_new=jnp.asarray(7, jnp.int32),
                    uplink_bytes=jnp.asarray(100.0, jnp.float32),
                    beta=jnp.asarray(1.5, jnp.float32)))
    log.append(_row())  # outage round: all zeros, inactive
    assert len(log) == 2
    s = log.summary()
    assert s["rounds"] == 2 and s["active_rounds"] == 1
    assert s["cache_hits"] == 3 and s["cache_miss_new"] == 7
    assert s["cache_hit_rate"] == pytest.approx(0.3)
    assert s["uplink_bytes"] == 100.0
    # gauge means average over ACTIVE rounds only
    assert s["beta_mean"] == 1.5 and s["beta_last"] == 1.5

    # from_stacked must reproduce an appended log exactly
    stacked = RoundTelemetry(*[np.stack([np.asarray(getattr(r, f))
                                         for r in log._rounds])
                               for f in RoundTelemetry._fields])
    log2 = TelemetryLog.from_stacked(stacked)
    for f in RoundTelemetry._fields:
        assert np.array_equal(log.stacks()[f], log2.stacks()[f])
    assert json.dumps(log.as_dict(), sort_keys=True)  # JSON-ready


def test_telemetry_log_empty_summary():
    assert TelemetryLog().summary() == {"rounds": 0}


def test_telemetry_log_totals():
    log = TelemetryLog([_row(cache_hits=jnp.asarray(2, jnp.int32)),
                        _row(cache_hits=jnp.asarray(5, jnp.int32))])
    assert int(log.totals().cache_hits) == 7


# ---------------------------------------------------------------------------
# host plane: tracer + validator + exporters + report + CLI
# ---------------------------------------------------------------------------

def test_span_tracer_nesting_and_chrome_trace():
    tr = SpanTracer("t", meta={"k": "v"})
    with tr.span("outer", engine="scan"):
        with tr.span("inner"):
            pass
    assert [s.name for s in tr.spans] == ["inner", "outer"]  # exit order
    assert tr.spans[0].depth == 1 and tr.spans[1].depth == 0
    assert tr.spans[1].dur_s >= tr.spans[0].dur_s >= 0.0
    trace = tr.chrome_trace()
    assert validate_trace(trace) == []
    assert trace["otherData"]["k"] == "v"
    # B/E pairs are well-nested in event order
    phs = [e["ph"] for e in trace["traceEvents"] if e["ph"] in "BE"]
    assert phs == ["B", "B", "E", "E"]


def test_validate_trace_catches_malformed():
    assert validate_trace({}) == ["top-level 'traceEvents' missing or "
                                  "not a list"]
    bad = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 0.0},
        {"name": "MISMATCH", "ph": "E", "ts": 1.0},
    ]}
    assert any("does not close" in p for p in validate_trace(bad))
    unclosed = {"traceEvents": [{"name": "a", "ph": "B", "ts": 0.0}]}
    assert any("unclosed" in p for p in validate_trace(unclosed))
    empty = {"traceEvents": []}
    assert validate_trace(empty) == ["no B/E span events found"]


def test_exporters_and_run_record(tmp_path):
    tr = SpanTracer("exp")
    with tr.span("run"):
        pass
    trace_path = write_chrome_trace(str(tmp_path / "trace.json"), tr)
    assert validate_trace(json.load(open(trace_path))) == []
    jsonl_path = write_spans_jsonl(str(tmp_path / "spans.jsonl"), tr)
    lines = [json.loads(li) for li in open(jsonl_path)]
    assert len(lines) == 1 and lines[0]["name"] == "run"

    log = TelemetryLog([_row(cache_hits=jnp.asarray(4, jnp.int32),
                             participants=jnp.asarray([2], jnp.int32))])
    rec = write_run_record(
        str(tmp_path / "rec.json"), name="unit", telemetry=log, tracer=tr,
        history={"final_server_acc": 0.5,
                 "comm": {"rounds": 1, "cumulative_total": 2048.0,
                          "uplink_mean": 1024.0, "downlink_mean": 1024.0}})
    on_disk = json.load(open(tmp_path / "rec.json"))
    assert on_disk == rec and rec["record"] == "repro.obs/run"
    assert rec["telemetry"]["summary"]["cache_hits"] == 4

    # telemetry defaults from the history when not passed explicitly
    rec2 = run_record(name="u2", history={"telemetry": log.as_dict()})
    assert rec2["telemetry"]["summary"]["cache_hits"] == 4
    assert telemetry_summary(object()) is None


def test_render_markdown_and_text(tmp_path):
    tr = SpanTracer("r")
    with tr.span("run", engine="scan"):
        pass
    log = TelemetryLog([_row(participants=jnp.asarray([3], jnp.int32),
                             cache_hits=jnp.asarray(6, jnp.int32),
                             cache_miss_new=jnp.asarray(4, jnp.int32))])
    rec = run_record(name="demo", telemetry=log, tracer=tr,
                     history={"final_server_acc": 0.75,
                              "comm": {"rounds": 1,
                                       "cumulative_total": 1e6,
                                       "uplink_mean": 5e5,
                                       "downlink_mean": 5e5}})
    md = render(rec, fmt="markdown")
    txt = render(rec, fmt="text")
    for body in (md, txt):
        assert "demo" in body and "cache_hits" in body and "0.75" in body
        assert "staleness" in body.lower()
    assert "| cache_hits | 6 |" in md and "|" not in txt
    with pytest.raises(ValueError, match="unknown format"):
        render(rec, fmt="html")
    assert "empty record" in render({"name": "nothing"}, fmt="text")


def test_cli_render_and_validate(tmp_path, capsys):
    tr = SpanTracer("cli")
    with tr.span("work"):
        pass
    trace_path = str(tmp_path / "trace.json")
    write_chrome_trace(trace_path, tr)
    rec_path = str(tmp_path / "rec.json")
    write_run_record(rec_path, name="cli-demo", tracer=tr)

    assert obs_main(["validate", trace_path]) == 0
    assert "ok:" in capsys.readouterr().out

    out_path = str(tmp_path / "report.md")
    assert obs_main(["render", rec_path, "--out", out_path]) == 0
    capsys.readouterr()
    assert "cli-demo" in open(out_path).read()

    # invalid trace -> exit 1
    bad_path = str(tmp_path / "bad.json")
    json.dump({"traceEvents": [{"name": "a", "ph": "B", "ts": 0.0}]},
              open(bad_path, "w"))
    assert obs_main(["validate", bad_path]) == 1
    assert "INVALID" in capsys.readouterr().out
    json.dump([], open(bad_path, "w"))  # not even a trace object
    assert obs_main(["validate", bad_path]) == 1
    capsys.readouterr()

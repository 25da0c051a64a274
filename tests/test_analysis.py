"""Tests for the static contract analyzer (``repro.analysis``).

Two halves:

- the real repo must come back clean from all three passes (the same
  property CI's ``python -m repro.analysis --strict`` enforces);
- every deliberately broken fixture must be flagged at its expected
  level, and the repaired replication twin must NOT be flagged (the
  false-positive check).

Everything here is trace-only: no kernel executes, no training runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import fixtures, jaxpr_checks, pallas_checks
from repro.analysis.report import Finding, Report
from repro.analysis.traceutil import record_host_rng, trace


# ---------------------------------------------------------------------------
# Report plumbing
# ---------------------------------------------------------------------------

def test_report_exit_codes():
    r = Report()
    r.add("ok", "p", "s", "fine")
    r.add("info", "p", "s", "fyi")
    assert r.exit_code(strict=False) == 0
    assert r.exit_code(strict=True) == 0  # info never fails

    r.add("warn", "p", "s", "hmm")
    assert r.exit_code(strict=False) == 0
    assert r.exit_code(strict=True) == 1

    r.add("error", "p", "s", "bad")
    assert r.exit_code(strict=False) == 1
    assert len(r.errors) == 1 and len(r.warnings) == 1


def test_report_render_and_json():
    r = Report()
    r.add("error", "pallas", "case", "boom")
    text = r.render(verbose=True)
    assert "boom" in text and "ERROR" in text.upper()
    d = r.to_dict()
    assert d["findings"][0]["level"] == "error"
    assert "boom" in r.to_json()


def test_finding_str():
    f = Finding("warn", "jaxpr", "subj", "msg")
    assert "warn" in str(f).lower() and "subj" in str(f)


# ---------------------------------------------------------------------------
# traceutil
# ---------------------------------------------------------------------------

def test_trace_detects_callbacks():
    def f(x):
        out = jax.ShapeDtypeStruct(x.shape, x.dtype)
        return jax.pure_callback(lambda a: a, out, x)

    tr = trace(f, jax.ShapeDtypeStruct((4,), jnp.float32))
    assert tr.ok and tr.callbacks
    assert any("callback" in v for v in tr.scan_safety_violations())


def test_record_host_rng_spy():
    seen = []
    with record_host_rng(seen):
        np.random.default_rng(0)
    assert seen  # constructor call recorded
    # and restored afterwards
    assert np.random.default_rng(0).integers(10) >= 0


# ---------------------------------------------------------------------------
# The repo itself is clean
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_repo_jaxpr_pass_clean():
    findings = jaxpr_checks.run()
    errs = [f for f in findings if f.level in ("error", "warn")]
    assert not errs, "\n".join(str(f) for f in errs)
    assert any(f.level == "ok" for f in findings)


def test_repo_pallas_pass_clean():
    findings = pallas_checks.run()
    errs = [f for f in findings if f.level in ("error", "warn")]
    assert not errs, "\n".join(str(f) for f in errs)
    # every kernel module contributed at least one linted case
    subjects = {f.subject.split("/")[0] for f in findings}
    for mod in ("era_fused", "quant", "round", "distill", "attn", "mlp_distill"):
        assert any(s.startswith(mod.split("_")[0]) for s in subjects), mod


@pytest.mark.slow
def test_repo_replication_pass_clean():
    from repro.analysis import replication_checks

    findings = replication_checks.run()
    errs = [f for f in findings if f.level == "error"]
    assert not errs, "\n".join(str(f) for f in errs)
    assert any(f.level == "ok" for f in findings)


# ---------------------------------------------------------------------------
# Broken fixtures are flagged
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(fixtures.BROKEN_STRATEGIES))
def test_broken_strategy_flagged(name):
    want = fixtures.EXPECTED_STRATEGY_LEVEL[name]
    got = jaxpr_checks.check_strategy(name, fixtures.BROKEN_STRATEGIES[name])
    assert any(f.level == want for f in got), (
        f"{name}: expected a {want!r} finding, got "
        + "\n".join(str(f) for f in got))


@pytest.mark.parametrize(
    "label,fn,args,want",
    fixtures.broken_kernel_cases(),
    ids=[c[0] for c in fixtures.broken_kernel_cases()])
def test_broken_kernel_flagged(label, fn, args, want):
    got = pallas_checks.check_case(label, fn, args)
    assert any(f.level == want for f in got), (
        f"{label}: expected {want!r}, got "
        + "\n".join(str(f) for f in got))


@pytest.mark.slow
def test_repo_obs_pass_clean():
    from repro.analysis import obs_checks

    findings = obs_checks.run()
    errs = [f for f in findings if f.level == "error"]
    assert not errs, "\n".join(str(f) for f in errs)
    assert any("structurally additive" in f.message for f in findings)


def test_telemetry_callback_hook_flagged():
    from repro.analysis import obs_checks

    got = obs_checks.check_round_body(
        "fixture/telemetry-callback", fixtures.telemetry_callback_engine())
    errs = [f for f in got if f.level == "error"]
    assert errs, "debug_callback-smuggling telemetry hook not flagged"
    assert any("callback" in f.message for f in errs)


def _telemetry_engine(tel, carried=False):
    """The obs pass's structural subject; ``carried`` plants the fault
    the pass forbids: the telemetry row also rides the scan carry."""
    from repro.analysis import obs_checks

    eng = obs_checks._build_engine("scarlet", {}, {"cache_duration": 2},
                                   "identity", telemetry=tel)
    if tel and carried:
        body = eng._round_device

        def round_with_carried_row(carry, xs):
            new_carry, ys = body(carry, xs)
            return dict(new_carry, telemetry=ys["telemetry"]), ys
        eng._round_device = round_with_carried_row
    return eng


def test_telemetry_structure_check_clean():
    from repro.analysis import obs_checks

    got = obs_checks.check_off_on_structure("telemetry[structure]",
                                            _telemetry_engine)
    assert [f.level for f in got] == ["ok"], "\n".join(map(str, got))


def test_telemetry_in_the_carry_flagged():
    from repro.analysis import obs_checks

    got = obs_checks.check_off_on_structure(
        "fixture/telemetry-carry",
        lambda tel: _telemetry_engine(tel, carried=True))
    errs = [f for f in got if f.level == "error"]
    assert any("carry out" in f.message for f in errs), (
        "\n".join(map(str, got)))


@pytest.mark.slow
def test_repo_active_pass_clean():
    from repro.analysis import active_checks

    findings = active_checks.run()
    errs = [f for f in findings if f.level == "error"]
    assert not errs, "\n".join(str(f) for f in errs)
    # one ok per analysis variant, each certifying the K-separation
    oks = [f for f in findings if f.level == "ok"]
    assert len(oks) == len(active_checks.ANALYSIS_VARIANTS)
    assert all(f"K={active_checks.K_ANALYSIS}" in f.message for f in oks)


def test_leaky_active_engine_flagged():
    from repro.analysis import active_checks

    got = active_checks.check_engine(
        "fixture/active-k-leak", fixtures.leaky_active_engine())
    errs = [f for f in got if f.level == "error"]
    assert errs, "O(K) leak into the gathered client step not flagged"
    assert any("client step" in f.message for f in errs)
    # the leak is in the client step, not the (legitimately O(K))
    # bookkeeping step
    assert all("client-step" in f.subject for f in errs)


def test_active_pass_traces_the_right_functions():
    """The K-presence sanity check: hand the checker an engine whose
    bookkeeping never touches K-sized state and it must refuse to
    certify (a vacuous K-separation proof is worse than none)."""
    from repro.analysis import active_checks

    eng = active_checks.build_engine("scarlet", {}, {"cache_duration": 2},
                                     "identity")
    orig = eng.active_round_fns

    def swapped():
        entries = orig()
        # keep only the client step but mislabel it as bookkeeping
        (_, fn, args) = [e for e in entries if e[0] == "client-step"][0]
        return [("bookkeeping", fn, args)]

    eng.active_round_fns = swapped
    got = active_checks.check_engine("fixture/mislabeled", eng)
    errs = [f for f in got if f.level == "error"]
    assert errs and any("proves nothing" in f.message for f in errs)


def test_broken_carry_flagged_fixed_carry_clean():
    from repro.analysis import replication_checks

    broken = replication_checks.check_shard_map_fn(
        *fixtures.broken_carry_fn(), subject_prefix="fixture-broken:")
    errs = [f for f in broken if f.level == "error"]
    assert errs, "axis_index-tainted replicated carry not flagged"
    assert any("data" in f.message for f in errs)

    fixed = replication_checks.check_shard_map_fn(
        *fixtures.fixed_carry_fn(), subject_prefix="fixture-fixed:")
    assert not [f for f in fixed if f.level == "error"], (
        "psum-cleaned twin falsely flagged:\n"
        + "\n".join(str(f) for f in fixed))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_selftest_fast(capsys):
    from repro.analysis.__main__ import main

    assert main(["--selftest", "--fast"]) == 0
    out = capsys.readouterr().out
    assert "flagged as expected" in out


def test_cli_fast_strict_on_repo(capsys, tmp_path):
    from repro.analysis.__main__ import main

    json_path = tmp_path / "report.json"
    assert main(["--fast", "--strict", "--json", str(json_path)]) == 0
    assert json_path.exists() and "findings" in json_path.read_text()

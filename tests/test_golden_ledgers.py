"""Golden-ledger regression fixtures (``tests/golden/*.json``).

Each fixture is the exact ``CommLedger.summary()`` of a tiny scanned
run, serialized canonically (sorted keys, 2-space indent, trailing
newline) and compared **byte-for-byte** against the committed file.
Ledger values are analytic functions of exact integer counts, so any
drift — a changed payload model, an extra charged byte, a reordered
round — fails here even when cross-engine conformance still holds
(conformance compares engines to each other; the goldens pin the
absolute values the paper's tables are computed from).

The participation draw behind every fixture comes from JAX's default
PRNG.  Since JAX 0.5 that is the partitionable threefry
(``jax_threefry_partitionable=True``), which draws different bits than
the older default; the fixtures were regenerated under it and hold on
any backend, device count and process history (``chip_smoke.py``
compares them on the chip).

Intentional changes: regenerate with

    PYTHONPATH=src python -m pytest tests/test_golden_ledgers.py \
        --update-golden

and commit the diff (the run skips with an "updated" note).
"""
import json
from pathlib import Path

import pytest

from repro.fl import FLConfig, Scenario, bernoulli_participation, run_method

GOLDEN_DIR = Path(__file__).parent / "golden"

CFG = FLConfig(n_clients=4, n_classes=4, dim=8, rounds=4, local_steps=2,
               distill_steps=2, public_size=48, public_per_round=10,
               private_size=64, alpha=0.5, eval_every=2, seed=0, hidden=12)

METHOD_KW = {
    "scarlet": dict(cache_duration=3, beta=1.5),
    "dsfl": dict(T=0.1),
    "cfd": dict(),
}
CODECS = ("identity", "quant8")
CASES = [(m, c) for m in sorted(METHOD_KW) for c in CODECS]


def summary_text(method: str, codec: str, **run_kw) -> str:
    """Canonical ledger summary of one golden cell (``run_kw`` adds
    ``run_method`` options, e.g. ``fused_round=True``); also what
    ``chip_smoke.py`` compares with the fixtures on the chip."""
    h = run_method(
        method, CFG, engine="scan", codec=codec,
        scenario=Scenario(participation=bernoulli_participation(0.5)),
        **run_kw, **METHOD_KW[method])
    return json.dumps(h.ledger.summary(), sort_keys=True, indent=2) + "\n"


@pytest.mark.parametrize("method,codec", CASES,
                         ids=[f"{m}-{c}" for m, c in CASES])
def test_golden_ledger(method, codec, request):
    path = GOLDEN_DIR / f"{method}-{codec}.json"
    text = summary_text(method, codec)
    if request.config.getoption("--update-golden"):
        path.write_text(text)
        pytest.skip(f"updated {path.name}")
    assert path.exists(), (
        f"missing golden fixture {path}; generate it with "
        "--update-golden and commit the file")
    golden = path.read_text()
    assert golden == text, (
        f"{path.name} drifted from the committed bytes.\n"
        f"committed:\n{golden}\ncomputed:\n{text}\n"
        "If the change is intentional, regenerate with --update-golden "
        "and commit the diff.")


@pytest.mark.parametrize("codec", CODECS)
def test_golden_ledger_fused_round_byte_identical(codec):
    """The fused fast path must reproduce the committed per-op goldens
    byte-for-byte: comm accounting is analytic in integer counts, so
    fusing the compute hot path may not move a single byte.  No separate
    fused fixtures exist on purpose — the per-op files are the contract."""
    path = GOLDEN_DIR / f"scarlet-{codec}.json"
    assert path.read_text() == summary_text("scarlet", codec,
                                            fused_round=True)


def test_no_stale_golden_fixtures():
    """Every committed fixture corresponds to a live matrix cell, so a
    renamed case cannot leave an unchecked golden behind."""
    expected = {f"{m}-{c}.json" for m, c in CASES}
    actual = {p.name for p in GOLDEN_DIR.glob("*.json")}
    assert actual == expected

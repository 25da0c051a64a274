"""Exposed device ms a round of the collective operations: on each chip,
the intervals of its collective operations (``all-reduce`` and its async
``-start``/``-done`` pair, ``all-gather``, ``reduce-scatter``,
``collective-permute``, ``all-to-all``, each with its async pair) less
the union of that chip's other operations, clipped to the window,
averaged over the chips, over the window's rounds.  The part of a
collective that runs under compute costs the round nothing and is not
counted.  None where the window holds no collective operation."""
from chipbench import trace as tr

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")
OPCODES = frozenset(c + sfx for c in COLLECTIVES for sfx in ("", "-start", "-done"))


def is_collective(text):
    return tr.opcode(text) in OPCODES


def read(rec):
    t = rec["trace"]
    exposed, found = 0.0, False
    for ops in t.devices.values():
        coll = [(s, e) for text, s, e in ops if is_collective(text)]
        found |= bool(coll)
        other = tr.union((s, e) for text, s, e in ops if not is_collective(text))
        exposed += tr.length(tr.subtract(tr.clip(tr.union(coll), t.window), other))
    if not found or not t.devices or rec["rounds"] <= 0:
        return None
    return exposed / len(t.devices) / rec["rounds"] * 1e-6

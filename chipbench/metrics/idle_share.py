"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips: 1 - (union of operation intervals) / window."""
from chipbench import trace as tr


def read(rec):
    share = tr.idle_share(rec["trace"])
    return None if share is None else 100.0 * share

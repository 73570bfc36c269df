"""Device milliseconds per cohort window of the Eq. 6 programs: the
per-leaf einsum of ``stacked_weighted`` (one chip) and the shard_map psum
reducers of ``core/aggregate`` (a mesh).  Tip stacking is left out: its
programs are shared with batched tip validation."""
from bench.harness.trace import seconds_matching

PROGRAMS = r"^jit__?einsum$|^jit_local$"


def read(r):
    if r.trace is None or not r.raw.get("flushes"):
        return None
    s = seconds_matching(r.trace["modules"], PROGRAMS)
    return 1e3 * s / r.raw["flushes"] if s > 0 else None

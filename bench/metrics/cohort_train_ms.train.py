"""Device milliseconds per cohort window of the cohort train programs
(``CohortBackend._train_impl`` / ``_train_uniform_impl``)."""
from bench.harness.trace import seconds_matching

PROGRAMS = r"^jit__train(_uniform)?_impl$"


def read(r):
    if r.trace is None or not r.raw.get("flushes"):
        return None
    s = seconds_matching(r.trace["modules"], PROGRAMS)
    return 1e3 * s / r.raw["flushes"] if s > 0 else None

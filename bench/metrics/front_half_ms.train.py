"""Mean host milliseconds of one round's front half (tip selection with
candidate validation, path audit, cost draws): the benchmark's span around
``DagAflCoordinator._front_half``."""


def read(r):
    n = r.spans.calls.get("front_half", 0)
    if not n:
        return None
    return 1e3 * r.spans.seconds["front_half"] / n

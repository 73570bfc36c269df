"""Share of the traced window in which no op ran on the device, in %,
averaged over the chips (nothing when the trace holds no device op)."""


def read(r):
    if r.trace is None or r.trace["window_s"] <= 0 or r.trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])

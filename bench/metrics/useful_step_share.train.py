"""Real client SGD steps over the steps the train programs ran, in %, over
the window: the cohort programs' scan steps count client and step padding;
a window of one round runs the backend's program, unpadded."""


def read(r):
    real, scan = r.raw.get("real_steps"), r.raw.get("scan_steps")
    if not scan:
        return None
    return 100.0 * real / scan

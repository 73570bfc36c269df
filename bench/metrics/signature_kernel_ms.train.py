"""Device milliseconds per cohort window of the Eq. 3 signature kernel:
the trace's ops that are the kernel's own instruction (XLA names a Pallas
kernel's custom call by its ``name``: ``%dagafl_signature.3 = ...
custom-call(...)``; an op that only reads its result is not counted), over
the window's cohort windows."""
import re

KERNEL = re.compile(r"%?dagafl_signature(\.\d+)?( =|$)")


def read(r):
    if r.trace is None or not r.raw.get("flushes"):
        return None
    s = sum(t for name, t in r.trace["ops"].items() if KERNEL.match(name))
    return 1e3 * s / r.raw["flushes"] if s > 0 else None

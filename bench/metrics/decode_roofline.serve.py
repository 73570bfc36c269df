"""Share of its roofline of a decode step, in %: the least time to read
every matmul weight once in bfloat16 and the K/V cache at the step's
length, at peak HBM bandwidth, over the decode program's mean device time
per step in the trace."""
from bench.harness import counts
from bench.harness.trace import seconds_matching

PROGRAM = r"^jit_serve_decode$"


def read(r):
    q = r.raw.get("queries")
    if r.trace is None or not q:
        return None
    t = seconds_matching(r.trace["modules"], PROGRAM)
    steps = q * (r.raw["new_tokens"] - 1)
    if t <= 0:
        return None
    b, s = r.raw["batch"], r.raw["prompt_len"]
    least = sum(counts.decode_bytes(r.config, b, s + 1 + i)
                for i in range(r.raw["new_tokens"] - 1)) / (
        r.raw["new_tokens"] - 1) / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (t / steps)

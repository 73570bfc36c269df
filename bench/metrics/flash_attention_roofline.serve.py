"""Share of its roofline of the prefill flash-attention kernel, in %: the
larger of its FLOPs over peak and its bytes over bandwidth, counted from
shapes, over the kernel's device time in the trace: the Pallas ops inside
the prefill program ``serve_prefill``."""
from bench.harness import counts
from bench.harness.trace import seconds_matching

PROGRAM = r"^jit_serve_prefill$"


def read(r):
    q = r.raw.get("queries")
    if r.trace is None or not q:
        return None
    t = seconds_matching(r.trace["kernels"], PROGRAM)
    if t <= 0:
        return None
    flops, nbytes = counts.flash_attention_cost(r.config, r.raw["batch"],
                                                r.raw["prompt_len"])
    least = q * max(flops / r.peaks["bf16_flops_per_s"],
                    nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t

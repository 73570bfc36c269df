"""Prefill FLOPs over the prefill time's chip peak, in %: every layer on
every prompt token, causal attention and the head on the last position,
over the window's summed synced ``prefill_s`` times peak bf16 FLOP/s."""
from bench.harness import counts


def read(r):
    q = r.raw.get("queries")
    if not q:
        return None
    flops = q * counts.prefill_flops(r.config, r.raw["batch"],
                                     r.raw["prompt_len"])
    return 100.0 * flops / (sum(r.raw["prefill_s"])
                            * r.peaks["bf16_flops_per_s"])

"""Serving FLOPs over the window's chip peak, in %: each query's prefill
and every decode step at its cache length, over window seconds times peak
bf16 FLOP/s."""
from bench.harness import counts


def read(r):
    q = r.raw.get("queries")
    if not q or not r.window_s:
        return None
    b, s, new = r.raw["batch"], r.raw["prompt_len"], r.raw["new_tokens"]
    per_query = counts.prefill_flops(r.config, b, s) + sum(
        counts.decode_flops(r.config, b, s + 1 + i) for i in range(new - 1))
    return 100.0 * q * per_query / (r.window_s * r.peaks["bf16_flops_per_s"])

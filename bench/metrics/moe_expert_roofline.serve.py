"""Share of its roofline of the held experts' grouped matmul, in %: the
larger of its FLOPs over peak and its bytes over bandwidth, over the
device time of the trace's ``dagafl_moe_gmm`` ops (the kernel's own
instructions; XLA names a Pallas kernel's custom call by its ``name``).
FLOPs are ``6 * hidden * expert width`` a routed row (gate, up and down
projections); bytes are each hit expert's three matrices once per call and
layer, and each routed row read in and written out once, at two bytes a
value.  Rows and hits are the window's ``dagafl.moe_routed_rows`` and
``dagafl.moe_experts_hit`` counters (prefill and decode)."""
import re

KERNEL = re.compile(r"%?dagafl_moe_gmm(\.\d+)?( =|$)")


def expert_cost(cfg: dict, rows: int, hits: int) -> tuple:
    """(FLOPs, bytes) of the held experts over ``rows`` routed rows that
    reach ``hits`` (expert, call, layer) triples."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 6.0 * d * f * rows, 2.0 * (3 * d * f * hits + 2 * d * rows)


def read(r):
    c = r.raw.get("counters") or {}
    rows = c.get("dagafl.moe_routed_rows")
    hits = c.get("dagafl.moe_experts_hit")
    if r.trace is None or not rows or not hits:
        return None
    t = sum(v for name, v in r.trace["ops"].items() if KERNEL.match(name))
    if t <= 0:
        return None
    flops, nbytes = expert_cost(r.config, rows, hits)
    least = max(flops / r.peaks["bf16_flops_per_s"],
                nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t

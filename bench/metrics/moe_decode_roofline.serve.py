"""Share of its roofline of an MLA + expert-layer decode step, in %: the
least time to read, at peak HBM bandwidth and two bytes a value, every
weight a step multiplies except the held experts (attention, dense and
shared experts, routers, the head, the batch's embedding rows), the held
experts the step's tokens hit (the window's decode hits over its steps),
and the latent cache (``kv_lora_rank`` + ``qk_rope_head_dim`` a token and
layer) at its mean length, over the decode program's mean device time per
step in the trace."""
from bench.harness.trace import seconds_matching

PROGRAM = r"^jit_serve_decode$"


def non_expert_params(cfg: dict, batch: int) -> int:
    """Weights a decode step reads outside the held experts."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, qr = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    f = cfg["moe_intermediate_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    attn = (d * qr + qr + qr * h * (nope + rope) + d * (r + rope) + r
            + r * h * (nope + vd) + h * vd * d + 2 * d)
    moe = d * cfg["router_experts"] + 3 * d * cfg["n_shared_experts"] * f
    return (layers * attn + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * moe + d * cfg["vocab_size"] + d + batch * d)


def step_bytes(cfg: dict, batch: int, hits_per_step: float,
               cache_len: float) -> float:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    cache = (cfg["num_hidden_layers"] * batch * cache_len
             * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]))
    return 2.0 * (non_expert_params(cfg, batch) + 3 * d * f * hits_per_step
                  + cache)


def read(r):
    q = r.raw.get("queries")
    hits = r.raw.get("decode_experts_hit")
    if r.trace is None or not q or hits is None:
        return None
    t = seconds_matching(r.trace["modules"], PROGRAM)
    if t <= 0:
        return None
    new, b, s = r.raw["new_tokens"], r.raw["batch"], r.raw["prompt_len"]
    steps = q * (new - 1)
    # cache lengths s + 1 .. s + new - 1 over the steps
    least = step_bytes(r.config, b, hits / steps,
                       s + new / 2.0) / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (t / steps)

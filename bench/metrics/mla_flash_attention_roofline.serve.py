"""Share of its roofline of the prefill flash-attention kernel under
multi-head latent attention, in %: the larger of its FLOPs over peak and
its bytes over bandwidth, over the device time of the trace's
``dagafl_flash_attention`` ops (the kernel's own instructions; XLA names a
Pallas kernel's custom call by its ``name``).  Counted from the
configuration's MLA shapes: every head's queries and keys are
``qk_nope_head_dim + qk_rope_head_dim`` wide, its values and outputs
``v_head_dim``.  FLOPs are QK^T and PV over the causal pairs of each
prompt, in every layer; bytes are Q, K and V read and O written once, at
two bytes a value."""
import re

KERNEL = re.compile(r"%?dagafl_flash_attention(\.\d+)?( =|$)")


def mla_attention_cost(cfg: dict, batch: int, seq: int) -> tuple:
    """(FLOPs, bytes) of causal MLA attention over ``batch`` prompts of
    ``seq`` tokens, every layer."""
    h, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    pairs = seq * (seq + 1) / 2.0
    flops = 2.0 * batch * h * pairs * (qk + vd) * layers
    nbytes = 2.0 * batch * seq * h * (2 * qk + 2 * vd) * layers
    return flops, nbytes


def read(r):
    q, b, s = (r.raw.get(k) for k in ("queries", "batch", "prompt_len"))
    if r.trace is None or not q or not b or not s:
        return None
    t = sum(v for name, v in r.trace["ops"].items() if KERNEL.match(name))
    if t <= 0:
        return None
    flops, nbytes = mla_attention_cost(r.config, b, s)
    least = q * max(flops / r.peaks["bf16_flops_per_s"],
                    nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t

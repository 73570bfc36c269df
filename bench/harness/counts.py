"""Operations and bytes the algorithms need, counted from shapes.  These
are the yardstick of every roofline and mfu metric: a program that does
extra work does not raise them."""
from __future__ import annotations


def vgg_forward_flops(cfg: dict) -> float:
    """FLOPs (2 per multiply-add) of one image's forward pass through a
    VGG configuration: SAME 3x3 convs, a 2x2 pool after each stack, then
    the fully connected head."""
    size, cin, k = cfg["image_size"], cfg["in_channels"], cfg["kernel_size"]
    flops = 0.0
    for stack in cfg["conv_stacks"]:
        for cout in stack:
            flops += 2.0 * size * size * k * k * cin * cout
            cin = cout
        size //= 2
    d = cin * size * size
    for out in list(cfg["fc_dims"]) + [cfg["n_classes"]]:
        flops += 2.0 * d * out
        d = out
    return flops


def vgg_train_flops_per_sample(cfg: dict) -> float:
    """Forward plus backward: three times the forward."""
    return 3.0 * vgg_forward_flops(cfg)


def vgg_param_count(cfg: dict) -> int:
    size, cin, k = cfg["image_size"], cfg["in_channels"], cfg["kernel_size"]
    n = 0
    for stack in cfg["conv_stacks"]:
        for cout in stack:
            n += k * k * cin * cout + cout
            cin = cout
        size //= 2
    d = cin * size * size
    for out in list(cfg["fc_dims"]) + [cfg["n_classes"]]:
        n += d * out + out
        d = out
    return n


# -- dense GQA decoder (internlm2 family) -----------------------------------


def decoder_layer_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    ff = cfg["intermediate_size"]
    return d * q + 2 * d * kv + q * d + 3 * d * ff + 2 * d


def decoder_param_count(cfg: dict) -> int:
    """Every parameter: embedding, layers, final norm, untied head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return (v * d + cfg["num_hidden_layers"] * decoder_layer_params(cfg)
            + d + head)


def decoder_matmul_params(cfg: dict) -> int:
    """Parameters that multiply every token: layers and the head (the
    embedding is a lookup)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * decoder_layer_params(cfg) + d * v


def attention_flops(cfg: dict, batch: int, q_len: int, kv_len: int,
                    causal: bool) -> float:
    """QK^T and PV FLOPs over all layers; a causal full-sequence pass
    counts only the key positions at or before each query."""
    hd, h = cfg["head_dim"], cfg["num_attention_heads"]
    if causal:
        pairs = q_len * (q_len + 1) / 2.0
    else:
        pairs = float(q_len * kv_len)
    return 4.0 * batch * h * hd * pairs * cfg["num_hidden_layers"]


def prefill_flops(cfg: dict, batch: int, prompt: int) -> float:
    """Prefill: every layer on every prompt token, causal attention, and
    the head on the last position only (what the serving path needs)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    layer = cfg["num_hidden_layers"] * decoder_layer_params(cfg)
    return (2.0 * layer * batch * prompt
            + attention_flops(cfg, batch, prompt, prompt, causal=True)
            + 2.0 * d * v * batch)


def decode_flops(cfg: dict, batch: int, kv_len: int) -> float:
    """One decode step of ``batch`` tokens against ``kv_len`` cached keys."""
    return (2.0 * decoder_matmul_params(cfg) * batch
            + attention_flops(cfg, batch, 1, kv_len, causal=False))


def decode_bytes(cfg: dict, batch: int, kv_len: int, weight_bytes: int = 2,
                 cache_bytes: int = 2) -> float:
    """Least bytes one decode step reads: every matmul weight once at the
    compute dtype, the batch's embedding rows, and the K and V caches up to
    ``kv_len``."""
    d = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    weights = weight_bytes * (decoder_matmul_params(cfg) + batch * d)
    cache = cache_bytes * 2 * cfg["num_hidden_layers"] * batch * kv_len * kv
    return float(weights + cache)


def flash_attention_cost(cfg: dict, batch: int, seq: int,
                         io_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of causal flash attention over all layers: Q, K, V
    read and O written once at the compute dtype."""
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    flops = attention_flops(cfg, batch, seq, seq, causal=True)
    per_layer = io_bytes * batch * seq * hd * (2 * h + 2 * kvh)
    return flops, float(per_layer * cfg["num_hidden_layers"])

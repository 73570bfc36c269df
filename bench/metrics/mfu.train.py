"""Useful training FLOPs over the window's chip peak, in %: real samples
trained times three times the configuration's forward FLOPs per image
(padded steps and padded clients do not count), over window seconds times
chips times peak bf16 FLOP/s."""
from bench.harness import counts


def read(r):
    n = r.raw.get("samples")
    if not n or not r.window_s:
        return None
    flops = n * counts.vgg_train_flops_per_sample(r.config)
    return 100.0 * flops / (r.window_s * r.chips
                            * r.peaks["bf16_flops_per_s"])

"""Training launcher: single-host execution of any --arch config.

``--reduced`` runs the 2-layer family member (CPU-friendly); without it the
full config is used (requires accelerators).  ``--dagafl N`` federates N
clients through the DAG-AFL coordinator instead of single-stream training.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduced
from repro.data.pipeline import TokenPipeline
from repro.models import transformer as tfm
from repro.runtime import Runtime, enable_compile_cache
from repro.train.checkpoint import save_checkpoint
from repro.train.step import make_train_step


def train_single(cfg, args):
    runtime = Runtime(want_signature=True, use_pallas=args.pallas,
                      kernel_policy=args.kernel_policy or "auto")
    step, opt = make_train_step(cfg, runtime=runtime)
    jstep = jax.jit(step)
    key = jax.random.PRNGKey(args.seed)
    params = tfm.init_params(key, cfg)
    opt_state = opt.init(params)
    pipe = TokenPipeline(cfg.vocab_size, args.batch, args.seq, seed=args.seed)
    it = iter(pipe)
    t0 = time.time()
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_dict(next(it)).items()}
        if cfg.encoder is not None:
            batch["enc_embed"] = jnp.zeros(
                (args.batch, cfg.encoder.n_ctx, cfg.d_model), jnp.float32)
        params, opt_state, m = jstep(params, opt_state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            # jstep dispatches asynchronously: sync before reading the clock
            # or tok/s measures dispatch latency, not compute
            jax.block_until_ready((params, m))
            dt = time.time() - t0
            tok_s = args.batch * args.seq * (i + 1) / max(dt, 1e-9)
            print(f"step {i:5d} loss={float(m['loss']):.4f} "
                  f"grad_norm={float(m['grad_norm']):.3f} tok/s={tok_s:,.0f}")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, params, step=args.steps)
        print(f"saved {args.checkpoint}")
    return params


def train_dagafl(cfg, args):
    from repro.core import DagAflConfig, DagAflCoordinator
    from repro.core.simulator import CostModel, make_profiles
    from repro.data import make_lm_dataset
    from repro.fl.backend import LMBackend

    backend = LMBackend(cfg, lr=args.lr, local_steps=args.local_steps,
                        batch_size=args.batch, seq_len=args.seq,
                        kernel_policy=args.kernel_policy or None)
    streams = [make_lm_dataset(vocab=cfg.vocab_size, n_tokens=50_000,
                               order=1.5 + 0.5 * c, seed=c)
               for c in range(args.dagafl)]
    client_data = [{"train": s, "val": s, "test": s} for s in streams]
    global_test = make_lm_dataset(vocab=cfg.vocab_size, n_tokens=50_000,
                                  seed=999)
    dcfg = DagAflConfig(n_clients=args.dagafl, max_rounds=args.rounds,
                        local_epochs=args.local_steps, seed=args.seed,
                        kernel_policy=args.kernel_policy or None)
    coord = DagAflCoordinator(backend, client_data, global_test, dcfg,
                              CostModel(), make_profiles(args.dagafl))
    res = coord.run()
    print(res.row())
    print("chain:", res.extra)
    return res


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="internlm2-1.8b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--kernel-policy", default="",
                    choices=["", "auto", "compiled", "interpret", "reference"],
                    help="kernel dispatch policy for the Pallas hot paths "
                         "(empty = incumbent stock-XLA math; see "
                         "repro.kernels.dispatch)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--dagafl", type=int, default=0,
                    help="federate N clients via DAG-AFL")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--local-steps", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(reduced(cfg), compute_dtype="float32")
    print(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M")
    if args.dagafl:
        train_dagafl(cfg, args)
    else:
        train_single(cfg, args)


if __name__ == "__main__":
    main()

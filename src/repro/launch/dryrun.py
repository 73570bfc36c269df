"""Multi-pod dry-run: prove every (arch x input-shape x mesh) combination
lowers, compiles, and fits — and extract the roofline terms.

MUST set the host-device count before ANY other import (jax locks the device
count on first init).
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import functools         # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config  # noqa: E402
from repro.configs.base import ArchConfig, InputShape         # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo  # noqa: E402
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,  # noqa: E402
                               make_production_mesh)
from repro.models import transformer as tfm                   # noqa: E402
from repro.sharding.rules import (MeshPlan, batch_shardings,  # noqa: E402
                                  cache_shardings, opt_state_shardings,
                                  param_shardings, small_model_plan)
from repro.runtime import Runtime                             # noqa: E402
from repro.train.step import (make_serve_decode, make_serve_prefill,  # noqa: E402
                              make_train_step)

_COLLECTIVE_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)",
)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def input_specs(cfg: ArchConfig, shape: InputShape):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    f32 = jnp.float32
    i32 = jnp.int32
    if shape.mode == "train":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                 "labels": jax.ShapeDtypeStruct((B, S), i32)}
    elif shape.mode == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    else:  # decode
        batch = {"token": jax.ShapeDtypeStruct((B, 1), i32),
                 "pos": jax.ShapeDtypeStruct((), i32)}
    if cfg.encoder is not None and shape.mode in ("train", "prefill"):
        batch["enc_embed"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder.n_ctx, cfg.d_model), f32)
    if cfg.mrope_sections is not None and shape.mode in ("train", "prefill"):
        batch["positions"] = jax.ShapeDtypeStruct((3, B, S), i32)
    return batch


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op in the partitioned HLO."""
    totals = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        totals[kind] = totals.get(kind, 0) + n * _DTYPE_BYTES[dtype]
    return totals


def model_flops(cfg: ArchConfig, shape: InputShape) -> float:
    """Useful-FLOPs yardstick: 6·N_active·tokens (train), 2·N_active·tokens
    (forward-only)."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    mult = 6.0 if shape.mode == "train" else 2.0
    return mult * n * tokens


def build_step(cfg: ArchConfig, shape: InputShape, mesh, plan: MeshPlan):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs)."""
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda k: tfm.init_params(k, cfg), key)
    params_sh = param_shardings(params_shape, cfg, mesh, plan)
    repl = NamedSharding(mesh, P())
    bsize = int(np.prod([mesh.shape[a] for a in plan.batch_axes]))
    runtime = Runtime(want_signature=(shape.mode == "train"),
                      batch_axes=plan.batch_axes, batch_axis_size=bsize,
                      mesh=mesh)

    if shape.mode == "train":
        # H3 (auto plan): gradient accumulation for the giant archs — layer-
        # scan activation carries scale by 1/microbatches
        mb = 1
        if not plan.enable_fsdp or plan.enable_tp is False:
            mb = 1
        if getattr(plan, "_microbatches", 0):
            mb = plan._microbatches
        step, opt = make_train_step(cfg, runtime=runtime, microbatches=mb)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        opt_sh = opt_state_shardings(opt_shape, params_sh, mesh)
        batch = input_specs(cfg, shape)
        batch_sh = batch_shardings(batch, mesh, plan)
        jitted = jax.jit(step,
                         in_shardings=(params_sh, opt_sh, batch_sh),
                         out_shardings=(params_sh, opt_sh, None))
        return jitted, (params_shape, opt_shape, batch)

    if shape.mode == "prefill":
        fn = make_serve_prefill(cfg, runtime=runtime)
        batch = input_specs(cfg, shape)
        batch_sh = batch_shardings(batch, mesh, plan)
        jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh))
        return jitted, (params_shape, batch)

    # decode
    fn = make_serve_decode(cfg, runtime=runtime)
    caches_shape = jax.eval_shape(
        functools.partial(tfm.init_cache, cfg, shape.global_batch,
                          shape.seq_len))
    cache_sh = cache_shardings(caches_shape, cfg, mesh, plan)
    spec = input_specs(cfg, shape)
    token_sh = batch_shardings({"tokens": spec["token"]}, mesh, plan)["tokens"]
    jitted = jax.jit(fn,
                     in_shardings=(params_sh, token_sh, cache_sh, repl),
                     out_shardings=(None, None, cache_sh, None))
    return jitted, (params_shape, spec["token"], caches_shape, spec["pos"])


def make_plan(cfg: ArchConfig, multi_pod: bool, plan_mode: str = "baseline",
              shape=None) -> MeshPlan:
    """``auto`` = the beyond-paper plan assembled from the §Perf hillclimbs:

    H1  small archs (<3B): pure data parallelism — TP collectives cost
        orders of magnitude more than the model's compute (61x on
        xlstm-125m).  Applied only when the global batch divides the
        widened batch axes (a 256-way batch axis with batch 32 replicates
        everything — measured 90x WORSE; see §Perf refuted-hypotheses).
    H2  decode: no FSDP (per-token weight gathers dominated), bf16 weights,
        2-D expert sharding, 2-D lookup tables.  Prefill keeps the baseline
        plan: its token count amortises FSDP gathers (serving plan measured
        0.26x on deepseek prefill).
    H3  giant-arch training (>100B): gradient accumulation (microbatches=4)
        trades ~1.5x collective for ~4x activation memory — the fit-first
        compromise; bf16 params halve the re-gather cost on real TPUs.
    """
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    n_batch_chips = 256 if not multi_pod else 512
    if plan_mode == "auto":
        if cfg.param_count() < 3e9 and shape is not None \
                and shape.global_batch % n_batch_chips == 0:
            return small_model_plan(batch_axes, "model", cfg.param_count())
        if shape is not None and shape.mode == "train" \
                and cfg.param_count() > 1e11:
            plan = MeshPlan(batch_axes=batch_axes)
            object.__setattr__(plan, "_microbatches", 4)
            return plan
        if shape is not None and shape.mode == "decode":
            return MeshPlan(batch_axes=batch_axes, enable_fsdp=False,
                            expert_data_shard=cfg.moe is not None,
                            dense_2d_shard=True)
    return MeshPlan(batch_axes=batch_axes)


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            out_dir: str = "experiments/dryrun", verbose: bool = True,
            plan_mode: str = "baseline", tag_suffix: str = ""):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if plan_mode == "auto" and shape.mode == "decode":
        # serving weights in bf16 (inference-standard; halves HBM + traffic)
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    if plan_mode == "auto" and shape.mode == "train" \
            and cfg.param_count() > 1e11:
        # giant-arch training: bf16 param storage halves the FSDP all-gather
        # traffic that gradient accumulation multiplies (bf16 master weights
        # + bf16 moments; stochastic-rounding caveat noted in EXPERIMENTS)
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16",
                                  moment_dtype="bfloat16")
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    plan = make_plan(cfg, multi_pod, plan_mode, shape)

    t0 = time.time()
    record = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
              "mesh": dict(mesh.shape), "n_chips": n_chips, "ok": False,
              "plan": plan_mode}
    try:
        jitted, args = build_step(cfg, shape, mesh, plan)
        with mesh:
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        analysis = analyze_hlo(hlo)
        coll = {k: float(v) for k, v in analysis.colls.items()}
        coll_total = float(analysis.collective_bytes)
        flops = float(analysis.flops)
        bytes_acc = float(analysis.bytes)
        mf = model_flops(cfg, shape)
        raw = {"flops": float(cost.get("flops", 0.0)),
               "bytes accessed": float(cost.get("bytes accessed", 0.0))}

        # memory_analysis fields (per device)
        mem_fields = {}
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem_fields[f] = int(getattr(mem, f, 0) or 0)
        args_b = mem_fields["argument_size_in_bytes"]
        temp_b = mem_fields["temp_size_in_bytes"]

        # roofline terms (cost_analysis is the per-partition SPMD module)
        t_compute = flops / PEAK_FLOPS_BF16
        t_memory = bytes_acc / HBM_BW
        t_coll = coll_total / ICI_BW
        terms = {"compute_s": t_compute, "memory_s": t_memory,
                 "collective_s": t_coll}
        dominant = max(terms, key=terms.get)

        record.update({
            "ok": True,
            "xla_cost_analysis_raw": raw,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "hlo_flops_per_chip": flops,
            "hlo_bytes_per_chip": bytes_acc,
            "collective_bytes_per_chip": coll_total,
            "collectives": coll,
            "memory_analysis": mem_fields,
            "model_flops_global": mf,
            "model_flops_per_chip": mf / n_chips,
            "useful_flop_ratio": (mf / n_chips) / flops if flops else None,
            "roofline": terms,
            "dominant": dominant,
            "step_time_bound_s": max(terms.values()),
            "hbm_gib_per_chip": (args_b + temp_b) / 2 ** 30,
        })
        if verbose:
            print(f"[{arch} x {shape_name}{' x multipod' if multi_pod else ''}] "
                  f"OK lower={t_lower:.1f}s compile={t_compile:.1f}s")
            print(f"  mem/chip: args={args_b/2**30:.2f}GiB "
                  f"temp={temp_b/2**30:.2f}GiB")
            print(f"  flops/chip={flops:.3e} bytes/chip={bytes_acc:.3e} "
                  f"coll/chip={coll_total:.3e}")
            print(f"  terms: compute={t_compute*1e3:.2f}ms "
                  f"memory={t_memory*1e3:.2f}ms coll={t_coll*1e3:.2f}ms "
                  f"-> {dominant} dominates; useful-flop ratio="
                  f"{record['useful_flop_ratio'] and round(record['useful_flop_ratio'],3)}")
    except Exception as e:  # noqa: BLE001
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[{arch} x {shape_name}] FAILED: {record['error']}")

    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}" + ("__multipod" if multi_pod else "")         + tag_suffix
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(record, f, indent=2, default=str)
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS) + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) baseline")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--plan", default="baseline",
                    choices=["baseline", "auto"],
                    help="auto = beyond-paper sharding optimizations")
    args = ap.parse_args()

    if args.all:
        results = []
        for arch in ARCH_IDS:
            for shape in INPUT_SHAPES:
                results.append(run_one(
                    arch, shape, args.multi_pod, args.out,
                    plan_mode=args.plan,
                    tag_suffix="__opt" if args.plan == "auto" else ""))
        ok = sum(r["ok"] for r in results)
        print(f"\n{ok}/{len(results)} combinations lowered+compiled")
        raise SystemExit(0 if ok == len(results) else 1)

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    rec = run_one(args.arch, args.shape, args.multi_pod, args.out,
                  plan_mode=args.plan,
                  tag_suffix="__opt" if args.plan == "auto" else "")
    raise SystemExit(0 if rec["ok"] else 1)


if __name__ == "__main__":
    main()

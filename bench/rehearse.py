#!/usr/bin/env python3
"""Compile rehearsal of each cell's device programs at the cell's sizes for
a described (not attached) TPU v5e, printing each program's
``memory_analysis()``.  Needs no chip: run it on the CPU host.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]

It compiles what the timed path runs: for the federated cells the cohort
engine's train, validation and Eq. 3 signature programs at the cohort size
(on one chip, or sharded over the 4-way clients mesh); for the serving cell
prefill and decode at the query's batch and lengths.  A program the chip's
compiler refuses, or one that does not fit, fails here."""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

GB = 1e9


def _mem(name, compiled) -> dict:
    ma = compiled.memory_analysis()
    row = {k: getattr(ma, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes")}
    total = (row["argument_size_in_bytes"] + row["output_size_in_bytes"]
             + row["temp_size_in_bytes"] - row["alias_size_in_bytes"])
    print(json.dumps({"program": name, "total_gb": round(total / GB, 3),
                      **row}), flush=True)
    return row


def federated(cell: str, topo) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    from bench.drivers.federated_rounds import _vgg_config
    from bench.gen import images
    from bench.harness import loader, manifest
    from bench.reference import vgg as ref
    from repro.fl.backend import CNNBackend
    from repro.fl.cohort import CohortBackend

    m = manifest.load()
    w = manifest.workload(m, cell)
    cfg = loader.config(manifest.config_entry(m, w["config"]))
    t = loader.traffic(w["traffic"])
    k = t["cohort_size"]
    steps = images.client_steps(t, cfg, cfg["batch_size"], cfg["local_epochs"])
    backend = CNNBackend(_vgg_config(cfg), lr=cfg["optimizer"]["lr"],
                         batch_size=cfg["batch_size"],
                         local_epochs=cfg["local_epochs"],
                         kernel_policy=cfg["kernel_policy"])
    if w["chips"] == 1:
        mesh = None
        sh = SingleDeviceSharding(topo.devices[0])
        rep = sh
    else:
        mesh = Mesh(np.array(topo.devices[:w["chips"]]), ("clients",))
        sh = NamedSharding(mesh, PartitionSpec("clients"))
        rep = NamedSharding(mesh, PartitionSpec())
    eng = CohortBackend(backend, capacity=k, mesh=mesh,
                        kernel_policy=cfg["kernel_policy"])
    params = jax.eval_shape(lambda key: ref.init(key, cfg),
                            jax.random.PRNGKey(0))

    def stacked(n, s):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype,
                                           sharding=s), params)

    def arr(shape, dtype, s=sh):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s)

    T, B = max(steps), cfg["batch_size"]
    img = (cfg["image_size"], cfg["image_size"], cfg["in_channels"])
    print(json.dumps({"cell": cell, "cohort": k, "pad_T": T,
                      "mean_steps": float(np.mean(steps))}), flush=True)
    _mem("cohort train", eng._train_jit.lower(
        stacked(k, sh), arr((k, T, B) + img, jnp.float32),
        arr((k, T, B), jnp.int32), arr((k, T), jnp.float32)).compile())
    _mem("cohort validation (512 rows)", eng._eval_jit.lower(
        stacked(k, sh), arr((k, 512) + img, jnp.float32),
        arr((k, 512), jnp.int32), arr((k, 512), jnp.float32)).compile())
    sig = eng._sig_jit.lower(stacked(k, sh), arr((k, 128) + img, jnp.float32),
                             arr((k, 128), jnp.float32)).compile()
    _mem("cohort Eq. 3 signature (128 rows)", sig)
    print(json.dumps({"signature_kernel_in_program":
                      "tpu_custom_call" in sig.as_text()}), flush=True)
    m_pad = 16 if w["chips"] == 1 else 32
    _mem(f"tip validation, {m_pad} candidates", eng._eval_many_jit.lower(
        stacked(m_pad, sh), arr((512,) + img, jnp.float32, rep),
        arr((512,), jnp.int32, rep), arr((512,), jnp.float32, rep)).compile())


def serving(cell: str, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench.drivers.replica_serving import _program_config
    from bench.harness import loader, manifest
    from bench.reference import decoder as ref
    from repro.launch.serve import extend_caches, make_serving_fns
    from repro.runtime import serve_runtime

    m = manifest.load()
    w = manifest.workload(m, cell)
    cfg = loader.config(manifest.config_entry(m, w["config"]))
    t = loader.traffic(w["traffic"])
    arch = _program_config(cfg)
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: ref.init(k, cfg), jax.random.PRNGKey(0)))
    b, s, new = t["batch"], t["prompt_len"], t["new_tokens"]
    prefill, decode = make_serving_fns(arch, serve_runtime(cfg["kernel_policy"]))
    toks = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one)}
    pc = prefill.lower(params, toks).compile()
    _mem(f"prefill {b}x{s}", pc)
    print(json.dumps({"flash_kernel_in_prefill":
                      "tpu_custom_call" in pc.as_text()}), flush=True)
    caches = jax.eval_shape(lambda p, x: extend_caches(
        prefill(p, x)[1], arch, new), params, toks)
    caches = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), caches)
    _mem(f"decode {b} x cache {s + new}", decode.lower(
        params, jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one), caches,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile())


def main(argv=None) -> int:
    from jax.experimental import topologies

    from bench.harness import loader, manifest
    m = manifest.load()
    cells = (argv if argv else sys.argv[1:]) or [w["name"] for w in
                                                  m["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for cell in cells:
        w = manifest.workload(m, cell)
        drv = loader.config(manifest.config_entry(m, w["config"]))["driver"]
        {"federated_rounds": federated, "replica_serving": serving}[drv](
            cell, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Compile rehearsal of an MLA + expert-layer serving cell (the
``replica_serving_mla_moe`` driver's) for a described, not attached, TPU v5e:
prefill and decode at the cell's batch and lengths, and the reference's
dense and expert layers at one and at every replayed row, each with its
``memory_analysis()``.  Needs no chip: run it on the CPU host.

    JAX_PLATFORMS=cpu python bench/rehearse_mla_moe.py [cell]
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

GB = 1e9


def _mem(name, compiled) -> None:
    ma = compiled.memory_analysis()
    row = {k: getattr(ma, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")}
    total = (row["argument_size_in_bytes"] + row["output_size_in_bytes"]
             + row["temp_size_in_bytes"] - row["alias_size_in_bytes"])
    print(json.dumps({"program": name, "total_gb": round(total / GB, 3),
                      **row}), flush=True)


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.drivers.replica_serving_mla_moe import program_config
    from bench.harness import loader, manifest
    from bench.reference import deepseek_v2 as ref
    from repro.launch.serve import extend_caches, make_serving_fns
    from repro.runtime import serve_runtime

    cell = (argv if argv else sys.argv[1:] or
            ["deepseek-v2.replica-decode-2k"])[0]
    m = manifest.load()
    w = manifest.workload(m, cell)
    cfg = loader.config(manifest.config_entry(m, w["config"]))
    t = loader.traffic(w["traffic"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    arch = program_config(cfg)
    params = on_chip(jax.eval_shape(lambda k: ref.init(k, cfg),
                                    jax.random.PRNGKey(0)))
    b, s, new = t["batch"], t["prompt_len"], t["new_tokens"]
    prefill, decode = make_serving_fns(arch, serve_runtime(cfg["kernel_policy"]))
    toks = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one)}
    pc = prefill.lower(params, toks).compile()
    _mem(f"prefill {b}x{s}", pc)
    caches = on_chip(jax.eval_shape(lambda p, x: extend_caches(
        prefill.jitted(p, x)[1], arch, new), params, toks))
    dc = decode.lower(params, jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                                   sharding=one),
                      caches, jax.ShapeDtypeStruct((), jnp.int32,
                                                   sharding=one)).compile()
    _mem(f"decode {b} x cache {s + new}", dc)
    print(json.dumps({"gmm_kernel_in_decode":
                      "dagafl_moe_gmm" in dc.as_text()}), flush=True)
    fz = ref.freeze(cfg)
    for si, stage in enumerate(params["stages"]):
        lp = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype,
                                           sharding=one), stage["l0"])
        for rows in (1, b):
            x = jax.ShapeDtypeStruct((rows, s + new - 1, cfg["hidden_size"]),
                                     jnp.float32, sharding=one)
            held = None       # the driver routes expert layers as served
            if "router" in lp["ffn"]:
                held = jax.ShapeDtypeStruct(
                    x.shape[:2] + (cfg["n_routed_experts"],), jnp.bool_,
                    sharding=one)
            _mem(f"reference stage {si} layer, {rows} rows",
                 ref.layer.lower(lp, x, held, cfg_key=fz).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())

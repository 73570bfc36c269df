"""Runtime knobs threaded through model apply functions, and the process
setup the launchers share (persistent compile cache)."""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Optional, Tuple

# <repo>/.jax_cache: a fixed path inside the checkout (listed in .gitignore)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


@dataclass(frozen=True)
class Runtime:
    use_pallas: bool = False       # route hot-spots through Pallas kernels
    # kernel dispatch policy for those hot-spots (repro.kernels.dispatch):
    # "auto" resolves via $REPRO_KERNEL_POLICY then the platform (TPU ->
    # "compiled", else "interpret"); "reference" forces the pure-jnp
    # oracles.  Supersedes pallas_interpret, which remains only as a
    # legacy explicit override consumed by kernels.dispatch.
    kernel_policy: str = "auto"
    pallas_interpret: Optional[bool] = None  # legacy; None = follow policy
    remat: bool = True             # checkpoint scanned periods in training
    want_signature: bool = False   # emit DAG-AFL feature signature in aux
    signature_tau: float = 0.05
    signature_dims: int = 64
    # activation sharding: constrain the residual stream's batch dim to these
    # mesh axes (set by the launcher; None = no constraints, e.g. CPU tests)
    batch_axes: Optional[Tuple[str, ...]] = None
    batch_axis_size: int = 1
    # mesh handle for shard_map regions (recurrent blocks move their weight-
    # gradient reduction out of the timestep loop this way; see xlstm.py)
    mesh: Optional[Any] = None


DEFAULT = Runtime()


def serve_runtime(kernel_policy: Optional[str] = None) -> Runtime:
    """Runtime for the serving path (prefill + KV-cache decode): no
    signature extraction, kernel hot-spots routed per ``kernel_policy``
    (None / "reference" keep the stock-XLA math — the same convention the
    FL backends use for their ``kernel_policy`` knob)."""
    if kernel_policy is None or kernel_policy == "reference":
        return Runtime()
    return Runtime(use_pallas=True, kernel_policy=kernel_policy)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a launcher process and
    return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache from outside:
    JAX reads it itself and nothing is set here.  Otherwise the cache goes
    to the fixed :data:`COMPILE_CACHE_DIR`, so every run from the same
    checkout reuses what earlier runs compiled.  Launchers call this once
    at start-up; tests never do."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR

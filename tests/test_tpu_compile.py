"""Compile the main path's kernels and steps for a TPU v5e, without a chip.

The TPU compiler runs here against a described ``v5e:2x2`` topology: it
refuses what the chip's compiler would refuse — Mosaic block-shape rules,
unsupported comparisons, programs that overflow device memory — which the
Pallas interpreter of the CPU tests never sees.  Nothing runs, so these
tests say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and each test worker
imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16e9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                             # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_signature_per_channel_compiles_at_vgg16_widths(one_chip):
    from repro.kernels import ops as kops
    x = jax.ShapeDtypeStruct((64, 32, 32, 64), jnp.float32, sharding=one_chip)
    c = _compile(lambda a: kops.signature_per_channel(
        a, tau=0.0, policy="compiled"), x)
    assert _has_kernel(c)


def _vgg16_engine():
    from repro.configs.cnn import VGG16
    from repro.fl.backend import CNNBackend
    from repro.fl.cohort import CohortBackend
    backend = CNNBackend(VGG16, local_epochs=1, batch_size=64,
                         kernel_policy="compiled")
    return backend, CohortBackend(backend, capacity=8,
                                  kernel_policy="compiled")


def _stacked_vgg16(backend, k, sharding):
    from repro.core.aggregate import tree_stack
    return _sds(jax.eval_shape(lambda key: tree_stack(
        [backend.init(key)] * k), jax.random.PRNGKey(0)), sharding)


def test_cohort_signature_program_compiles_k8(one_chip):
    """The cohort engine's Eq. 3 program: the kernel inside ``lax.map``
    over K=8 clients of VGG16 at 32x32x3."""
    backend, engine = _vgg16_engine()
    stacked = _stacked_vgg16(backend, 8, one_chip)
    x = jax.ShapeDtypeStruct((8, 128, 32, 32, 3), jnp.float32,
                             sharding=one_chip)
    mask = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=one_chip)
    c = engine._sig_jit.lower(stacked, x, mask).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("path", ["signature", "per_sample_signature"])
def test_bf16_signatures_compile_at_d2048(path, one_chip):
    """The LM signature hot paths take bf16 final-norm activations."""
    from repro.kernels import ops as kops
    from repro.models import transformer as tfm
    from repro.runtime import Runtime
    if path == "signature":
        x = jax.ShapeDtypeStruct((512, 2048), jnp.bfloat16,
                                 sharding=one_chip)
        fn = lambda a: kops.signature(a, tau=0.05, n_sig=64,
                                      policy="compiled")
    else:
        x = jax.ShapeDtypeStruct((4, 128, 2048), jnp.bfloat16,
                                 sharding=one_chip)
        rt = Runtime(use_pallas=True, kernel_policy="compiled")
        fn = lambda h: tfm.per_sample_signature(h, rt)
    assert _has_kernel(_compile(fn, x))


def test_flash_attention_compiles_at_internlm2_widths(one_chip):
    """head_dim 128, S=4096 (multi-block q and kv), GQA 16 query / 8 kv."""
    from repro.kernels import ops as kops
    q = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    c = _compile(lambda a, b, v: kops.flash_attention(
        a, b, v, causal=True, policy="compiled"), q, kv, kv)
    assert _has_kernel(c)


def test_vgg16_cohort_train_step_fits_one_chip(one_chip):
    """K=8 VGG16 clients, 5 masked SGD steps of 64 images each."""
    backend, engine = _vgg16_engine()
    k, t, b = 8, 5, 64
    stacked = _stacked_vgg16(backend, k, one_chip)
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    c = engine._train_jit.lower(stacked, sds((k, t, b, 32, 32, 3)),
                                sds((k, t, b), jnp.int32),
                                sds((k, t))).compile()
    m = c.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one v5e"

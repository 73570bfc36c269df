"""Vectorized cohort execution engine: K clients as ONE batched XLA program.

The simulator's event heap decides *when* each client's round runs in
simulated time; this module decides *how* the container executes the work.
Instead of K serial ``train_local`` / ``evaluate`` / ``signature`` calls, a
:class:`CohortBackend` stacks the K clients' parameter pytrees along a
leading client axis (``tree_stack``) and runs local training, evaluation and
signature extraction as single batched jitted programs.

The batched programs themselves are supplied per backend family by a
*cohort programs* suite (:class:`CohortPrograms`):

  * :class:`CNNCohortPrograms` — the paper-faithful VGG path.  Training is
    ``jax.vmap``-batched with the convolutions rewritten as im2col GEMMs
    (see ``_conv_as_matmul``); evaluation and signatures are FLOP-light, so
    they are ``lax.map``-fused into one dispatch while keeping the
    dense-conv lowering per client.
  * :class:`LMCohortPrograms` — the transformer (``LMBackend``) path.
    Training vmaps the stacked K-client param pytrees over the same masked
    scan (token batches pre-sampled per client exactly like the sequential
    RNG stream); evaluation and Eq. 3 signatures (threshold-zero fractions
    of the designated final-norm activations, per sample so padding masks
    out) run ``lax.map``-fused like the CNN ones.

``register_cohort_programs`` extends the registry; ``CohortBackend.supports``
answers for any backend instance, and callers fall back to the sequential
path for unregistered backends.

Ragged shards are handled by padding + masking:

  * training: every client's step sequence is padded to a common length
    ``T``; masked steps compute a gradient on zero-padding but the pytree
    select keeps the pre-step params/optimizer state, so padding NEVER
    leaks into the trained weights.
  * evaluation/signature: sample axes are padded to a common length and the
    accuracy / Eq. 3 zero-fraction means are masked, so padded samples carry
    zero weight.

Shape discipline (CPU/TPU friendly): the cohort axis is padded to powers of
two capped at ``capacity``, the training step axis to a monotone registered
maximum, and eval/signature sample axes to per-call targets quantized by
``eval_pad_quantum`` — so steady-state dispatches hit a bounded set of
compiled programs instead of retracing.  Eval/signature data buffers are
cached per dataset with an LRU bound (``eval_cache_entries``) so a
long-running simulator never pins an unbounded set of shards.

SPMD over a device mesh: passing ``mesh`` (any ``jax.sharding.Mesh`` whose
``clients_axis`` axis has more than one device — see
``repro.launch.mesh.make_cohort_mesh``) turns every batched program into one
``shard_map`` SPMD program: the stacked client axis is sharded over the mesh
so each device runs the vmapped train step (and the lax.map-fused
eval/signature programs) on its own client group, with no cross-device
communication inside a window — client rounds are embarrassingly parallel;
the cross-device work is the window's Eq. 6 aggregation, which
``repro.core.aggregate`` phrases as psum collectives over the same axis.
Cohort padding rounds up to a mesh-size multiple so the groups divide
evenly; masking keeps the padding out of every result exactly as on one
device.  ``mesh=None`` (or a 1-device mesh) is bit-for-bit today's
single-device path.

2-D (clients, data) meshes (``make_cohort_mesh(C, data=D)``) additionally
shard each client group's TRAINING DATA: the per-step batch axis (and the
eval/signature sample axes) splits over the ``data`` axis, every device
computes the sum-form loss/metric terms on its local sample slice, and one
``lax.psum`` over ``data`` re-assembles the full-batch gradient (and the
masked eval/signature means) inside each client group — the client models
stay replicated within a group and advance in lockstep, so the 2-D result
matches the 1-D clients-mesh result up to float-reduction order (property-
tested).  Ragged batch/sample axes pad to a ``data`` multiple with
zero-weight rows (``bm`` masks), so non-divisible batch sizes cost padding
FLOPs but never numerics.  The suites expose their losses/metrics in
sum-and-count form (``sum_loss``/``eval_terms``) exactly so the engine can
place the division AFTER the psum.

Host-side window assembly lives in
:class:`repro.data.pipeline.WindowAssembler`: a double-buffered background
stage that samples, stacks, pads and ``device_put``s a window while the
device computes (``prefetch_window``/``take``), preserving the sequential
per-seed np RNG streams exactly.  This all works identically for both
program suites — the mesh plumbing never inspects what the programs
compute.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import List, Optional, Sequence, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.aggregate import (next_pow2, pad_leading, round_up_multiple,
                                  tree_stack, tree_unstack)
from repro.fl.backend import CNNBackend, LMBackend
from repro.optim.optimizers import apply_updates


def _tree_select(keep, new, old):
    """Per-leaf ``where(keep, new, old)`` — identity step when masked out."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(keep, a, b), new, old)


# -- scenario update transforms (see repro/fl/scenarios.py) -------------------
#
#   new' = agg + gamma * (new - agg) + sigma * N(0, I)
#
# gamma = scale_gamma < 0 is scaled-gradient model poisoning, gamma = 0 is a
# free-rider republishing the aggregate, sigma > 0 is DP noise.  gamma=1 /
# sigma=0 is the identity only ALGEBRAICALLY (a + 1*(l-a) reorders the float
# ops), so callers skip unaffected dispatches entirely and the stacked
# program re-selects unaffected rows' original bits below.


def _perturb_key(seed: int, client: int, seq: int):
    """One PRNG key per (scenario seed, client, per-client update seq) —
    shared by the single and stacked programs, so they agree bit-for-bit."""
    key = jax.random.PRNGKey(seed)
    return jax.random.fold_in(jax.random.fold_in(key, client), seq)


def _perturb_tree(params, agg, gamma, sigma, key):
    leaves, treedef = jax.tree_util.tree_flatten(params)
    agg_leaves = jax.tree_util.tree_leaves(agg)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, leaf, a in zip(keys, leaves, agg_leaves):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            out.append(leaf)
            continue
        v = a + gamma * (leaf - a)
        out.append(v + sigma * jax.random.normal(k, leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


_PERTURB_ONE = jax.jit(_perturb_tree)
_PERTURB_STACKED = jax.jit(jax.vmap(_perturb_tree))


def perturb_update(agg, new, plan: dict, k: int):
    """Apply row ``k`` of a :meth:`repro.fl.scenarios.Scenario.update_plan`
    to one trained model (the sequential path / windows of one)."""
    key = _perturb_key(plan["seed"], int(plan["clients"][k]),
                       int(plan["seqs"][k]))
    return _PERTURB_ONE(new, agg, jnp.float32(plan["gammas"][k]),
                        jnp.float32(plan["sigmas"][k]), key)


def perturb_cohort_stacked_trees(agg_stacked, new_stacked, plan: dict):
    """Whole-window transform as ONE vmapped jitted program over the stacked
    K-client pytrees, then a per-leaf select that restores unaffected rows'
    exact bits (fault injection must not perturb honest clients)."""
    keys = jnp.stack([_perturb_key(plan["seed"], int(c), int(s))
                      for c, s in zip(plan["clients"], plan["seqs"])])
    transformed = _PERTURB_STACKED(new_stacked, agg_stacked,
                                   jnp.asarray(plan["gammas"]),
                                   jnp.asarray(plan["sigmas"]), keys)
    keep = jnp.asarray(plan["affected"])
    return jax.tree_util.tree_map(
        lambda t, o: jnp.where(
            keep.reshape(keep.shape + (1,) * (t.ndim - 1)), t, o),
        transformed, new_stacked)


def _conv_as_matmul(x, w):
    """SAME-padding stride-1 convolution as im2col + one GEMM.

    ``jax.vmap`` over per-client kernels turns ``lax.conv`` into a
    batch-grouped convolution that XLA:CPU executes on a slow generic path
    (measured ~2x slower than K serial convs).  The same contraction phrased
    as a matmul vmaps into a single batched GEMM — the fast path on CPU
    (Eigen) and the MXU-native form on TPU.  Math is identical to
    ``lax.conv_general_dilated`` up to float summation order.
    """
    kh, kw, cin, cout = w.shape
    ph, pw = kh // 2, kw // 2
    b, h, ww, c = x.shape
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    # (B, H, W, kh*kw, C): taps ordered (kh, kw) row-major to match the
    # HWIO kernel layout flattened as (kh*kw*cin, cout)
    patches = jnp.stack([xp[:, i:i + h, j:j + ww, :]
                         for i in range(kh) for j in range(kw)], axis=3)
    patches = patches.reshape(b * h * ww, kh * kw * c)
    y = patches @ w.reshape(kh * kw * cin, cout)
    return y.reshape(b, h, ww, cout)


def _max_pool_2x2(x):
    b, h, w, c = x.shape
    x = x[:, :h // 2 * 2, :w // 2 * 2]        # VALID-window truncation
    x = x.reshape(b, h // 2, 2, w // 2, 2, c)
    return jnp.max(x, axis=(2, 4))


# ---------------------------------------------------------------------------
# per-backend cohort program suites
# ---------------------------------------------------------------------------


class CohortPrograms:
    """Batched train/eval/signature program suite for one backend family.

    :class:`CohortBackend` supplies the *execution* discipline — stacking,
    padding, masking, vmap/lax.map fusion, jit, mesh ``shard_map`` — and
    delegates everything backend-specific to this interface.  A suite owns:

    traced (called inside the engine's jitted programs):
      * ``loss(params, x, y)``            scalar training loss on one batch
      * ``sum_loss(params, x, y, w, denom)``  sum-form loss: row-weighted
        loss sum over ``denom`` (the GLOBAL weighted count in this suite's
        loss units), so a psum over a data mesh axis reconstructs ``loss``
        on the full batch; must equal ``loss`` when ``w`` is all-ones and
        ``denom`` the local count
      * ``loss_denom(w, y)``              local count in loss units for a
        row-weight vector ``w`` (samples for CNN, tokens for LM)
      * ``eval_terms(params, xs, ys, ms)``   (num, den) masked-accuracy
        terms on one shard; ``masked_eval`` = num / max(den, 1)
      * ``eval_shared_terms(params, x, y, mask)``  (num (K,), den (K,))
        terms for ONE model on K stacked shards
      * ``sample_signature(params, xs)``  per-sample Eq. 3 signature rows,
        so the engine can take a padding-masked mean

    host-side (batch assembly, matching the sequential RNG streams exactly):
      * ``train_steps(ds, epochs)``       step count one client will run
      * ``client_batches(ds, seed, epochs)``  (xb (T, ...), yb (T, ...))
      * ``eval_single(ds, limit, kind)``  (x (n, ...), y, n) for one shard;
        ``kind`` is "eval" or "sig" (suites whose two paths sample
        differently — the LM backend — return different tokens per kind)
      * ``summarize_losses(losses, steps, epochs)``  the sequential path's
        per-client loss contract
      * ``evaluate_one(params, ds, limit)``  sequential single-model eval
        (the M=1 fast path of ``evaluate_many``)
    """

    backend_cls: Type = None
    # lax.map (dispatch fusion, per-iteration lowering kept) vs jax.vmap
    # (arithmetic batching) for the eval/signature programs: convs vmap onto
    # XLA:CPU's slow grouped path, transformers vmap onto batched GEMMs
    vmap_eval: bool = False
    # below this many candidate models, evaluate_many runs the sequential
    # per-model program: the pow2 model-axis padding + tree_stack overhead
    # outweigh fusion for tiny sweeps (suite-specific dispatch economics)
    eval_many_min_batch: int = 1

    def __init__(self, backend, kernel_policy: Optional[str] = None):
        self.backend = backend
        self.cfg = backend.cfg
        # concrete kernel policy for the suite's Eq. 3 hot paths: an explicit
        # argument wins, else inherit the backend's (backends without the
        # knob mean the incumbent pure-jnp math)
        if kernel_policy is None:
            self.kernel_policy = getattr(backend, "kernel_policy", "reference")
        else:
            from repro.kernels.dispatch import resolve_policy
            self.kernel_policy = resolve_policy(kernel_policy)

    @property
    def default_epochs(self) -> int:
        raise NotImplementedError

    # traced
    def loss(self, params, x, y):
        raise NotImplementedError

    def sum_loss(self, params, x, y, w, denom):
        raise NotImplementedError

    def loss_denom(self, w, y):
        raise NotImplementedError

    def eval_terms(self, params, xs, ys, ms):
        raise NotImplementedError

    def eval_shared_terms(self, params, x, y, mask):
        raise NotImplementedError

    def masked_eval(self, params, xs, ys, ms):
        """Masked accuracy on one shard — the division placed after the
        suite's sum-form terms (same math the 2-D data-mesh path psums)."""
        num, den = self.eval_terms(params, xs, ys, ms)
        return num / jnp.maximum(den, 1.0)

    def eval_shared(self, params, x, y, mask):
        """ONE model on K stacked shards, via the sum-form terms."""
        num, den = self.eval_shared_terms(params, x, y, mask)
        return num / jnp.maximum(den, 1.0)

    def sample_signature(self, params, xs):
        raise NotImplementedError

    # host-side
    def train_steps(self, ds, epochs: int) -> int:
        raise NotImplementedError

    def client_batches(self, ds, seed: int, epochs: int):
        raise NotImplementedError

    def eval_single(self, ds, limit: int, kind: str):
        raise NotImplementedError

    def summarize_losses(self, losses: np.ndarray, steps: Sequence[int],
                         epochs: int) -> List[float]:
        raise NotImplementedError

    def evaluate_one(self, params, ds, limit: int) -> float:
        raise NotImplementedError


class CNNCohortPrograms(CohortPrograms):
    """VGG-family programs (the paper's experimental setup).

    Training runs the matmul-form forward (`_conv_as_matmul`) so the vmapped
    cohort step lowers to batched GEMMs; evaluation and signatures keep the
    dense-conv lowering per client and rely on ``lax.map`` dispatch fusion
    (see the engine's ``_eval_impl`` note).
    """

    backend_cls = CNNBackend

    @property
    def default_epochs(self) -> int:
        return self.backend.local_epochs

    def _forward(self, params, x):
        """``cnn_forward`` in matmul form (see :func:`_conv_as_matmul`)."""
        for stack_params in params["convs"]:
            for p in stack_params:
                x = jax.nn.relu(_conv_as_matmul(x, p["w"]) + p["b"])
            x = _max_pool_2x2(x)
        x = x.reshape(x.shape[0], -1)
        for p in params["fcs"][:-1]:
            x = jax.nn.relu(x @ p["w"] + p["b"])
        p = params["fcs"][-1]
        return x @ p["w"] + p["b"]

    def _sample_losses(self, params, x, y):
        """(B,) per-sample cross-entropy in matmul form."""
        logits = self._forward(params, x)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return logz - ll

    def loss(self, params, x, y):
        return jnp.mean(self._sample_losses(params, x, y))

    def sum_loss(self, params, x, y, w, denom):
        """Row-weighted loss sum over the GLOBAL sample count: psum over a
        data mesh axis reconstructs the full-batch ``loss`` exactly."""
        return jnp.sum(self._sample_losses(params, x, y) * w) / denom

    def loss_denom(self, w, y):
        return jnp.sum(w)

    def eval_terms(self, params, xs, ys, ms):
        """Masked #correct terms on one shard, conv-form forward: eval is
        FLOP-light and per-client weights make a vmapped conv lower to
        XLA:CPU's slow grouped path, so dense-conv + dispatch fusion wins
        over arithmetic batching here."""
        from repro.models import cnn as cnn_mod
        logits, _ = cnn_mod.cnn_forward(params, xs, self.cfg)
        correct = (jnp.argmax(logits, -1) == ys).astype(jnp.float32)
        return jnp.sum(correct * ms), jnp.sum(ms)

    def eval_shared_terms(self, params, x, y, mask):
        """ONE model on K padded shards (publisher's convergence monitor).
        The params carry no cohort axis, so the K shards simply fold into
        the batch dimension of the conv-form forward — true batching."""
        from repro.models import cnn as cnn_mod
        k, n = y.shape
        flat = x.reshape((k * n,) + x.shape[2:])
        logits, _ = cnn_mod.cnn_forward(params, flat, self.cfg)
        correct = (jnp.argmax(logits.reshape(k, n, -1), -1) == y)
        correct = correct.astype(jnp.float32) * mask
        return jnp.sum(correct, axis=1), jnp.sum(mask, axis=1)

    def sample_signature(self, params, x):
        """Per-sample Eq. 3 zero fractions, conv-form, EARLY EXIT: only the
        convs up to ``signature_layer`` run — the classifier head and later
        stacks contribute nothing to the signature."""
        cfg = self.cfg
        conv_idx = 0
        for stack_params in params["convs"]:
            for p in stack_params:
                x = jax.lax.conv_general_dilated(
                    x, p["w"], window_strides=(1, 1), padding="SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                x = jax.nn.relu(x + p["b"])
                if conv_idx == cfg.signature_layer:
                    # per-sample zero fractions through the kernel dispatch
                    # layer ("reference" -> the incumbent jnp.mean bits)
                    from repro.kernels import ops as kops
                    return kops.signature_per_channel(
                        x, tau=0.0, policy=self.kernel_policy)    # (N, ch)
                conv_idx += 1
            x = _max_pool_2x2(x)
        raise ValueError(f"signature_layer {cfg.signature_layer} out of "
                         f"range for {cfg.name}")

    def train_steps(self, ds, epochs: int) -> int:
        b = self.backend
        return epochs * max(len(ds) // b.batch_size, 1)

    def client_batches(self, ds, seed: int, epochs: int):
        """Replicates ``CNNBackend.train_local``'s exact per-client batch
        sampling (same np RNG stream per seed)."""
        b = self.backend
        rng = np.random.default_rng(seed)
        xs, ys = [], []
        for _ in range(epochs):
            xb, yb = b._batches(ds, rng)
            xs.append(xb)
            ys.append(yb)
        return jnp.concatenate(xs), jnp.concatenate(ys)

    def eval_single(self, ds, limit: int, kind: str):
        n = min(len(ds), limit)
        return jnp.asarray(ds.x[:n]), jnp.asarray(ds.y[:n]), n

    def summarize_losses(self, losses, steps, epochs) -> List[float]:
        """Sequential contract: mean loss over the client's LAST epoch."""
        per_epoch = [s // epochs for s in steps]
        return [float(np.mean(losses[i, s - per_epoch[i]:s]))
                for i, s in enumerate(steps)]

    def evaluate_one(self, params, ds, limit: int) -> float:
        return self.backend.evaluate(params, ds, limit)


class LMCohortPrograms(CohortPrograms):
    """Transformer (``LMBackend``) programs: the framework-scale path.

    Training vmaps the per-client SGD scan over the stacked param pytrees —
    the transformer step is already GEMM-shaped, so unlike the CNN path no
    lowering rewrite is needed; the win is one fused dispatch (and one
    shard_map program under a mesh) instead of K serial jitted calls.  Token
    batches are pre-sampled on the host with the SAME np RNG stream as
    ``LMBackend.train_local``/``evaluate``/``signature``, so cohort and
    sequential runs see identical data.  Signatures are the Eq. 3
    threshold-zero fractions of the designated signature layer (the
    final-norm hidden state, matching ``Runtime.want_signature``), computed
    per sample so the engine's padding mask keeps padded rows out.
    """

    backend_cls = LMBackend
    vmap_eval = True            # transformer forwards vmap onto batched GEMMs
    eval_many_min_batch = 3

    def __init__(self, backend, kernel_policy: Optional[str] = None):
        super().__init__(backend, kernel_policy)
        import dataclasses
        # eval/signature forwards don't need the fused aux signature (we
        # compute per-sample rows ourselves for maskability); the suite's
        # kernel policy decides whether they run the Pallas hot paths
        use_pallas = self.kernel_policy != "reference"
        self.runtime = dataclasses.replace(
            backend.runtime, want_signature=False, use_pallas=use_pallas,
            kernel_policy=self.kernel_policy)
        # per-sample Eq. 3 rows read tau/dims off this one (keeps the
        # backend's want_signature semantics but the suite's policy)
        self.sig_runtime = dataclasses.replace(
            backend.runtime, use_pallas=use_pallas,
            kernel_policy=self.kernel_policy)
        # the batched train step drops remat: rematerialization trades
        # compute for activation memory, the right call for production-size
        # models but pure overhead for FL-size ones (~1.3x extra forward
        # FLOPs); gradients are bit-comparable either way, which the
        # cohort-vs-sequential property tests pin down.  Training always
        # stays on the stock-XLA path: pallas_call has no VJP rule.
        self.train_runtime = dataclasses.replace(self.runtime, remat=False,
                                                 use_pallas=False)

    @property
    def default_epochs(self) -> int:
        return self.backend.local_steps

    def loss(self, params, x, y):
        """x (B, S+1) token rows; y (B, S) = x[:, 1:] (next-token labels)."""
        from repro.models import transformer as tfm
        batch = {"tokens": x[:, :-1], "labels": y}
        return tfm.loss_fn(params, batch, self.cfg, self.train_runtime)[0]

    def sum_loss(self, params, x, y, w, denom):
        """Row-weighted token-CE sum over the GLOBAL token count ``denom``
        (+ the MoE aux weighted by the local token fraction, so dense
        models — aux 0 — psum to exactly the full-batch ``loss`` and MoE
        models psum to the count-weighted mean of per-shard auxes)."""
        from repro.models import transformer as tfm
        m = jnp.broadcast_to(w[:, None], y.shape).astype(jnp.float32)
        batch = {"tokens": x[:, :-1], "labels": y, "mask": m}
        total, _ = tfm.loss_fn(params, batch, self.cfg, self.train_runtime)
        return total * jnp.sum(m) / denom

    def loss_denom(self, w, y):
        return jnp.sum(w) * y.shape[-1]

    def _row_correct(self, params, xs, ys):
        """(N, S) correctness grid for a padded token shard."""
        from repro.models import transformer as tfm
        logits, _, _ = tfm.forward(params, {"tokens": xs[:, :-1]}, self.cfg,
                                   self.runtime, mode="prefill")
        return (jnp.argmax(logits, -1) == ys).astype(jnp.float32)

    def eval_terms(self, params, xs, ys, ms):
        """Per-row next-token accuracy terms, padding-masked over rows.
        Rows all carry ``seq_len`` real positions, so the masked mean of
        row means equals the sequential path's grand mean."""
        per_row = jnp.mean(self._row_correct(params, xs, ys), axis=-1)
        return jnp.sum(per_row * ms), jnp.sum(ms)

    def eval_shared_terms(self, params, x, y, mask):
        """ONE model on K stacked token shards: fold K into the batch dim —
        true batching, same as the CNN suite."""
        k, n = x.shape[0], x.shape[1]
        flat = x.reshape((k * n,) + x.shape[2:])
        correct = self._row_correct(params, flat, y.reshape((k * n,) +
                                                            y.shape[2:]))
        per_row = jnp.mean(correct, axis=-1).reshape(k, n) * mask
        return jnp.sum(per_row, axis=1), jnp.sum(mask, axis=1)

    def sample_signature(self, params, xs):
        """(N, sig_dims) Eq. 3 rows from the designated signature layer."""
        from repro.models import transformer as tfm
        h, _, _ = tfm.forward_hidden(params, {"tokens": xs[:, :-1]}, self.cfg,
                                     self.runtime, mode="prefill")
        return tfm.per_sample_signature(h, self.sig_runtime)

    def train_steps(self, ds, epochs: int) -> int:
        # one step per "epoch" regardless of stream length (LMBackend
        # samples `epochs` fixed-size token batches)
        return epochs

    def client_batches(self, ds, seed: int, epochs: int):
        """Same np RNG stream as ``LMBackend.train_local``: one
        ``_sample`` call drawing (epochs, B, S+1) token windows."""
        toks = self.backend._sample(ds, np.random.default_rng(seed), epochs)
        return toks, toks[:, :, 1:]

    # sequential LMBackend.evaluate/signature fix their sampling seeds
    _EVAL_SEEDS = {"eval": 1, "sig": 2}

    def eval_single(self, ds, limit: int, kind: str):
        toks = self.backend._sample(ds, np.random.default_rng(
            self._EVAL_SEEDS[kind]), 1)[0]
        return toks, toks[:, 1:], int(toks.shape[0])

    def summarize_losses(self, losses, steps, epochs) -> List[float]:
        """Sequential contract: mean loss over ALL the client's steps."""
        return [float(np.mean(losses[i, :s])) for i, s in enumerate(steps)]

    def evaluate_one(self, params, ds, limit: int) -> float:
        return self.backend.evaluate(params, ds)


_PROGRAM_REGISTRY: List[Type[CohortPrograms]] = []


def register_cohort_programs(programs_cls: Type[CohortPrograms]) -> None:
    """Register a program suite; later registrations win on overlap."""
    if not isinstance(getattr(programs_cls, "backend_cls", None), type):
        raise TypeError(
            f"{programs_cls.__name__}.backend_cls must name the backend "
            "class the suite batches for")
    _PROGRAM_REGISTRY.insert(0, programs_cls)


register_cohort_programs(CNNCohortPrograms)
register_cohort_programs(LMCohortPrograms)


def _programs_for(backend) -> Optional[Type[CohortPrograms]]:
    for cls in _PROGRAM_REGISTRY:
        if isinstance(backend, cls.backend_cls):
            return cls
    return None


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class CohortBackend:
    """Batched train/eval/signature over a stacked K-client pytree.

    Wraps a per-client backend; ``capacity`` fixes the cohort axis so every
    flush compiles to the same program (short cohorts are padded with a
    repeat of the last client and fully masked out).  The backend-specific
    programs come from the :class:`CohortPrograms` registry.
    """

    def __init__(self, backend, capacity: Optional[int] = None,
                 eval_pad_quantum: int = 64, mesh=None,
                 clients_axis: str = "clients", data_axis: str = "data",
                 eval_cache_entries: int = 64, overlap: bool = True,
                 kernel_policy: Optional[str] = None):
        programs_cls = _programs_for(backend)
        if programs_cls is None:
            raise TypeError(
                f"no CohortPrograms registered for {type(backend).__name__}; "
                f"known: {[c.backend_cls.__name__ for c in _PROGRAM_REGISTRY]}")
        # third-party suites registered before the kernel_policy kwarg keep
        # working: only pass it through when the caller asked for one
        if kernel_policy is None:
            self.programs = programs_cls(backend)
        else:
            self.programs = programs_cls(backend, kernel_policy=kernel_policy)
        self.backend = backend
        self.capacity = capacity
        # padding quantum for eval/signature sample axes: shards pad to the
        # next power of two below it and to multiples of it above, keeping
        # the compiled-program count bounded with ragged validation shards
        self.eval_pad_quantum = eval_pad_quantum
        self.cfg = backend.cfg
        self.opt = backend.opt
        # LRU over padded eval/signature buffers: a long-running simulator
        # sweeps many shards; the cap bounds pinned device memory
        self._eval_data_cache: "OrderedDict" = OrderedDict()
        self.eval_cache_entries = max(int(eval_cache_entries), 1)
        # a 1x1 (or absent) mesh degrades to the exact single-device
        # programs — same jit cache, same numerics
        self.clients_axis = clients_axis
        self.data_axis = data_axis
        self.mesh = None
        self._n_data = 1
        n_clients_axis = 1
        if mesh is not None:
            if clients_axis not in mesh.shape:
                raise ValueError(
                    f"mesh axes {tuple(mesh.axis_names)} carry no "
                    f"{clients_axis!r} axis")
            n_clients_axis = int(dict(mesh.shape)[clients_axis])
            n_data = int(dict(mesh.shape).get(data_axis, 1))
            if n_clients_axis > 1 or n_data > 1:
                self.mesh = mesh
                self._n_data = n_data
        self._n_shards = n_clients_axis if self.mesh is not None else 1
        if self.mesh is None:
            self._train_jit = jax.jit(self._train_impl)
            self._train_uniform_jit = jax.jit(self._train_uniform_impl)
            self._eval_jit = jax.jit(self._eval_impl)
            self._eval_shared_jit = jax.jit(self._eval_shared_impl)
            self._eval_many_jit = jax.jit(self._eval_many_impl)
            self._sig_jit = jax.jit(self._sig_impl)
        else:
            from jax.sharding import PartitionSpec
            c, r = PartitionSpec(clients_axis), PartitionSpec()

            def spmd(fn, in_specs, out_specs, check_vma):
                """Cohort SPMD: each device runs ``fn`` on its local client
                group (and, on a 2-D mesh, its local sample slice).  On the
                1-D mesh there are no collectives inside — aggregation
                happens in ``repro.core.aggregate``'s psum programs; the
                2-D programs psum their sum-form loss/metric terms over the
                data axis themselves."""
                return jax.jit(jax.shard_map(fn, mesh=self.mesh,
                                             in_specs=in_specs,
                                             out_specs=out_specs,
                                             check_vma=check_vma))

            # pallas_call has no shard_map replication rule, so the
            # eval/signature programs (the ones that run kernels when the
            # suite's policy is not "reference") must opt out of
            # varying-manual-axes checking.  The train programs opt out
            # too: check_vma types every scan carry, and the optimizer's
            # step counter and the LM loss's chunk accumulators start from
            # constants that do not vary over `clients`.  Every 1-D
            # program's outputs are sharded over `clients`, so the check
            # has no replication claim to verify.
            ck = self.programs.kernel_policy == "reference"

            if self._n_data <= 1:
                self._train_jit = spmd(self._train_impl, (c, c, c, c),
                                       (c, c), check_vma=False)
                self._train_uniform_jit = spmd(self._train_uniform_impl,
                                               (c, c, c), (c, c),
                                               check_vma=False)
                self._eval_jit = spmd(self._eval_impl, (c, c, c, c), c,
                                      check_vma=ck)
                # shared model replicated, K val shards sharded over clients
                self._eval_shared_jit = spmd(self._eval_shared_impl,
                                             (r, c, c, c), c, check_vma=ck)
                # M candidate models sharded, the one val shard replicated
                self._eval_many_jit = spmd(self._eval_many_impl,
                                           (c, r, r, r), c, check_vma=ck)
                self._sig_jit = spmd(self._sig_impl, (c, c, c), c,
                                     check_vma=ck)
            else:
                # 2-D (clients, data): batch arrays split their sample dim
                # over `data` (dim 2 for train (K, T, B, ...), dim 1 for
                # eval (K, N, ...)); params replicate within a client group
                # and the programs psum their sum-form terms over `data`.
                # check_vma is off: the rep-tracking rules do not cover
                # remat/scan composition, and the psum-restored
                # replication of params is pinned by the equivalence tests.
                d = data_axis
                cb = PartitionSpec(clients_axis, None, d)
                ce = PartitionSpec(clients_axis, d)
                dv = PartitionSpec(d)
                self._train_jit = spmd(self._train2d_impl,
                                       (c, cb, cb, dv, c), (c, c),
                                       check_vma=False)
                self._train_uniform_jit = spmd(self._train2d_uniform_impl,
                                               (c, cb, cb, dv), (c, c),
                                               check_vma=False)
                self._eval_jit = spmd(self._eval2d_impl, (c, ce, ce, ce), c,
                                      check_vma=False)
                self._eval_shared_jit = spmd(self._eval2d_shared_impl,
                                             (r, ce, ce, ce), c,
                                             check_vma=False)
                # M models over clients, the ONE shard's samples over data
                self._eval_many_jit = spmd(self._eval2d_many_impl,
                                           (c, dv, dv, dv), c,
                                           check_vma=False)
                self._sig_jit = spmd(self._sig2d_impl, (c, ce, ce), c,
                                     check_vma=False)
        # host-side window assembly: double-buffered background pipeline
        # (prefetch_window/take) or inline when overlap is off
        from repro.data.pipeline import WindowAssembler
        shardings = None
        if self.mesh is not None:
            from repro.sharding.rules import (cohort_batch_sharding,
                                              data_shard_sharding)
            d_ax = data_axis if self._n_data > 1 else None
            shardings = {
                "batch": cohort_batch_sharding(self.mesh, clients_axis,
                                               d_ax, 2 if d_ax else None),
                "mask": cohort_batch_sharding(self.mesh, clients_axis),
                "bm": (data_shard_sharding(self.mesh, data_axis)
                       if d_ax else None),
            }
        self.assembler = WindowAssembler(self.programs, n_data=self._n_data,
                                         shardings=shardings, overlap=overlap)

    @staticmethod
    def supports(backend) -> bool:
        return _programs_for(backend) is not None

    def register_shards(self, train_shards: Sequence,
                        epochs: Optional[int] = None) -> None:
        """Pre-size the training step-axis pad target from the client
        shards and the epochs the caller will actually train with, so the
        very first flush already compiles the steady-state program.  The
        target must match the real step count: it is monotone, so an
        over-estimate (e.g. the backend's default epochs when the
        coordinator trains fewer) would permanently pad — and compute —
        every cohort scan to the inflated length.  (Eval pad targets are
        per-call: a global target would let one large shard — e.g. the
        final global-test sweep — permanently inflate every small-val-set
        dispatch.)"""
        epochs = epochs or self.programs.default_epochs
        self.assembler.register_shards(train_shards, epochs)

    @property
    def _pad_T(self) -> int:
        """Monotone step-axis pad target (owned by the window assembler)."""
        return self.assembler.pad_T

    def _round_chunk(self, n: int) -> int:
        """Pad target for a sample axis: next power of two below the
        quantum (tiny val shards don't pay quantum-multiple waste), quantum
        multiples above it (bounded compile count either way)."""
        c = self.eval_pad_quantum
        if n >= c:
            return round_up_multiple(n, c)
        return next_pow2(n)

    # -- jitted programs ----------------------------------------------------

    def _train_impl(self, stacked_params, xb, yb, mask):
        """xb (K, T, ...); yb (K, T, ...); mask (K, T) — one vmapped scan:
        the whole cohort advances one SGD step per scan tick."""

        def one_client(params, xs, ys, ms):
            opt_state = self.opt.init(params)

            def step(carry, batch):
                params, opt_state = carry
                x, y, m = batch
                loss, grads = jax.value_and_grad(self.programs.loss)(
                    params, x, y)
                updates, new_opt = self.opt.update(grads, opt_state, params)
                new_params = apply_updates(params, updates)
                params = _tree_select(m, new_params, params)
                opt_state = _tree_select(m, new_opt, opt_state)
                return (params, opt_state), jnp.where(m, loss, 0.0)

            (params, _), losses = jax.lax.scan(
                step, (params, opt_state), (xs, ys, ms))
            return params, losses

        return jax.vmap(one_client)(stacked_params, xb, yb, mask)

    def _train_uniform_impl(self, stacked_params, xb, yb):
        """Mask-free variant for cohorts whose clients all run the SAME
        number of steps (every LM window; CNN windows with equal shard
        geometry): no padded scan ticks exist, so the per-leaf select ops
        — two pytree-wide ``where`` sweeps per step — drop out entirely.
        Cohort-axis padding still composes: padded repeat clients just
        train redundantly and their rows are discarded by the caller."""

        def one_client(params, xs, ys):
            opt_state = self.opt.init(params)

            def step(carry, batch):
                params, opt_state = carry
                x, y = batch
                loss, grads = jax.value_and_grad(self.programs.loss)(
                    params, x, y)
                updates, opt_state = self.opt.update(grads, opt_state, params)
                return (apply_updates(params, updates), opt_state), loss

            (params, _), losses = jax.lax.scan(
                step, (params, opt_state), (xs, ys))
            return params, losses

        return jax.vmap(one_client)(stacked_params, xb, yb)

    def _eval_impl(self, stacked_params, x, y, mask):
        """K models on K padded shards: x (K, N, ...), mask (K, N).

        Fusion style is the program suite's call (``vmap_eval``):
        ``lax.map`` runs the K per-client forwards inside ONE compiled
        program (one dispatch, one sync) keeping each iteration's preferred
        lowering — right for convs, whose vmap form lowers to XLA:CPU's
        slow grouped path; ``jax.vmap`` batches the arithmetic — right for
        transformers, whose vmap form is batched GEMMs."""
        if self.programs.vmap_eval:
            return jax.vmap(self.programs.masked_eval)(
                stacked_params, x, y, mask)
        return jax.lax.map(
            lambda args: self.programs.masked_eval(*args),
            (stacked_params, x, y, mask))

    def _eval_shared_impl(self, params, x, y, mask):
        return self.programs.eval_shared(params, x, y, mask)

    def _eval_many_impl(self, stacked_params, x, y, mask):
        """M models on ONE padded shard (batched tip validation): fused
        per the suite's ``vmap_eval`` style, same as ``_eval_impl``."""
        if self.programs.vmap_eval:
            return jax.vmap(
                lambda p: self.programs.masked_eval(p, x, y, mask))(
                stacked_params)
        return jax.lax.map(
            lambda p: self.programs.masked_eval(p, x, y, mask),
            stacked_params)

    def _sig_impl(self, stacked_params, x, mask):
        """Masked Eq. 3-4 signatures: per-sample zero fractions from the
        programs suite, then a masked mean so padding samples never enter
        the signature."""

        def one(params, xs, ms):
            zf = self.programs.sample_signature(params, xs)
            w = ms[:, None]
            return jnp.sum(zf * w, axis=0) / jnp.maximum(jnp.sum(w), 1.0)

        if self.programs.vmap_eval:
            return jax.vmap(one)(stacked_params, x, mask)
        return jax.lax.map(lambda args: one(*args), (stacked_params, x, mask))

    # -- 2-D (clients, data) programs: sample dims sharded over `data`,
    # sum-form terms psum'd back so every device in a client group sees the
    # full-batch gradient / metric — the models stay in lockstep ------------

    def _train2d_impl(self, stacked_params, xb, yb, bm, mask):
        """xb (K, T, B_local, ...); bm (B_local,) batch-row weights; mask
        (K, T) step mask.  Per step: grads of the sum-form loss on the
        local sample slice, one psum over `data` per (grads, loss) — the
        full-batch SGD step, computed D ways."""
        ax = self.data_axis
        denom = jax.lax.psum(self.programs.loss_denom(bm, yb[0, 0]), ax)

        def one_client(params, xs, ys, ms):
            opt_state = self.opt.init(params)

            def step(carry, batch):
                params, opt_state = carry
                x, y, m = batch
                loss, grads = jax.value_and_grad(
                    lambda p: self.programs.sum_loss(p, x, y, bm, denom))(
                    params)
                loss = jax.lax.psum(loss, ax)
                grads = jax.lax.psum(grads, ax)
                updates, new_opt = self.opt.update(grads, opt_state, params)
                new_params = apply_updates(params, updates)
                params = _tree_select(m, new_params, params)
                opt_state = _tree_select(m, new_opt, opt_state)
                return (params, opt_state), jnp.where(m, loss, 0.0)

            (params, _), losses = jax.lax.scan(
                step, (params, opt_state), (xs, ys, ms))
            return params, losses

        return jax.vmap(one_client)(stacked_params, xb, yb, mask)

    def _train2d_uniform_impl(self, stacked_params, xb, yb, bm):
        """Mask-free data-sharded variant (see ``_train_uniform_impl``)."""
        ax = self.data_axis
        denom = jax.lax.psum(self.programs.loss_denom(bm, yb[0, 0]), ax)

        def one_client(params, xs, ys):
            opt_state = self.opt.init(params)

            def step(carry, batch):
                params, opt_state = carry
                x, y = batch
                loss, grads = jax.value_and_grad(
                    lambda p: self.programs.sum_loss(p, x, y, bm, denom))(
                    params)
                loss = jax.lax.psum(loss, ax)
                grads = jax.lax.psum(grads, ax)
                updates, opt_state = self.opt.update(grads, opt_state, params)
                return (apply_updates(params, updates), opt_state), loss

            (params, _), losses = jax.lax.scan(
                step, (params, opt_state), (xs, ys))
            return params, losses

        return jax.vmap(one_client)(stacked_params, xb, yb)

    def _eval2d_terms(self, fn, args):
        """Fused per-client terms + one psum pair over `data`."""
        if self.programs.vmap_eval:
            num, den = jax.vmap(fn)(*args)
        else:
            num, den = jax.lax.map(lambda a: fn(*a), args)
        num = jax.lax.psum(num, self.data_axis)
        den = jax.lax.psum(den, self.data_axis)
        return num / jnp.maximum(den, 1.0)

    def _eval2d_impl(self, stacked_params, x, y, mask):
        """K models on K shards, samples sharded over `data`: local terms,
        psum, divide — the masked mean over each client's FULL shard."""
        return self._eval2d_terms(self.programs.eval_terms,
                                  (stacked_params, x, y, mask))

    def _eval2d_shared_impl(self, params, x, y, mask):
        num, den = self.programs.eval_shared_terms(params, x, y, mask)
        num = jax.lax.psum(num, self.data_axis)
        den = jax.lax.psum(den, self.data_axis)
        return num / jnp.maximum(den, 1.0)

    def _eval2d_many_impl(self, stacked_params, x, y, mask):
        """M models over `clients`, the ONE shard's samples over `data`."""

        def one(p):
            return self.programs.eval_terms(p, x, y, mask)

        if self.programs.vmap_eval:
            num, den = jax.vmap(one)(stacked_params)
        else:
            num, den = jax.lax.map(one, stacked_params)
        num = jax.lax.psum(num, self.data_axis)
        den = jax.lax.psum(den, self.data_axis)
        return num / jnp.maximum(den, 1.0)

    def _sig2d_impl(self, stacked_params, x, mask):
        """Masked signatures with samples sharded over `data`."""
        ax = self.data_axis

        def one(params, xs, ms):
            zf = self.programs.sample_signature(params, xs)
            w = ms[:, None]
            return jnp.sum(zf * w, axis=0), jnp.sum(w)

        if self.programs.vmap_eval:
            num, den = jax.vmap(one)(stacked_params, x, mask)
        else:
            num, den = jax.lax.map(lambda a: one(*a),
                                   (stacked_params, x, mask))
        num = jax.lax.psum(num, ax)
        den = jax.lax.psum(den, ax)
        return num / jnp.maximum(den[:, None], 1.0)

    # -- host-side batch assembly -------------------------------------------
    # (window sampling/stacking/padding/device_put lives in
    # repro.data.pipeline.WindowAssembler so it can run double-buffered on
    # a background thread; the engine owns only the pad-target policy)

    def _cohort_target(self, k: int) -> int:
        """Cohort-axis pad target: next power of two (capped at
        ``capacity``) so short cohorts waste at most 2x compute while the
        jit cache stays bounded at log2(capacity) programs per shape
        family; under a mesh it additionally rounds up to a multiple of the
        clients-axis size, so the shard_map groups divide evenly for any
        ragged cohort."""
        target = next_pow2(k)
        if self.capacity is not None:
            target = min(max(target, 1), max(self.capacity, k))
        if self._n_shards > 1:
            target = round_up_multiple(target, self._n_shards)
        return max(target, k)

    def _pad_params(self, stacked, k: int, target: int):
        """Pad a stacked K-client pytree's client axis with repeats of the
        last client (fully masked / discarded downstream)."""
        if k >= target:
            return stacked
        reps = target - k
        return jax.tree_util.tree_map(
            lambda leaf: jnp.concatenate(
                [leaf, jnp.repeat(leaf[-1:], reps, axis=0)]), stacked)

    def prefetch_window(self, datasets: Sequence, seeds: Sequence[int],
                        epochs: Optional[int] = None) -> None:
        """Start assembling the given window's training batch on the
        assembler's background thread (sampling, stacking, padding,
        ``device_put``) so it overlaps whatever the device is running —
        the previous window, the Eq. 6 aggregation, tip validation.  The
        matching ``train_cohort_stacked`` call collects it; a mismatched or
        absent prefetch silently assembles inline (identical numerics — the
        per-seed np RNG streams don't depend on where sampling runs)."""
        epochs = epochs or self.programs.default_epochs
        self.assembler.prefetch(datasets, seeds, epochs,
                                self._cohort_target(len(datasets)))

    def _pad_cohort(self, stacked, xb, yb, mask):
        """Pad the cohort axis (see ``_cohort_target``) with fully-masked
        repeats — the eval/signature-path twin of the assembler's
        client-axis padding."""
        k = int(mask.shape[0])
        target = self._cohort_target(k)
        if k >= target:
            return stacked, xb, yb, mask, k
        reps = target - k
        stacked = jax.tree_util.tree_map(
            lambda leaf: jnp.concatenate(
                [leaf, jnp.repeat(leaf[-1:], reps, axis=0)]), stacked)
        xb = jnp.concatenate([xb, jnp.repeat(xb[-1:], reps, axis=0)])
        yb = jnp.concatenate([yb, jnp.repeat(yb[-1:], reps, axis=0)])
        mask = jnp.concatenate(
            [mask, jnp.zeros((reps,) + mask.shape[1:], mask.dtype)])
        return stacked, xb, yb, mask, k

    def _eval_arrays(self, datasets: Sequence, limit: int,
                     kind: str = "eval"):
        """Padded (x, y, mask) for a tuple of shards.  Per-DATASET LRU
        caching: each shard is padded to its own rounded size once; per call
        we stack the cached singles (topping up to the call-wide max if the
        batch mixes sizes), so arbitrary cohort compositions — the monitor's
        full val-set sweep, a window's subset — reuse the same buffers while
        the cache stays bounded at ``eval_cache_entries``."""
        singles, ns = [], []
        for ds in datasets:
            key = (id(ds), limit, kind)
            hit = self._eval_data_cache.get(key)
            if hit is None:
                x1, y1, n = self.programs.eval_single(ds, limit, kind)
                own = self._round_chunk(n)
                x1 = pad_leading(jnp.asarray(x1), own)
                y1 = pad_leading(jnp.asarray(y1), own)
                m1 = (jnp.arange(own) < n).astype(jnp.float32)
                # hold ds so the id() key stays unique for our lifetime
                hit = (ds, x1, y1, m1, n)
                self._eval_data_cache[key] = hit
            else:
                self._eval_data_cache.move_to_end(key)
            singles.append(hit)
            ns.append(hit[4])
        # evict AFTER the batch, clamped to the call's own width: evicting
        # inside the loop would let one wide sweep (e.g. the monitor's
        # n_clients val sets with n_clients > the cap) evict its own
        # entries mid-call and turn the cache into pure overhead
        cap = max(self.eval_cache_entries, len(datasets))
        while len(self._eval_data_cache) > cap:
            self._eval_data_cache.popitem(last=False)
        target = max(self._round_chunk(n) for n in ns)
        if self._n_data > 1:
            # sample axes shard over the data mesh axis: pad to a multiple
            # (masked rows, so the extra padding never enters a mean)
            target = round_up_multiple(target, self._n_data)
        x = jnp.stack([pad_leading(s[1], target) for s in singles])
        y = jnp.stack([pad_leading(s[2], target) for s in singles])
        mask = jnp.stack([pad_leading(s[3], target) for s in singles])
        return x, y, mask

    # -- public API ----------------------------------------------------------

    def train_cohort_stacked(self, stacked_params, datasets, seeds,
                             epochs: Optional[int] = None):
        """Train K clients as one program; returns (stacked params, losses).

        ``losses[k]`` matches the sequential path's per-backend contract
        (see ``CohortPrograms.summarize_losses``).
        """
        with obs.span("dagafl.train", rounds=len(datasets)):
            return self._train_window(stacked_params, datasets, seeds,
                                      epochs)

    def _train_window(self, stacked_params, datasets, seeds, epochs):
        epochs = epochs or self.programs.default_epochs
        k = len(datasets)
        target = self._cohort_target(k)
        # collect the prefetched window (or assemble inline): batches are
        # already stacked, padded (steps / cohort / data-multiple batch
        # rows) and — under a mesh — device_put with the final layout, so
        # every host->mesh transfer happens once instead of bouncing
        # through device 0
        win = self.assembler.take(datasets, seeds, epochs, target)
        stacked_params = self._pad_params(stacked_params, k, target)
        if self.mesh is not None:
            from repro.sharding.rules import stacked_client_shardings
            stacked_params = jax.device_put(
                stacked_params, stacked_client_shardings(
                    stacked_params, self.mesh, self.clients_axis,
                    data_axis=self.data_axis if self._n_data > 1 else None))
        # mask-free fast path when no step padding exists: every client
        # (and therefore every cohort-padding repeat) runs exactly _pad_T
        # steps, so the masked and uniform programs are the same math
        if self._n_data > 1:
            if win.uniform:
                new_params, losses = self._train_uniform_jit(
                    stacked_params, win.xb, win.yb, win.bm)
            else:
                new_params, losses = self._train_jit(
                    stacked_params, win.xb, win.yb, win.bm, win.mask)
        elif win.uniform:
            new_params, losses = self._train_uniform_jit(stacked_params,
                                                         win.xb, win.yb)
        else:
            new_params, losses = self._train_jit(stacked_params, win.xb,
                                                 win.yb, win.mask)
        losses = obs.fetch(losses)
        final = self.programs.summarize_losses(losses, win.steps, epochs)
        if k < losses.shape[0]:
            new_params = jax.tree_util.tree_map(lambda l: l[:k], new_params)
        return new_params, final

    def train_cohort(self, params_list, datasets, seeds,
                     epochs: Optional[int] = None):
        stacked, losses = self.train_cohort_stacked(
            tree_stack(params_list), datasets, seeds, epochs)
        return tree_unstack(stacked), losses

    def evaluate_cohort_stacked(self, stacked_params, datasets,
                                limit: int = 512) -> List[float]:
        """K models, each on its own (ragged) shard."""
        with obs.span("dagafl.eval", rounds=len(datasets)):
            x, y, mask = self._eval_arrays(datasets, limit)
            stacked_params, x, y, mask, k = self._pad_cohort(
                stacked_params, x, y, mask)
            accs = self._eval_jit(stacked_params, x, y, mask)
            return [float(a) for a in obs.fetch(accs)[:k]]

    def evaluate_cohort(self, params_list, datasets,
                        limit: int = 512) -> List[float]:
        return self.evaluate_cohort_stacked(tree_stack(params_list), datasets,
                                            limit)

    def evaluate_shared(self, params, datasets, limit: int = 512
                        ) -> List[float]:
        """One model on K shards in one dispatch (publisher's monitor)."""
        x, y, mask = self._eval_arrays(datasets, limit)
        k = int(x.shape[0])
        if self._n_shards > 1 and k % self._n_shards:
            t = round_up_multiple(k, self._n_shards)
            x, y, mask = pad_leading(x, t), pad_leading(y, t), \
                pad_leading(mask, t)
        accs = self._eval_shared_jit(params, x, y, mask)
        return [float(a) for a in obs.fetch(accs)[:k]]

    def evaluate_many(self, params_list, ds, limit: int = 512) -> List[float]:
        """M candidate models on one validation shard (tip selection).

        The model axis is padded to the next power of two (with repeats) so
        repeated tip sweeps reuse a handful of compiled programs.
        """
        m = len(params_list)
        if m == 0:
            return []
        with obs.span("dagafl.eval_many", candidates=m):
            if m <= self.programs.eval_many_min_batch:
                # tiny sweeps: the backend's own jitted program wins — no
                # stacking, no pow2 model-axis padding, and it shares the
                # sequential jit cache (threshold is suite-specific)
                return [self.programs.evaluate_one(p, ds, limit)
                        for p in params_list]
            m_pad = next_pow2(m)
            if self._n_shards > 1:
                m_pad = round_up_multiple(m_pad, self._n_shards)
            padded = list(params_list) + [params_list[-1]] * (m_pad - m)
            # sample axis padded to the shared eval target: compilations stay
            # bounded at log2(M) programs even with ragged validation shards
            x, y, mask = self._eval_arrays([ds], limit)
            accs = self._eval_many_jit(tree_stack(padded), x[0], y[0], mask[0])
            return [float(a) for a in obs.fetch(accs)[:m]]

    def signature_cohort_stacked(self, stacked_params, datasets,
                                 limit: int = 128) -> np.ndarray:
        """(K, dims) Eq. 3 signatures, one masked batched dispatch."""
        with obs.span("dagafl.signature", rounds=len(datasets)):
            x, _, mask = self._eval_arrays(datasets, limit, kind="sig")
            # pass mask in the label slot: _pad_cohort pads a (K, N) array
            # there, not a second full copy of the (K, N, ...) sample batch
            stacked_params, x, _, mask, k = self._pad_cohort(
                stacked_params, x, mask, mask)
            sigs = self._sig_jit(stacked_params, x, mask)
            return obs.fetch(sigs)[:k]

    def signature_cohort(self, params_list, datasets,
                         limit: int = 128) -> np.ndarray:
        return self.signature_cohort_stacked(tree_stack(params_list),
                                             datasets, limit)

    def perturb_cohort_stacked(self, agg_stacked, new_stacked, plan: dict):
        """Scenario fault injection for a whole window (see
        repro/fl/scenarios.py): ``new' = agg + gamma*(new-agg) + sigma*N``
        as one vmapped jitted program; rows the plan marks unaffected keep
        their exact bits."""
        return perturb_cohort_stacked_trees(agg_stacked, new_stacked, plan)


# ---------------------------------------------------------------------------
# engine construction helpers (shared by the coordinator and all baselines)
# ---------------------------------------------------------------------------


def parse_mesh_spec(spec):
    """A mesh spec's (clients, data) request.  Accepts ``"auto"``,
    ``"CxD"`` strings (``"4x2"``, ``"8x1"``, ``"8"``), and 2-tuples whose
    clients slot may be ``"auto"`` (``("auto", 2)``, ``(4, 2)``)."""
    if isinstance(spec, str):
        parts = spec.lower().split("x")
        if len(parts) > 2 or not all(
                p == "auto" or p.isdigit() for p in parts):
            raise ValueError(
                f"mesh must be 'auto', 'CxD' (e.g. '4x2'), a (clients, "
                f"data) tuple, None or a Mesh: {spec!r}")
    elif isinstance(spec, (tuple, list)):
        parts = list(spec)
        if len(parts) != 2:
            raise ValueError(f"mesh tuple must be (clients, data): {spec!r}")
    else:
        raise TypeError(f"unsupported mesh spec: {spec!r}")
    clients = parts[0]
    data = int(parts[1]) if len(parts) > 1 else 1
    if clients != "auto":
        clients = int(clients)
    return clients, data


def resolve_cohort_mesh(mesh, cohort_size: int, clients_axis: str = "clients",
                        data_axis: str = "data"):
    """``"auto"`` -> a clients mesh clamped to this host's devices (never
    raises; 1 device degrades to the single-device engine); ``"CxD"`` (e.g.
    ``"4x2"``) or a ``(clients, data)`` tuple (clients may be ``"auto"`` ->
    ``cohort_size``) -> the 2-D (clients, data) mesh, clamped the same way;
    ``None`` -> single-device; a Mesh -> itself."""
    if mesh is None or hasattr(mesh, "axis_names"):
        return mesh
    clients, data = parse_mesh_spec(mesh)
    if clients == "auto":
        clients = cohort_size
    from repro.launch.mesh import make_cohort_mesh
    return make_cohort_mesh(clients, axis=clients_axis, data=data,
                            data_axis=data_axis)


def build_cohort_engine(backend, train_shards: Sequence, *,
                        cohort_size: int, mesh="auto",
                        clients_axis: str = "clients",
                        data_axis: str = "data",
                        epochs: Optional[int] = None,
                        overlap: bool = True,
                        kernel_policy: Optional[str] = None
                        ) -> Optional[CohortBackend]:
    """One-stop engine construction for any registered backend family:
    resolves the mesh spec (1-D or 2-D, see :func:`resolve_cohort_mesh`),
    builds the engine, and pre-registers the training shards so the first
    flush compiles the steady-state program.  Returns ``None`` when cohort
    execution is off (``cohort_size <= 1``) or the backend has no
    registered program suite — callers then run the sequential path."""
    if cohort_size <= 1 or not CohortBackend.supports(backend):
        return None
    engine = CohortBackend(
        backend, capacity=cohort_size,
        mesh=resolve_cohort_mesh(mesh, cohort_size, clients_axis, data_axis),
        clients_axis=clients_axis, data_axis=data_axis, overlap=overlap,
        kernel_policy=kernel_policy)
    engine.register_shards(train_shards, epochs=epochs)
    return engine

"""Host-side data pipelines: token-batch sampling and cohort-window assembly.

:class:`TokenPipeline` is the LM streaming sampler (synthetic Markov
streams, optional disjoint per-client sharding).

:class:`WindowAssembler` is the cohort engine's host-side batch-assembly
stage, extracted from ``repro.fl.cohort`` so it can run as a prefetching
double-buffered pipeline: while the device computes one cohort window, the
NEXT window's batches are sampled, stacked, padded and ``device_put`` on a
background thread.  RNG parity is by construction — every client's batch
stream comes from ``np.random.default_rng(seed)`` seeded per client, so the
sampled tokens/images are identical whether assembly runs inline, early, or
on another thread; the only ordered RNG (the coordinator's seed/jitter
stream) never enters the assembler.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from typing import Iterator, List, Optional, Sequence

import numpy as np

from repro import obs
from repro.data.synthetic import make_lm_dataset


class TokenPipeline:
    """Infinite (batch, seq+1) sampler over a token stream with optional
    per-client sharding (each client sees a disjoint slice).

    Shard boundaries follow ``np.array_split`` semantics: the remainder
    tokens of ``len(stream) % n_shards`` spread over the first shards
    instead of silently falling off the tail, so every token belongs to
    exactly one client."""

    def __init__(self, vocab: int, batch: int, seq: int,
                 n_tokens: int = 500_000, seed: int = 0,
                 n_shards: int = 1, shard: int = 0):
        if not 0 <= shard < n_shards:
            raise ValueError(f"shard {shard} out of range for "
                             f"{n_shards} shards")
        stream = make_lm_dataset(vocab=vocab, n_tokens=n_tokens, seed=seed)
        self.stream = np.array_split(stream, n_shards)[shard]
        # a (seq+1)-token window needs at least one valid start position
        if len(self.stream) < seq + 1:
            raise ValueError(
                f"shard {shard} holds {len(self.stream)} tokens but "
                f"seq={seq} windows need at least {seq + 1}; lower "
                f"n_shards (={n_shards}) or raise n_tokens (={n_tokens})")
        self.batch = batch
        self.seq = seq
        self.rng = np.random.default_rng(seed * 997 + shard)

    def __iter__(self) -> Iterator[np.ndarray]:
        # starts range over EVERY valid window, so the shard's final token
        # is reachable (high is exclusive: max start = len - seq - 1)
        while True:
            starts = self.rng.integers(
                0, len(self.stream) - self.seq, self.batch)
            yield np.stack([self.stream[s:s + self.seq + 1] for s in starts])

    def batch_dict(self, arr: np.ndarray):
        return {"tokens": arr[:, :-1].astype(np.int32),
                "labels": arr[:, 1:].astype(np.int32)}


# ---------------------------------------------------------------------------
# cohort-window assembly (the cohort engine's host-side stage)
# ---------------------------------------------------------------------------


_SHARED_EXECUTOR: Optional[ThreadPoolExecutor] = None
_SHARED_EXECUTOR_LOCK = threading.Lock()


def _shared_executor() -> ThreadPoolExecutor:
    """One process-wide assembly worker, created on first use: a sweep that
    builds hundreds of engines (benchmarks, experiments) must not
    accumulate one idle thread per engine, and the one-slot prefetch
    protocol never has more than one window in flight anyway."""
    global _SHARED_EXECUTOR
    with _SHARED_EXECUTOR_LOCK:
        if _SHARED_EXECUTOR is None:
            _SHARED_EXECUTOR = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="window-assembler")
        return _SHARED_EXECUTOR


@dataclass
class AssembledWindow:
    """One cohort window's device-ready training batch.

    ``xb``/``yb`` are (K_pad, T, B_pad, ...) stacked client batches (client
    axis padded to the engine's cohort target, step axis to the monotone
    ``T`` target, batch axis to a data-mesh multiple); ``mask`` (K_pad, T)
    masks padded steps; ``bm`` (B_pad,) masks padded batch rows (``None``
    off the data axis); ``steps`` are the real per-client step counts and
    ``uniform`` says whether every client runs exactly ``T`` steps (the
    engine's mask-free fast path)."""

    xb: object
    yb: object
    mask: object
    bm: object
    steps: List[int]
    uniform: bool


class WindowAssembler:
    """Double-buffered host-side batch assembly for the cohort engine.

    ``assemble`` is the synchronous reference path: sample every client's
    batches (``programs.client_batches`` — the exact sequential np RNG
    stream per seed), pad the step axis to the monotone ``T`` target, the
    client axis to the engine's cohort target (repeats of the last client,
    fully masked), the batch axis to a ``data``-mesh multiple (zero rows,
    masked by ``bm``), and ``device_put`` everything with the engine's
    shardings.

    ``prefetch``/``take`` add the overlap: ``prefetch`` schedules the same
    assembly on a ONE-SLOT background executor (double buffering: at most
    one window in flight while one computes) and ``take`` collects it —
    falling back to inline assembly whenever the prefetched request doesn't
    match, so correctness never depends on the caller prefetching the right
    thing.  ``overlap=False`` disables the executor entirely (every take
    assembles inline); both modes produce bit-identical windows, which the
    parity tests pin down.
    """

    def __init__(self, programs, *, n_data: int = 1, shardings=None,
                 overlap: bool = True):
        self.programs = programs
        self.n_data = max(int(n_data), 1)
        # dict with "batch" (xb/yb), "mask", "bm" NamedShardings (or None)
        self.shardings = shardings
        self.overlap = overlap
        self._lock = threading.Lock()
        self._pad_T = 0            # monotone step-axis pad target
        self._pending = None       # (key, Future[AssembledWindow])

    # -- pad-target registration (moved from CohortBackend) -----------------

    def register_shards(self, train_shards: Sequence, epochs: int) -> None:
        """Pre-size the monotone step-axis pad target so the very first
        window already compiles the steady-state program (see
        ``CohortBackend.register_shards`` for why the target must match the
        epochs the caller actually trains with)."""
        with self._lock:
            for ds in train_shards:
                self._pad_T = max(self._pad_T,
                                  self.programs.train_steps(ds, epochs))

    @property
    def pad_T(self) -> int:
        return self._pad_T

    # -- assembly ------------------------------------------------------------

    @staticmethod
    def _key(datasets, seeds, epochs: int, cohort_target: int):
        return (tuple(id(ds) for ds in datasets), tuple(int(s) for s in seeds),
                int(epochs), int(cohort_target))

    def assemble(self, datasets: Sequence, seeds: Sequence[int], epochs: int,
                 cohort_target: int) -> AssembledWindow:
        """Synchronous assembly (the reference path — also what the
        background thread runs)."""
        import jax
        import jax.numpy as jnp

        from repro.core.aggregate import pad_leading, round_up_multiple

        xs_all, ys_all, steps = [], [], []
        for ds, seed in zip(datasets, seeds):
            xb, yb = self.programs.client_batches(ds, seed, epochs)
            xs_all.append(xb)
            ys_all.append(yb)
            steps.append(int(xb.shape[0]))

        with self._lock:
            self._pad_T = max(self._pad_T, *steps)
            T = self._pad_T
        xb = jnp.stack([pad_leading(x, T) for x in xs_all])
        yb = jnp.stack([pad_leading(y, T) for y in ys_all])
        mask = jnp.stack([
            jnp.arange(T) < s for s in jnp.asarray(steps)]).astype(jnp.float32)
        uniform = all(s == T for s in steps)

        # client-axis padding: repeats of the last client, fully masked
        k = len(steps)
        if k < cohort_target:
            reps = cohort_target - k
            xb = jnp.concatenate([xb, jnp.repeat(xb[-1:], reps, axis=0)])
            yb = jnp.concatenate([yb, jnp.repeat(yb[-1:], reps, axis=0)])
            mask = jnp.concatenate(
                [mask, jnp.zeros((reps,) + mask.shape[1:], mask.dtype)])

        # batch-axis padding to a data-mesh multiple: zero rows carrying
        # zero weight in ``bm``, so they never enter the psum'd gradients
        bm = None
        if self.n_data > 1:
            b = int(xb.shape[2])
            b_pad = round_up_multiple(b, self.n_data)
            if b_pad != b:
                widths = [(0, 0), (0, 0), (0, b_pad - b)]
                xb = jnp.pad(xb, widths + [(0, 0)] * (xb.ndim - 3))
                yb = jnp.pad(yb, widths + [(0, 0)] * (yb.ndim - 3))
            bm = (jnp.arange(b_pad) < b).astype(jnp.float32)

        if self.shardings is not None:
            xb = jax.device_put(xb, self.shardings["batch"])
            yb = jax.device_put(yb, self.shardings["batch"])
            if not uniform:          # the uniform program never reads mask
                mask = jax.device_put(mask, self.shardings["mask"])
            if bm is not None:
                bm = jax.device_put(bm, self.shardings["bm"])
        return AssembledWindow(xb, yb, mask, bm, steps, uniform)

    def prefetch(self, datasets: Sequence, seeds: Sequence[int], epochs: int,
                 cohort_target: int) -> None:
        """Schedule background assembly of the given window (one slot: a
        second prefetch before the first is taken replaces it).  No-op when
        overlap is off."""
        if not self.overlap:
            return
        key = self._key(datasets, seeds, epochs, cohort_target)
        pending = self._pending
        if pending is not None and pending[0] == key:
            return                   # already in flight
        self._drain_pending()
        fut: Future = _shared_executor().submit(
            self.assemble, tuple(datasets), tuple(seeds), epochs,
            cohort_target)
        self._pending = (key, fut)

    def take(self, datasets: Sequence, seeds: Sequence[int], epochs: int,
             cohort_target: int) -> AssembledWindow:
        """The prefetched window when it matches this request, else inline
        assembly (identical output either way)."""
        pending, self._pending = self._pending, None
        with obs.span("dagafl.assembler_wait"):
            if pending is not None:
                key, fut = pending
                if key == self._key(datasets, seeds, epochs, cohort_target):
                    return fut.result()
                fut.result()         # stale prefetch: settle, then discard
            return self.assemble(datasets, seeds, epochs, cohort_target)

    def _drain_pending(self) -> None:
        pending, self._pending = self._pending, None
        if pending is not None:
            pending[1].result()      # never leave assembly racing the next

    def close(self) -> None:
        """Settle any in-flight assembly.  The worker thread itself is the
        process-wide shared executor's — nothing per-assembler to tear
        down."""
        self._drain_pending()

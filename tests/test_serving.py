"""Live-traffic consensus serving (repro/fl/serving.py).

The load-bearing properties:

* a query NEVER observes a half-written replica — whatever the interleaving
  of publish cadence and round arrivals, the replica's params always equal
  a fresh Eq. 6 aggregate over its OWN pinned refs (double-buffered swap);
* same seed + config => identical replica-version sequence, frontier
  tx-id sets and staleness counters (the serve gate pins these);
* serving is read-only: the training trajectory is bit-identical with the
  publisher + query stream on or off;
* refs pinned by a live replica survive bounded-ledger pruning and are
  evicted on the first swap that unpins them;
* concurrent recurring streams (publisher cadence + query stream +
  checkpoint cadence) never keep a drained simulation alive.

Most tests run against a synthetic ledger world (tiny numpy pytrees, no
training) so the event-loop logic is exercised densely and fast; the
read-only bit-identity test runs the real CNN coordinator.
"""
import numpy as np
import pytest

from tests._hypothesis_fallback import install as _install_hypothesis

_install_hypothesis()

from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core.dag import (BoundedDAGLedger, DAGLedger, ModelStore,
                            TxMetadata)  # noqa: E402
from repro.core.simulator import EventLoop  # noqa: E402
from repro.fl.serving import (ConsensusPublisher, QueryStream,
                              ServingConfig, consensus_over_refs,
                              make_query_driver, replica_parity,
                              trees_bitwise_equal)  # noqa: E402


def _meta(cid, epoch=0):
    return TxMetadata(client_id=cid, signature=(0.0,) * 16,
                      model_accuracy=0.5, current_epoch=epoch,
                      validation_node_id=cid)


def _model(v: float):
    return {"w": np.full(3, float(v), np.float32),
            "b": np.array([float(v) * 2.0], np.float32)}


class _World:
    """Synthetic training world: appends distinct-valued models on a
    schedule, no JAX, no backend."""

    def __init__(self, bounded=False, checkpoint_interval=0):
        self.loop = EventLoop()
        self.store = ModelStore()
        self.evicted = []
        if bounded:
            self.ledger = BoundedDAGLedger(
                checkpoint_interval=checkpoint_interval,
                evict_fn=self._on_prune)
        else:
            self.ledger = DAGLedger()
        self.publisher = None
        ref = self.store.put("genesis", _model(0.0))
        self.ledger.add_genesis(_meta(-1), 0.0, ref)
        self._next_val = 1.0

    def _on_prune(self, tx):
        # the coordinator's _evict_model chokepoint, miniaturized
        if self.publisher is not None and \
                self.publisher.guard_evict(tx.model_ref):
            return
        self.store.evict(tx.model_ref)
        self.evicted.append(tx.model_ref)

    def append(self, client: int, parents=None) -> str:
        """One 'round completion': publish a fresh distinct model approving
        ``parents`` (default: every current tip)."""
        v = self._next_val
        self._next_val += 1.0
        ref = self.store.put(f"m{int(v):06d}", _model(v))
        if parents is None:
            parents = tuple(self.ledger.tips()) or (self.ledger.genesis_id,)
        tx = self.ledger.add_transaction(_meta(client), tuple(parents),
                                         self.loop.now, ref)
        return tx.tx_id

    def schedule_appends(self, times, clients=None):
        for i, t in enumerate(times):
            c = clients[i] if clients is not None else i % 3
            self.loop.schedule(t, lambda c=c: self.append(c))


class _ProbeDriver:
    """Query driver that asserts replica integrity on every serve."""

    def __init__(self, store):
        self.store = store
        self.queries = 0
        self.versions = []

    def serve(self, replica):
        # params must be the Eq. 6 aggregate over the replica's OWN refs —
        # a half-written or mixed-frontier replica fails this bitwise check
        assert trees_bitwise_equal(
            replica.params, consensus_over_refs(self.store,
                                                replica.model_refs))
        assert len(replica.frontier) == len(replica.model_refs) > 0
        self.versions.append(replica.version)
        self.queries += 1
        return {}

    def report(self):
        return {"driver": "probe"}


# -- event-loop stream plumbing ----------------------------------------------


def test_schedule_stream_draws_one_gap_at_a_time():
    loop = EventLoop()
    rng = np.random.default_rng(0)
    fired = []
    loop.schedule(10.0, lambda: None)          # real work keeping it alive
    loop.schedule_stream(lambda: rng.exponential(2.0),
                         lambda: fired.append(loop.now))
    loop.run()
    # gaps must equal the rng's sequential draws exactly
    ref = np.random.default_rng(0)
    t, expect = 0.0, []
    while True:
        t += ref.exponential(2.0)
        if t > 10.0 and expect:
            # stream events after the last real event do fire once armed,
            # but no re-arm happens once only stream ticks remain
            break
        expect.append(t)
    assert fired[:len(expect)] == pytest.approx(expect)


def test_two_streams_do_not_keep_drained_loop_alive():
    """Publisher cadence + query stream must not ping-pong forever after
    the last real event."""
    loop = EventLoop()
    a, b = [], []
    loop.schedule(5.0, lambda: None)           # the only real work
    loop.schedule_every(1.0, lambda: a.append(loop.now))
    loop.schedule_every(1.3, lambda: b.append(loop.now))
    loop.run(max_events=10_000)
    # both streams stop shortly after the real event drains
    assert loop.now < 10.0
    assert all(t <= loop.now for t in a + b)
    assert len(a) + len(b) < 20


def test_schedule_every_still_rejects_nonpositive_interval():
    loop = EventLoop()
    with pytest.raises(ValueError):
        loop.schedule_every(0.0, lambda: None)


def test_head_seq_advances_once_per_append_and_survives_pruning():
    w = _World(bounded=True)
    assert w.ledger.head_seq() == 0            # genesis
    ids = [w.append(c) for c in (0, 1, 2, 0, 1, 2)]
    assert w.ledger.head_seq() == 6
    w.ledger.checkpoint(now=1.0)
    assert w.ledger.n_pruned > 0
    assert w.ledger.head_seq() == 6            # monotone across pruning
    w.append(0)
    assert w.ledger.head_seq() == 7
    assert ids[0] == "tx000000000001"


# -- publisher ---------------------------------------------------------------


def test_publish_noop_when_frontier_unchanged():
    w = _World()
    pub = ConsensusPublisher(w.ledger, w.store, w.loop, every=1.0)
    assert pub.publish() is not None           # v0: genesis frontier
    assert pub.publish() is None               # nothing appended
    assert (pub.publishes, pub.publishes_noop) == (1, 1)
    rep = pub.replica()
    assert rep.version == 0 and rep.frontier == (w.ledger.genesis_id,)
    w.append(0)
    rep2 = pub.publish()
    assert rep2 is not None and rep2.version == 1
    assert pub.replica() is rep2               # swap flipped the buffer
    assert rep.params is not None              # old replica left intact


def test_replica_is_exact_eq6_aggregate():
    w = _World()
    g = w.ledger.genesis_id
    for c in (0, 1, 2):                        # three branches off genesis
        w.append(c, parents=(g,))
    pub = ConsensusPublisher(w.ledger, w.store, w.loop, every=1.0)
    rep = pub.publish()
    assert set(rep.frontier) == set(w.ledger.tips())
    assert replica_parity(rep, w.store)
    # distinct models 1..3 at the tips: the aggregate is their plain mean
    np.testing.assert_array_equal(np.asarray(rep.params["w"]),
                                  np.full(3, 2.0, np.float32))


def test_eviction_protection_pins_replica_refs_until_swap():
    w = _World(bounded=True)
    pub = ConsensusPublisher(w.ledger, w.store, w.loop, every=1.0)
    w.publisher = pub
    g = w.ledger.genesis_id
    for c in (0, 1, 2):                        # three branches off genesis
        w.append(c, parents=(g,))
    rep1 = pub.publish()                       # pins the 3-tip frontier
    # two more generations confirm the old frontier; pruning now hits refs
    # rep1 still pins
    for c in (0, 1, 2, 0, 1, 2):
        w.append(c)
    w.ledger.checkpoint(now=2.0)
    assert w.ledger.n_pruned > 0
    pinned = set(rep1.model_refs) & set(pub._deferred)
    assert pinned, "checkpoint never tried to evict a pinned replica ref"
    for r in rep1.model_refs:
        assert r in w.store                    # protected while live
    pub.publish()                              # swap 1: rep1 in back buffer
    for r in rep1.model_refs:
        assert r in w.store                    # back slot still pins
    w.append(0)
    pub.publish()                              # swap 2: rep1 fully unpinned
    for r in pinned:
        assert r not in w.store                # released and evicted
    assert pub.evictions_released >= len(pinned)
    assert pub.evictions_deferred >= len(pinned)


def test_publisher_start_publishes_v0_immediately():
    w = _World()
    pub = ConsensusPublisher(w.ledger, w.store, w.loop, every=5.0)
    w.schedule_appends([1.0, 2.0, 9.0])
    probe = _ProbeDriver(w.store)
    qs = QueryStream(pub, probe, w.loop, w.ledger, query_rate=1.0, seed=7)
    pub.start()
    qs.start()
    assert pub.replica() is not None           # before any event ran
    w.loop.run()
    assert qs.skipped == 0
    assert probe.queries == qs.queries > 0
    assert probe.versions == sorted(probe.versions)  # versions monotone


def test_publisher_rejects_nonpositive_cadence():
    w = _World()
    with pytest.raises(ValueError):
        ConsensusPublisher(w.ledger, w.store, w.loop, every=0.0)
    with pytest.raises(ValueError):
        QueryStream(ConsensusPublisher(w.ledger, w.store, w.loop, 1.0),
                    _ProbeDriver(w.store), w.loop, w.ledger,
                    query_rate=0.0, seed=0)


# -- atomicity under randomized interleavings (satellite 2) ------------------


@settings(max_examples=15, deadline=None)
@given(st.floats(0.3, 4.0),
       st.lists(st.floats(0.1, 12.0), min_size=1, max_size=14),
       st.integers(0, 2 ** 20),
       st.booleans())
def test_replica_never_mixes_frontiers(every, arrival_times, seed, bounded):
    """Whatever the publish-cadence / round-arrival interleaving, every
    query sees a replica whose params are EXACTLY the Eq. 6 aggregate of
    its own frontier refs — never a mixture of two frontiers."""
    w = _World(bounded=bounded, checkpoint_interval=4 if bounded else 0)
    pub = ConsensusPublisher(w.ledger, w.store, w.loop, every=every)
    w.publisher = pub
    w.schedule_appends(sorted(arrival_times))
    probe = _ProbeDriver(w.store)
    qs = QueryStream(pub, probe, w.loop, w.ledger, query_rate=2.0, seed=seed)
    pub.start()
    qs.start()
    w.loop.run(max_events=50_000)
    assert qs.skipped == 0
    assert probe.versions == sorted(probe.versions)
    # staleness lags are measured at arrival and never negative
    assert all(l >= 0 for l in qs.seq_lags)
    assert all(t >= 0.0 for t in qs.time_lags)
    # version accounting closes: every served version was published
    assert set(qs.version_hist) <= set(range(pub.publishes))


# -- determinism (satellite 2) ----------------------------------------------


def _run_synthetic(seed: int, every=1.7, rate=1.5, bounded=True):
    w = _World(bounded=bounded, checkpoint_interval=0)
    rng = np.random.default_rng(seed)
    times = np.cumsum(rng.exponential(1.0, size=12))
    swaps = []
    pub = ConsensusPublisher(
        w.ledger, w.store, w.loop, every=every,
        on_swap=lambda r: swaps.append((r.version, r.frontier,
                                        r.ledger_seq, r.published_at)))
    w.publisher = pub
    if bounded:
        w.loop.schedule_every(
            2.5, lambda: w.ledger.maybe_checkpoint(now=w.loop.now))
    w.schedule_appends(times.tolist())
    probe = _ProbeDriver(w.store)
    qs = QueryStream(pub, probe, w.loop, w.ledger, query_rate=rate,
                     seed=seed + 1)
    pub.start()
    qs.start()
    w.loop.run(max_events=50_000)
    return swaps, qs.report(), pub.report()


def test_same_seed_same_replica_sequence_and_counters():
    swaps_a, qrep_a, prep_a = _run_synthetic(3)
    swaps_b, qrep_b, prep_b = _run_synthetic(3)
    assert swaps_a == swaps_b                  # versions, frontiers, seqs
    assert prep_a == prep_b
    assert qrep_a == qrep_b


def test_different_seed_different_trace():
    _, qrep_a, _ = _run_synthetic(3)
    _, qrep_b, _ = _run_synthetic(4)
    assert (qrep_a["arrivals"] != qrep_b["arrivals"]
            or qrep_a["replica_version_hist"]
            != qrep_b["replica_version_hist"])


# -- driver construction -----------------------------------------------------


def test_make_query_driver_auto_detects_backend():
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.configs.cnn import vgg_for
    from repro.fl.backend import CNNBackend, LMBackend
    from repro.fl.serving import CNNQueryDriver, LMQueryDriver

    from repro.data import make_benchmark_dataset
    ds = make_benchmark_dataset("mnist", n_samples=64, seed=0)
    cnn = CNNBackend(vgg_for("mnist"))
    scfg = ServingConfig(backend="auto")
    assert isinstance(make_query_driver(scfg, cnn, ds), CNNQueryDriver)

    lm_cfg = dataclasses.replace(reduced(get_config("internlm2-1.8b"),
                                         d_model=32), vocab_size=64)
    lm = LMBackend(lm_cfg)
    drv = make_query_driver(scfg, lm, None)
    assert isinstance(drv, LMQueryDriver)
    with pytest.raises(ValueError):
        make_query_driver(ServingConfig(backend="nope"), cnn, ds)


# -- serving is read-only: training bit-identity (real coordinator) ----------


@pytest.fixture(scope="module")
def cnn_world():
    from repro.configs.cnn import vgg_for
    from repro.data import (make_benchmark_dataset, partition_dirichlet,
                            split_811)
    from repro.fl.backend import CNNBackend
    ds = make_benchmark_dataset("mnist", n_samples=900, seed=0)
    splits = split_811(ds)
    parts = partition_dirichlet(splits["train"], 3, beta=0.5, seed=0)
    client_data = []
    for p in parts:
        s = split_811(p, seed=1)
        client_data.append({"train": s["train"], "val": s["val"],
                            "test": s["test"]})
    backend = CNNBackend(vgg_for("mnist"), local_epochs=1, batch_size=32)
    return backend, client_data, splits


def _run_coord(cnn_world, **over):
    import jax

    from repro.core.coordinator import DagAflConfig, DagAflCoordinator
    from repro.core.simulator import CostModel, make_profiles
    backend, client_data, splits = cnn_world
    cfg = DagAflConfig(n_clients=3, max_rounds=2, local_epochs=1, seed=0,
                       target_accuracy=None, patience=10 ** 6, **over)
    coord = DagAflCoordinator(backend, client_data, splits["test"], cfg,
                              CostModel(local_epoch=2.0),
                              make_profiles(3, 0.5, 0))
    res = coord.run(init_key=jax.random.PRNGKey(0))
    return coord, res


def test_serving_is_readonly_training_bit_identical(cnn_world):
    """The publisher + query stream ride the same event heap but mutate no
    training state: every published transaction's model must be
    bit-identical with serving on vs off."""
    coord_off, res_off = _run_coord(cnn_world)
    coord_on, res_on = _run_coord(
        cnn_world,
        serving=ServingConfig(every=2.0, query_rate=1.0, query_batch=8,
                              backend="cnn", seed=99))
    assert res_on.rounds == res_off.rounds
    assert res_on.sim_time == res_off.sim_time
    assert res_on.extra["chain_len"] == res_off.extra["chain_len"]
    txs_on = {t.tx_id: t for t in coord_on.ledger.transactions()}
    for t in coord_off.ledger.transactions():
        other = txs_on[t.tx_id]
        assert other.parents == t.parents
        assert trees_bitwise_equal(coord_off.store.get(t.model_ref),
                                   coord_on.store.get(other.model_ref))
    serving = res_on.extra["serving"]
    assert serving["queries"] > 0 and serving["replica_versions"] >= 1
    assert serving["skipped"] == 0
    assert replica_parity(coord_on.publisher.replica(), coord_on.store)


def test_serving_report_absent_when_off(cnn_world):
    _, res = _run_coord(cnn_world)
    assert "serving" not in res.extra

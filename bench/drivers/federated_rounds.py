"""Entry driver: DAG-AFL federated rounds on ``DagAflCoordinator``'s own
event loop with the cohort engine, at a configuration's published widths.

The coordinator is built from the seed (bench-made weights and data) and
``run()`` drives everything.  The benchmark only wraps bound methods of
that instance: spans around each layer's calls, a record of every round
(its batch seed, parents and published answers), and the cohort window's
flush, where the measured window opens and closes.  The window opens at
the first flush after set-up's rounds (every client published once, and
at least ``warm_flushes`` cohort windows), runs for ``--seconds`` and
closes at the next flush.

The simulated schedule is deterministic for a seed, so set-up first
rehearses it on a coordinator of its own: the same rounds up to the
window's opening and on past it, until the flushes that compiled nothing
add up to twice ``--seconds``.  That compiles (or loads) every program
shape the window will meet and no other; the rehearsal is then freed and
the measured coordinator built anew.

``rounds_per_s`` is the client rounds run by the window's cohort windows
(each trained, validated, signed and then published at its simulated
completion time) over the window's wall seconds.  After it closes, the
reference (bench/reference/vgg.py) recomputes (a) the first cohort window
of set-up, every client's local training from the bench-made genesis, and
(b) two rounds drawn from the seed in the window's first cohort window of
two rounds or more: their Eq. 6 aggregate from the published parents, and
local training from the aggregate the program trained from, validation
accuracy and Eq. 3 signature.  Each layer is judged from the inputs it
was given, so a parent mean that the program rounds (its einsum runs in
one bfloat16 pass) counts in ``aggregate_gap`` and not again, amplified
by the first SGD step, in the training numbers.  The ledger's Eq. 7 hash
chain over the window is re-derived in full.
"""
from __future__ import annotations

import hashlib
import json
import time

import numpy as np

MAX_REHEARSED_FLUSHES = 64
SPANS = (("selector", "select", "tip_selection"),
         ("cohort", "evaluate_many", "tip_validation"),
         ("cohort", "prefetch_window", "window_assembly"),
         ("cohort", "train_cohort_stacked", "cohort_train"),
         ("cohort", "evaluate_cohort_stacked", "cohort_eval"),
         ("cohort", "signature_cohort_stacked", "cohort_signature"),
         ("cohort", "evaluate_shared", "global_eval"),
         ("ledger", "add_transaction", "ledger_append"),
         ("ledger", "maybe_checkpoint", "ledger_checkpoint"))


def _seeds(seed: int) -> dict:
    """What ``--seed`` draws: image content, genesis weights, and the rounds
    the reference recomputes.  The schedule (client speeds, arrivals, batch
    order) comes from the traffic's ``schedule_seed``, so every seed does
    the same work."""
    names = ("content", "weights", "sample")
    return dict(zip(names, (int(s) for s in
                            np.random.SeedSequence(seed).generate_state(3))))


def _vgg_config(cfg: dict):
    from repro.configs.cnn import CNNConfig
    return CNNConfig(name=cfg["name"], citation=cfg["source"],
                     conv_stacks=tuple(tuple(s) for s in cfg["conv_stacks"]),
                     fc_dims=tuple(cfg["fc_dims"]),
                     n_classes=cfg["n_classes"], image_size=cfg["image_size"],
                     in_channels=cfg["in_channels"],
                     kernel_size=cfg["kernel_size"],
                     signature_layer=cfg["signature_layer"])


class Rounds:
    """Per-round record kept from the coordinator's own calls."""

    def __init__(self, n_clients: int):
        self.per_client = [0] * n_clients
        self.front = {}           # (client, epoch after) -> round record
        self.published = {}       # tx_id -> published answer
        self.window_txs = []      # tx ids published in the window
        self.bodies = {}          # tx id -> its ledger body, kept at publish
        self.current = []         # rounds of the flush in progress

    def on_front(self, rd: dict) -> None:
        rec = {"client": rd["client"], "seed": rd["seed"],
               "epoch": rd["epoch"] + 1, "refs": list(rd["refs"]),
               "parents": list(rd["parents"])}
        self.front[(rd["client"], rd["epoch"] + 1)] = rec
        self.current.append(rec)


def world(run):
    """What the seed makes once for both coordinators: the clients' shards,
    the global test set and the genesis weights (on the device)."""
    import jax

    from bench.gen import images
    from bench.reference import vgg as ref
    seeds = _seeds(run.seed)
    clients, test = images.client_world(run.traffic, run.config,
                                        seeds["content"])
    genesis = jax.jit(lambda key: ref.init(key, run.config))(
        jax.random.PRNGKey(seeds["weights"]))
    jax.block_until_ready(genesis)
    return clients, test, genesis


class Driver:
    def __init__(self, run, made, rehearsal: bool = False):
        self.run = run
        self.cfg = run.config
        self.t = run.traffic
        self.seeds = _seeds(run.seed)
        self.clients, self.test, self.genesis = made
        self.rehearsal = rehearsal
        self.patched = []         # (object, attribute, original or None)
        self.steady_s = 0.0       # rehearsal: time of flushes that compiled
        # which rounds of the window's first cohort window of two or more
        # are recomputed after the close
        self.rng = np.random.default_rng(self.seeds["sample"])
        self.phase = "warmup"
        self.flushes = 0
        self.window_flushes = 0
        self.t_open = None
        self.first = None         # first cohort window from genesis
        self.capture = None       # program outputs of the flush in progress
        self.sampled = []         # window rounds recomputed after the close
        self.sizes = []           # rounds per cohort window (window)
        self.steps = [0, 0]       # real client steps, steps run (window)
        self.samples = 0          # real training samples (window)

    # -- build ----------------------------------------------------------------

    def build(self, shared=None):
        """The coordinator, on ``shared`` (the backend and cohort engine of
        a rehearsal, whose compiled programs it then reuses) or new ones."""
        from bench.gen import images
        from repro.core.coordinator import DagAflConfig, DagAflCoordinator
        from repro.core.simulator import (ClientProfile, ConvergenceTracker,
                                          CostModel)
        from repro.core.tip_selection import TipSelectionConfig
        from repro.fl.backend import CNNBackend

        cfg, t, test = self.cfg, self.t, self.test
        if shared is None:
            backend = CNNBackend(_vgg_config(cfg), lr=cfg["optimizer"]["lr"],
                                 local_epochs=cfg["local_epochs"],
                                 batch_size=cfg["batch_size"],
                                 kernel_policy=cfg["kernel_policy"])
            engine = None
        else:
            backend, engine = shared
        self._patch(backend, "init", lambda key: self.genesis)
        self.backend = backend
        n = t["n_clients"]
        dcfg = DagAflConfig(
            n_clients=n, max_rounds=10 ** 9,
            local_epochs=cfg["local_epochs"],
            tip=TipSelectionConfig(n_select=t["n_select"]),
            heterogeneity=t["heterogeneity"], seed=t["schedule_seed"],
            cohort_size=t["cohort_size"], cohort_window=t["cohort_window"],
            mesh=t["mesh"], kernel_policy=cfg["kernel_policy"],
            ledger_checkpoint_every=t["ledger_checkpoint_every"])
        profs = [ClientProfile(c, *p) for c, p in enumerate(
            images.profiles(n, t["heterogeneity"], t["schedule_seed"]))]
        coord = DagAflCoordinator(backend, self.clients, test, dcfg,
                                  CostModel(local_epoch=t["local_epoch_cost"]),
                                  profs, cohort_engine=engine)

        class WindowTracker(ConvergenceTracker):
            """Never converges; the benchmark stops the loop."""
            stop = False

            @property
            def done(self):
                return self.stop

        coord.tracker = WindowTracker()
        self.coord = coord
        self.rounds = Rounds(n)
        self._hook()

    def _patch(self, obj, attr, new):
        self.patched.append((obj, attr, vars(obj).get(attr)))
        setattr(obj, attr, new)

    def unpatch(self):
        """Take the hooks off the backend and the cohort engine, which a
        later coordinator shares."""
        for obj, attr, orig in reversed(self.patched):
            if orig is None:
                delattr(obj, attr)
            else:
                setattr(obj, attr, orig)
        self.patched = []

    def _hook(self):
        coord, spans, rounds = self.coord, self.run.spans, self.rounds
        for owner, attr, name in SPANS:
            obj = getattr(coord, owner)
            self._patch(obj, attr, spans.wrapped(getattr(obj, attr), name))

        front = coord._front_half

        def front_half(client, t_start):
            with spans.span("front_half"):
                rd = front(client, t_start)
            rounds.on_front(rd)
            return rd

        coord._front_half = front_half

        publish = coord._publish

        def publish_round(client, model, accuracy, sig, epoch, parents):
            with spans.span("publish"):
                tx = publish(client, model, accuracy, sig, epoch, parents)
            rounds.per_client[client] += 1
            rounds.published[tx] = {
                "client": client, "epoch": epoch, "accuracy": float(accuracy),
                "signature": np.asarray(sig, np.float32)}
            if self.phase == "window":
                rounds.window_txs.append(tx)
                rounds.bodies[tx] = self.coord.ledger.get_tx(tx)
            for s in self.sampled:
                if s.get("key") == (client, epoch):
                    s["model"], s["tx"] = model, tx
            self._maybe_stop()
            return tx

        coord._publish = publish_round

        cohort = coord.cohort
        take = cohort.assembler.take

        def take_window(datasets, seeds, epochs, target):
            win = take(datasets, seeds, epochs, target)
            if self.phase == "window":
                real = sum(win.steps)
                self.steps[0] += real
                self.steps[1] += int(win.xb.shape[0]) * int(win.xb.shape[1])
                self.samples += real * int(win.xb.shape[2])
            return win

        self._patch(cohort.assembler, "take", take_window)

        # a window of one round runs the backend's own programs, unpadded
        backend, bs = self.backend, self.cfg["batch_size"]
        train_one = backend.train_local

        def train_local(params, ds, seed=0, epochs=None):
            if self.phase == "window":
                steps = max(len(ds) // bs, 1) * (epochs or backend.local_epochs)
                self.steps[0] += steps
                self.steps[1] += steps
                self.samples += steps * bs
            return train_one(params, ds, seed=seed, epochs=epochs)

        self._patch(backend, "train_local", train_local)

        for attr in ("_train_jit", "_train_uniform_jit"):
            self._wrap_train(cohort, attr)

        window = coord._window
        flush = window.flush_fn
        window.flush_fn = lambda batch: self._flush(flush, batch)

    def _wrap_train(self, cohort, attr):
        prog = getattr(cohort, attr)

        def train(*args):
            out = prog(*args)
            if self.capture is not None:
                self.capture.update({"in": args[0], "out": out})
            for s in self.sampled:
                if "agg" not in s and s["flush"] == self.window_flushes \
                        and self.phase == "window":
                    s["agg"] = _row(args[0], s["row"])
                    s["losses"] = out[1]
            return out

        self._patch(cohort, attr, train)

    # -- the window -----------------------------------------------------------

    def _window_over(self) -> bool:
        if self.rehearsal:
            return self.steady_s >= 2 * self.run.seconds or \
                self.window_flushes >= MAX_REHEARSED_FLUSHES
        return time.perf_counter() - self.t_open >= self.run.seconds

    def _flush(self, flush, batch):
        run = self.run
        if self.phase == "done":
            return
        if self.phase == "window" and self._window_over():
            if not self.rehearsal:
                run.close_window()
            self.phase = "done"
            self._maybe_stop()
            return
        if self.phase == "warmup" and self.first is not None \
                and min(self.rounds.per_client) >= 1 \
                and self.flushes >= self.t["check"]["warm_flushes"]:
            self.phase = "window"
            if not self.rehearsal:
                run.open_window()
            self.t_open = time.perf_counter()
        self.flushes += 1
        self.rounds.current = []
        if self.phase == "window":
            self.window_flushes += 1
            self.sizes.append(len(batch))
            if len(batch) > 1 and not self.sampled and not self.rehearsal:
                rows = self.rng.permutation(len(batch))[
                    :self.t["check"]["window_rounds"]]
                self.sampled = [{"flush": self.window_flushes, "row": int(r)}
                                for r in rows]
        elif self.first is None:
            self.capture = {}
        c0, t0 = run.compiles.snapshot()[0], time.perf_counter()
        with run.spans.span("flush_cohort"):
            flush(batch)
        if self.phase == "window" and run.compiles.snapshot()[0] == c0:
            self.steady_s += time.perf_counter() - t0
        cap, self.capture = self.capture, None
        if cap and all(rec["refs"] == ["genesis"]
                       for rec in self.rounds.current):
            self._keep_first(cap, list(self.rounds.current))
        for s in self.sampled:
            if s["flush"] == self.window_flushes and "rec" not in s:
                s["rec"] = rec = self.rounds.current[s["row"]]
                s["key"] = (rec["client"], rec["epoch"])
                s["parents"] = [self.coord.store.get(r) for r in rec["refs"]]

    def _maybe_stop(self):
        """Stop the loop once the window has closed and the sampled rounds
        have published (their publish events may fall after the close;
        flushes after it are dropped)."""
        if self.phase == "done" and all("model" in s for s in self.sampled):
            self.coord.tracker.stop = True

    def _keep_first(self, cap, rounds):
        """Host copy of the first cohort window from genesis: its trained
        models, the aggregates it trained from, and its per-step losses
        (set-up, not timed)."""
        import jax
        new, losses = cap["out"]
        k = len(rounds)
        self.first = {
            "rounds": rounds,
            "models": jax.device_get(jax.tree_util.tree_map(
                lambda a: a[:k], new)),
            "agg": jax.device_get(jax.tree_util.tree_map(
                lambda a: a[:k], cap["in"])),
            "losses": np.asarray(losses)[:k]}

    # -- after the window -----------------------------------------------------

    def check(self) -> dict:
        """Recompute with the reference; each number is a gap that the
        cell's limits bound."""
        import jax

        from bench.harness import checks
        from bench.reference import vgg as ref

        cfg, fz = self.cfg, ref.freeze(self.cfg)
        opt, bs, ep = cfg["optimizer"], cfg["batch_size"], cfg["local_epochs"]
        gaps = {"loss_gap": 0.0, "median_change_gap": 0.0,
                "aggregate_gap": 0.0, "accuracy_gap": 0.0,
                "signature_gap": 0.0}

        def answers(model, client, published):
            val = self.clients[client]["val"]
            tr = self.clients[client]["train"]
            n, ns = min(len(val), 512), min(len(tr), 128)
            acc = float(ref.accuracy(model, val.x[:n], val.y[:n], cfg_key=fz))
            sig = np.asarray(ref.signature(model, tr.x[:ns], cfg_key=fz))
            gaps["accuracy_gap"] = max(gaps["accuracy_gap"],
                                       abs(acc - published["accuracy"]))
            gaps["signature_gap"] = max(gaps["signature_gap"], float(
                np.max(np.abs(sig - published["signature"]))))

        def training(start_prog, start_ref, prog_model, client, seed,
                     prog_losses):
            ds = self.clients[client]["train"]
            r_model, losses, g0 = ref.train(start_ref, ds.x, ds.y, seed, cfg,
                                            opt, bs, ep, keep_first_grads=True,
                                            quant=ref.precision(cfg))
            keep = checks.moving_leaves(checks.norms(g0))
            dp = np.asarray(checks.delta_norms(prog_model, start_prog))
            dr = np.asarray(checks.delta_norms(r_model, start_ref))
            change = checks.norm_gap(dp, dr, keep)
            median = checks.median_norm_gap(dp, dr, keep)
            loss = checks.loss_gap(prog_losses, losses)
            gaps["loss_gap"] = max(gaps["loss_gap"], loss)
            gaps["median_change_gap"] = max(gaps["median_change_gap"], median)
            at = [i for i in (0, 1, 2, 3, 4, 9, 19, 49, 99) if i < len(losses)]
            self.run.log(
                f"client {client}, {len(losses)} steps: losses at {at}, "
                f"reference {[round(losses[i], 5) for i in at]}, program "
                f"{[round(float(prog_losses[i]), 5) for i in at]}; loss gap "
                f"over 3 steps {loss:.6f}; change gap median leaf "
                f"{median:.6f}, worst leaf {change:.6f}")

        first = self.first
        for k, rec in enumerate(first["rounds"]):
            model = jax.tree_util.tree_map(lambda a: a[k], first["models"])
            agg = jax.tree_util.tree_map(lambda a: a[k], first["agg"])
            gaps["aggregate_gap"] = max(gaps["aggregate_gap"],
                                        checks.relative_diff(agg, self.genesis))
            training(agg, self.genesis, model, rec["client"], rec["seed"],
                     first["losses"][k])
            tx = self._tx_of(rec)
            answers(model, rec["client"], self.rounds.published[tx])
        checked = len(first["rounds"])
        for s in self.sampled:
            if "model" not in s:
                continue
            agg_ref = ref.mean(s["parents"])
            gaps["aggregate_gap"] = max(gaps["aggregate_gap"],
                                        checks.relative_diff(s["agg"], agg_ref))
            training(s["agg"], s["agg"], s["model"], s["rec"]["client"],
                     s["rec"]["seed"], np.asarray(s["losses"])[s["row"]])
            answers(s["model"], s["rec"]["client"],
                    self.rounds.published[s["tx"]])
            checked += 1
        gaps["rounds_unchecked"] = float(
            len(first["rounds"]) + self.t["check"]["window_rounds"] - checked)
        return gaps

    def _tx_of(self, rec):
        for tx, p in self.rounds.published.items():
            if p["client"] == rec["client"] and \
                    self.rounds.front.get((p["client"], p["epoch"])) is rec:
                return tx
        raise KeyError("round never published")

    def _ledger_mismatches(self) -> int:
        """Window transactions whose Eq. 7 hash does not re-derive from
        their parents' hashes and metadata, whose metadata is not what the
        round published, or whose hash the ledger no longer holds.  The
        bounded ledger folds confirmed transactions into checkpoints and
        keeps only their hashes, so each body is the one kept at publish."""
        led = self.coord.ledger
        bad = 0
        for tx_id in self.rounds.window_txs:
            tx = self.rounds.bodies[tx_id]
            md = tx.metadata
            pub = self.rounds.published[tx_id]
            payload = json.dumps({
                "client_id": md.client_id,
                "signature": [round(float(s), 8) for s in md.signature],
                "model_accuracy": round(float(md.model_accuracy), 8),
                "current_epoch": int(md.current_epoch),
                "validation_node_id": int(md.validation_node_id),
            }, sort_keys=True)
            h = hashlib.sha256()
            for p in tx.parents:
                h.update(led.hash_of(p).encode())
            h.update(hashlib.sha256(payload.encode()).hexdigest().encode())
            ok = (h.hexdigest() == tx.tx_hash == led.hash_of(tx_id)
                  and md.model_accuracy == pub["accuracy"]
                  and np.allclose(md.signature, pub["signature"][:16],
                                  rtol=0, atol=0))
            bad += not ok
        return bad


def _row(stacked, row: int):
    import jax
    return jax.tree_util.tree_map(lambda a: a[row], stacked)


def run(ctx) -> dict:
    import gc

    from bench.harness import device
    made = world(ctx)
    t0 = time.perf_counter()
    rehearsal = Driver(ctx, made, rehearsal=True)
    rehearsal.build()
    rehearsal.coord.run()
    ctx.log(f"rehearsal {time.perf_counter() - t0:.3f} s: "
            f"{rehearsal.flushes} cohort windows, rounds in each of the "
            f"last {rehearsal.window_flushes}: {rehearsal.sizes}; "
            f"{rehearsal.steady_s:.3f} s of those compiled nothing")
    shared = rehearsal.backend, rehearsal.coord.cohort
    rehearsal.unpatch()
    del rehearsal
    gc.collect()
    d = Driver(ctx, made)
    d.build(shared)
    d.coord.run()
    if d.phase != "done":
        raise RuntimeError("the coordinator stopped before the window closed")
    peak = device.memory_peak_bytes(ctx.devices)
    rounds = int(sum(d.sizes))
    raw = {"rounds": rounds, "flushes": d.window_flushes,
           "real_steps": d.steps[0], "scan_steps": d.steps[1],
           "samples": d.samples}
    sizes = np.bincount(d.sizes, minlength=d.t["cohort_size"] + 1)[1:]
    ctx.log(f"{rounds} rounds in {d.window_flushes} cohort windows "
            f"{d.sizes}, rounds per window 1..{len(sizes)}: {sizes.tolist()}; "
            f"{len(d.rounds.window_txs)} published in the window; useful "
            f"steps {d.steps[0]} of {d.steps[1]}; set-up flushes "
            f"{d.flushes - d.window_flushes}")
    mismatches = d._ledger_mismatches()
    # the program's state goes before the reference runs
    d.coord = None
    gc.collect()
    gaps = d.check()
    gaps["ledger_mismatches"] = float(mismatches)
    return {"attempted": rounds, "failed": 0,
            "e2e": {"rounds_per_s": rounds / ctx.window_s},
            "raw": raw, "checks": gaps, "memory_peak_bytes": peak,
            "span_names": [s[2] for s in SPANS] + [
                "front_half", "publish", "flush_cohort"]}

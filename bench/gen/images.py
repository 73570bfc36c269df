"""Federated image traffic: seeded CIFAR-10-shaped images split 8:1:1,
Dirichlet-partitioned over clients, and the clients' simulated speeds.

Copied from the program's generators (``repro.data.synthetic``,
``repro.data.partition``, ``repro.core.simulator.make_profiles``) so that a
change to them cannot move the yardstick, with one change: labels, and so
every shard's size, come from ``partition_seed`` alone, and the run's seed
draws only the image content.  Every seed then does the same amount of
work."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Dataset:
    x: np.ndarray
    y: np.ndarray

    def __len__(self):
        return len(self.y)


def _prototypes(n_classes, size, channels, rng):
    """Smooth per-class prototype images (low-frequency random fields)."""
    base = rng.normal(0, 1, (n_classes, size // 4 + 1, size // 4 + 1,
                             channels))
    protos = np.zeros((n_classes, size, size, channels), np.float32)
    for c in range(n_classes):
        protos[c] = np.kron(base[c], np.ones((4, 4, 1)))[:size, :size]
    protos /= np.maximum(np.abs(protos).max(axis=(1, 2, 3), keepdims=True),
                         1e-6)
    return protos


def make_images(n, n_classes, size, channels, noise, *, label_seed,
                content_seed) -> Dataset:
    """Prototype + brightness + noise images; labels from ``label_seed``."""
    y = np.random.default_rng(label_seed).integers(0, n_classes, n)
    rng = np.random.default_rng(content_seed)
    protos = _prototypes(n_classes, size, channels, rng)
    x = protos[y] * rng.uniform(0.7, 1.3, (n, 1, 1, 1)).astype(np.float32)
    x += noise * rng.standard_normal(x.shape, dtype=np.float32)
    return Dataset(x, y.astype(np.int32))


def split_811(ds: Dataset, seed: int):
    """Train/val/test at 8:1:1 (DAG-AFL SIV-A)."""
    idx = np.random.default_rng(seed).permutation(len(ds))
    n = len(ds)
    n_tr, n_val = int(0.8 * n), int(0.1 * n)
    sl = {"train": idx[:n_tr], "val": idx[n_tr:n_tr + n_val],
          "test": idx[n_tr + n_val:]}
    return {k: Dataset(ds.x[v], ds.y[v]) for k, v in sl.items()}


def partition_dirichlet(ds: Dataset, n_clients: int, beta: float, seed: int,
                        min_per_client: int):
    """Label-Dirichlet partition; starved clients are topped up without
    duplicates from the global pool."""
    rng = np.random.default_rng(seed)
    n_classes = int(ds.y.max()) + 1
    client_idx = [[] for _ in range(n_clients)]
    for c in range(n_classes):
        idx_c = np.where(ds.y == c)[0]
        rng.shuffle(idx_c)
        props = rng.dirichlet(np.full(n_clients, beta))
        cuts = (np.cumsum(props) * len(idx_c)).astype(int)[:-1]
        for k, part in enumerate(np.split(idx_c, cuts)):
            client_idx[k].extend(part.tolist())
    for k in range(n_clients):
        missing = min_per_client - len(client_idx[k])
        if missing > 0:
            pool = np.setdiff1d(np.arange(len(ds)),
                                np.asarray(client_idx[k], dtype=int))
            client_idx[k].extend(rng.choice(pool, size=min(missing, len(pool)),
                                            replace=False).tolist())
    out = []
    for k in range(n_clients):
        sel = np.asarray(client_idx[k])
        rng.shuffle(sel)
        out.append(Dataset(ds.x[sel], ds.y[sel]))
    return out


def client_world(t: dict, cfg: dict, content_seed: int):
    """(per-client {"train","val","test"} shards, global test set)."""
    ps = int(t["partition_seed"])
    ds = make_images(t["n_samples"], cfg["n_classes"], cfg["image_size"],
                     cfg["in_channels"], t["noise"], label_seed=ps,
                     content_seed=content_seed)
    splits = split_811(ds, ps)
    parts = partition_dirichlet(splits["train"], t["n_clients"],
                                t["dirichlet_beta"], ps, t["min_per_client"])
    return [split_811(p, ps + 1) for p in parts], splits["test"]


def client_steps(t: dict, cfg: dict, batch_size: int, epochs: int):
    """Real local SGD steps of each client (shard geometry only)."""
    y = np.random.default_rng(int(t["partition_seed"])).integers(
        0, cfg["n_classes"], t["n_samples"])
    ds = Dataset(np.zeros((len(y), 1), np.float32), y.astype(np.int32))
    ps = int(t["partition_seed"])
    parts = partition_dirichlet(split_811(ds, ps)["train"], t["n_clients"],
                                t["dirichlet_beta"], ps, t["min_per_client"])
    return [epochs * max(len(split_811(p, ps + 1)["train"]) // batch_size, 1)
            for p in parts]


def profiles(n_clients: int, heterogeneity: float, seed: int):
    """Lognormal client speed, bandwidth and latency draws, as
    (speed, bandwidth, latency) tuples."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_clients):
        speed = float(np.exp(rng.normal(0.0, heterogeneity)))
        bw = float(50e6 * np.exp(rng.normal(0.0, heterogeneity)))
        lat = float(np.abs(rng.normal(0.05, 0.02)) + 0.01)
        out.append((speed, bw, lat))
    return out

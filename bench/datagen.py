"""The benchmark's own copy of the seeded federated data generator.

Class-conditional synthetic images at the datasets' resolutions and class
counts, a Dirichlet(alpha) label-skew split over the fleet, fixed-size
per-client sample tensors and a clean per-cloud reference set. Kept here,
apart from the program, so that a change to the program cannot move the
inputs the benchmark feeds it; for a seed it yields the same arrays as
the program's ``make_data`` at the time this copy was taken.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

@dataclass(frozen=True)
class FleetData:
    client_x: np.ndarray      # (N, S, H, W, C) float32
    client_y: np.ndarray      # (N, S) int64
    ref_x: np.ndarray         # (K, R, H, W, C) float32
    ref_y: np.ndarray         # (K, R) int64
    test_x: np.ndarray
    test_y: np.ndarray
    n_classes: int


def class_conditional_images(rng: np.random.Generator, n: int,
                             shape: Tuple[int, int, int], n_classes: int,
                             n_prototypes: int = 3, noise: float = 0.35
                             ) -> Tuple[np.ndarray, np.ndarray]:
    """Each class is a mixture of smooth low-frequency prototypes plus
    noise, scaled to [0, 1]."""
    h, w, c = shape
    y = rng.integers(0, n_classes, size=n)
    coarse = 4
    protos = rng.normal(0, 1, size=(n_classes, n_prototypes, coarse, coarse, c))
    reps_h, reps_w = h // coarse + 1, w // coarse + 1
    protos_full = np.repeat(np.repeat(protos, reps_h, axis=2), reps_w, axis=3)
    protos_full = protos_full[:, :, :h, :w, :]
    which = rng.integers(0, n_prototypes, size=n)
    x = protos_full[y, which] + noise * rng.normal(0, 1, size=(n, h, w, c))
    x = (x - x.min()) / (x.max() - x.min() + 1e-9)
    return x.astype(np.float32), y.astype(np.int64)


def dirichlet_partition(labels: np.ndarray, n_clients: int, alpha: float,
                        seed: int, min_size: int = 8) -> List[np.ndarray]:
    """Per-client index arrays with class shares drawn from
    Dirichlet(alpha); redrawn slightly more uniform until every client
    holds at least ``min_size`` samples."""
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    while True:
        idx_per_client: List[List[int]] = [[] for _ in range(n_clients)]
        for c in range(n_classes):
            idx_c = np.nonzero(labels == c)[0]
            rng.shuffle(idx_c)
            props = rng.dirichlet(np.full(n_clients, alpha))
            cuts = (np.cumsum(props) * len(idx_c)).astype(int)[:-1]
            for i, part in enumerate(np.split(idx_c, cuts)):
                idx_per_client[i].extend(part.tolist())
        if min(len(ix) for ix in idx_per_client) >= min_size:
            break
        alpha *= 1.5
    return [np.array(sorted(ix)) for ix in idx_per_client]


def make_fleet_data(shape: Tuple[int, int, int], n_classes: int,
                    n_clouds: int, clients_per_cloud: int, *,
                    n_samples: int, samples_per_client: int,
                    ref_samples: int, alpha: float, seed: int,
                    test_frac: float = 0.15) -> FleetData:
    """The whole fleet's inputs for one seed (clients ordered cloud by
    cloud, as the even topology assigns them)."""
    rng = np.random.default_rng(seed)
    x_all, y_all = class_conditional_images(rng, n_samples, shape, n_classes)
    n_clients = n_clouds * clients_per_cloud

    rng = np.random.default_rng(seed)
    n_test = int(n_samples * test_frac)
    perm = rng.permutation(n_samples)
    test_ix, pool_ix = perm[:n_test], perm[n_test:]
    ref_ix = pool_ix[:n_clouds * ref_samples].reshape(n_clouds, ref_samples)
    train_ix = pool_ix[n_clouds * ref_samples:]
    parts = dirichlet_partition(y_all[train_ix], n_clients, alpha, seed=seed)
    s = samples_per_client
    cx = np.empty((n_clients, s) + shape, np.float32)
    cy = np.empty((n_clients, s), np.int64)
    for i, p in enumerate(parts):
        ix = train_ix[p]
        take = rng.choice(ix, size=s, replace=len(ix) < s)
        cx[i], cy[i] = x_all[take], y_all[take]
    return FleetData(client_x=cx, client_y=cy,
                     ref_x=x_all[ref_ix], ref_y=y_all[ref_ix],
                     test_x=x_all[test_ix], test_y=y_all[test_ix],
                     n_classes=n_classes)

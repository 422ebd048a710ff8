"""Non-IID, imbalanced federated partition of the image pool.

A copy of the program's ``repro.data.partition.partition_clients``
(paper §V-A): client ``i`` has primary label ``i mod 10``, a share ``nu``
of its samples carries that label and the rest is drawn uniformly from
the pool; local sizes are spread over ``[varpi * low, varpi * high]``
(``varpi`` = pool size / clients; the paper's [varpi/6, 2 varpi]); each
shard splits 80/10/10 into train, validation and test.

One departure: the multiset of local sizes is the same for every seed
(evenly spaced over the range, so still uniform) and the seed only
permutes which client gets which size.  The program compiles one
training program per capacity class, and the classes follow from the
sizes, so a seed that changed the sizes would change the compiled
shapes and the work of a round.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Client:
    train_idx: np.ndarray
    val_idx: np.ndarray
    test_idx: np.ndarray
    primary_label: int

    @property
    def size(self) -> int:
        return len(self.train_idx)


def local_sizes(n_pool: int, n_clients: int, low: float, high: float
                ) -> np.ndarray:
    """The fixed multiset of local shard sizes (before the train split),
    in ascending order."""
    varpi = n_pool // n_clients
    lo = max(int(varpi * low), 10)
    hi = max(int(varpi * high), lo + 1)
    k = np.arange(n_clients, dtype=np.float64)
    return (lo + np.floor((hi - lo + 1) * (k + 0.5) / n_clients)
            ).astype(np.int64)


def partition(y: np.ndarray, n_clients: int, num_classes: int,
              non_iid: float, low: float, high: float,
              seed: int) -> List[Client]:
    rng = np.random.default_rng(seed)
    n_pool = len(y)
    by_label = [np.nonzero(y == c)[0] for c in range(num_classes)]
    sizes = rng.permutation(local_sizes(n_pool, n_clients, low, high))
    clients = []
    for i in range(n_clients):
        primary = i % num_classes
        size = int(sizes[i])
        n_primary = int(round(non_iid * size))
        idx_p = rng.choice(by_label[primary], n_primary,
                           replace=len(by_label[primary]) < n_primary)
        idx_r = (rng.choice(n_pool, size - n_primary, replace=False)
                 if size > n_primary else np.empty((0,), np.int64))
        idx = np.concatenate([idx_p, idx_r])
        rng.shuffle(idx)
        n_tr = int(0.8 * size)
        n_va = int(0.1 * size)
        clients.append(Client(train_idx=idx[:n_tr],
                              val_idx=idx[n_tr:n_tr + n_va],
                              test_idx=idx[n_tr + n_va:],
                              primary_label=primary))
    return clients

"""Every seed offers the same work in another order: each block of epochs
holds the same gaps and the same requests, whatever the seed."""
import collections
import itertools

import numpy as np
import pytest

import benchutil as U  # noqa: F401  (puts the repo on the path)
from bench import traffic as T


def first_block(cell: str, seed: int):
    spec = T.load_workload(cell)
    dep = T.load_deployment(spec["config_file"])
    tr = spec["traffic"]
    log: dict = {}
    eps = list(itertools.islice(
        T.epochs(dep, tr, T.rng_for(seed, 1), t0=0.0, prefix="s", log=log,
                 timed=True), int(tr["block_epochs"])))
    times = np.array([t for t, _ in eps])
    gaps = np.diff(np.concatenate([[0.0], times]))
    work = collections.Counter((q.kind, q.src, q.dst) for q in log.values())
    return dep, tr, gaps, work, [q.kind for q in log.values()]


@pytest.mark.parametrize("cell", ["usb-paper.b1", "usb-lm.b32-refdrain"])
def test_seeds_share_the_work_in_another_order(cell):
    dep, tr, g1, w1, k1 = first_block(cell, 2**31 + 11)
    _, _, g2, w2, k2 = first_block(cell, -5)
    np.testing.assert_allclose(np.sort(g1), np.sort(g2), rtol=1e-12)
    assert w1 == w2 and k1 != k2
    assert not np.allclose(g1, g2)
    n = int(tr["block_epochs"]) * int(tr["per_epoch"])
    assert sum(w1.values()) == n
    per_kind = np.bincount(k1, minlength=len(dep.weights)) / n
    np.testing.assert_allclose(per_kind, dep.weights, atol=1.0 / n)
    rate = float(tr["load"]) / dep.mean_service_s / int(tr["per_epoch"])
    assert abs(np.mean(g1) * rate - 1.0) < 0.05


def test_largest_remainder():
    assert T.largest_remainder(np.array([0.7, 0.3]), 10).tolist() == [7, 3]
    c = T.largest_remainder(np.array([1.0, 1.0, 1.0]), 10)
    assert c.sum() == 10 and c.tolist() == [4, 3, 3]

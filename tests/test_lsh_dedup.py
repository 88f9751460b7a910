"""LSH dedup index (VERDICT round-1 item 10): sub-quadratic near-dup
detection across many models."""

import numpy as np
import pytest

from netsdb_tpu.core.blocked import BlockedTensor
from netsdb_tpu.dedup.lsh import (LSHIndex, block_signatures,
                                  dedup_model_zoo)


def _tensor(arr, block=64):
    return BlockedTensor.from_dense(arr.astype(np.float32),
                                    (block, block))


def test_signatures_stable_and_near_dup_close():
    rng = np.random.default_rng(0)
    base = rng.standard_normal((128, 64))
    t1 = _tensor(base)
    t2 = _tensor(base + 1e-5 * rng.standard_normal(base.shape))
    t3 = _tensor(rng.standard_normal((128, 64)))
    _, s1 = block_signatures(t1)
    _, s1b = block_signatures(t1)
    np.testing.assert_array_equal(s1, s1b)  # deterministic
    _, s2 = block_signatures(t2)
    _, s3 = block_signatures(t3)
    near = np.count_nonzero(s1 != s2, axis=1)
    far = np.count_nonzero(s1 != s3, axis=1)
    assert near.max() < 8
    assert far.min() > 32  # unrelated blocks disagree broadly


def test_index_groups_variants_not_strangers():
    rng = np.random.default_rng(1)
    base = rng.standard_normal((128, 64))
    index = LSHIndex()
    index.add_model("a", _tensor(base))
    index.add_model("b", _tensor(base + 1e-5 * rng.standard_normal(
        base.shape)))
    index.add_model("c", _tensor(rng.standard_normal((128, 64))))
    groups = index.near_duplicate_groups()
    names = sorted({n for g in groups for n, _ in g})
    assert names == ["a", "b"]
    # every group pairs one block of a with the same block of b
    for g in groups:
        assert {n for n, _ in g} == {"a", "b"}
        assert len({idx for _, idx in g}) == 1


def test_candidates_are_subquadratic():
    rng = np.random.default_rng(2)
    models = {f"m{i}": _tensor(rng.standard_normal((128, 64)))
              for i in range(30)}
    res = dedup_model_zoo(models)
    assert res["groups"] == []  # all-distinct zoo: nothing groups
    assert res["pair_work_fraction"] < 0.2  # and few pairs verified

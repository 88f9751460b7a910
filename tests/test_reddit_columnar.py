"""Columnar reddit vs the host-object pipeline (VERDICT round-1 item
6): identical synthetic data through both paths, results must agree."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from netsdb_tpu.workloads import reddit as R
from netsdb_tpu.workloads import reddit_columnar as RC


@pytest.fixture(scope="module")
def data():
    return R.generate(num_comments=400, num_authors=30, num_subs=6,
                      seed=4)


@pytest.fixture(scope="module")
def tables(data):
    return RC.columnarize(*data)


def test_batch_features_match_scalar_path(data, tables):
    comments, _, _ = data
    got = np.asarray(RC.batch_features(tables["comments"]))
    want = np.stack([R.comment_features(c) for c in comments])
    assert got.shape == (len(comments), R.feature_dim())
    # int-exact features are exact; float32 day arithmetic ~1e-3
    np.testing.assert_allclose(got, want, atol=2e-3)


def test_three_way_join_matches_host(data, tables):
    comments, authors, subs = data
    joined, feats = RC.three_way_join(tables)
    valid = np.asarray(joined.mask())
    assert valid.all()  # every comment references a real author/sub
    karma = {a.author_id: a.karma for a in authors}
    subscribers = {s.id: s.subscribers for s in subs}
    got_k = np.asarray(joined["karma"])
    got_s = np.asarray(joined["subscribers"])
    aid = np.asarray(joined["author_id"])
    sid = np.asarray(joined["sub_id"])
    for i, c in enumerate(comments):
        assert got_k[i] == karma[aid[i]]
        assert got_s[i] == subscribers[subs[sid[i]].id]
        assert subs[sid[i]].id == c.subreddit_id


def test_label_propagation_matches_host_join(data, tables):
    comments, _, _ = data
    prop = np.asarray(RC.propagate_labels(tables["comments"]))
    # host oracle: set of authors with a positive comment
    pos_authors = {c.author for c in comments if c.label == 1}
    want = np.array([1 if c.author in pos_authors else 0
                     for c in comments], np.int32)
    np.testing.assert_array_equal(prop, want)


def test_author_counts_and_partition_grid(data, tables):
    comments, _, _ = data
    counts = np.asarray(RC.author_comment_counts(tables["comments"]))
    from collections import Counter

    want = Counter(np.asarray(tables["comments"]["author_id"]).tolist())
    for a, n in want.items():
        assert counts[a] == n
    grid = np.asarray(RC.label_partition_counts(tables["comments"]))
    assert grid.sum() == len(comments)
    w = Counter((c.label, c.index % 11) for c in comments)
    for (lab, part), n in w.items():
        assert grid[lab, part] == n


@pytest.mark.parametrize("force", ["broadcast", "partition"])
def test_sharded_three_way_matches_local(data, tables, force,
                                         monkeypatch):
    from netsdb_tpu.relational import planner as PLN

    monkeypatch.setattr(PLN, "plan_distribution",
                        lambda *a, **k: PLN.DistPlan(force))
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
    sh = RC.sharded_three_way(tables, mesh)
    local, _ = RC.three_way_join(tables)
    valid = np.asarray(sh.valid)
    got = sorted(zip(np.asarray(sh.cols["index"])[valid].tolist(),
                     np.asarray(sh.cols["karma"])[valid].tolist(),
                     np.asarray(sh.cols["subscribers"])[valid].tolist()))
    lv = np.asarray(local.mask())
    want = sorted(zip(np.asarray(local["index"])[lv].tolist(),
                      np.asarray(local["karma"])[lv].tolist(),
                      np.asarray(local["subscribers"])[lv].tolist()))
    assert got == want

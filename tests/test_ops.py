"""Golden numeric tests for the op layer vs NumPy (SURVEY §4: replaces the
reference's eyeball-the-console oracle with real assertions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from netsdb_tpu.core.blocked import BlockedTensor
from netsdb_tpu.ops import conv as conv_ops
from netsdb_tpu.ops import embedding as emb_ops
from netsdb_tpu.ops import linalg as la
from netsdb_tpu.ops import lstm as lstm_ops
from netsdb_tpu.ops import nn as nn_ops
from netsdb_tpu.ops.matmul import gram, matmul, matmul_t, t_matmul

RNG = np.random.default_rng(42)


def bt(x, block):
    return BlockedTensor.from_dense(np.asarray(x, np.float32), block)


def dense(t):
    return np.asarray(t.to_dense())


class TestMatmul:
    def test_matmul_exact_blocks(self):
        a = RNG.standard_normal((8, 6)).astype(np.float32)
        b = RNG.standard_normal((6, 10)).astype(np.float32)
        out = matmul(bt(a, (4, 3)), bt(b, (3, 5)))
        np.testing.assert_allclose(dense(out), a @ b, rtol=1e-5)
        assert out.meta.block_shape == (4, 5)

    def test_matmul_ragged_blocks(self):
        a = RNG.standard_normal((7, 5)).astype(np.float32)
        b = RNG.standard_normal((5, 9)).astype(np.float32)
        out = matmul(bt(a, (4, 4)), bt(b, (4, 4)))
        np.testing.assert_allclose(dense(out), a @ b, rtol=1e-5)
        # padded margin stays zero
        assert np.abs(np.asarray(out.data)[7:, :]).sum() == 0

    def test_matmul_mismatched_contraction_blocking(self):
        a = RNG.standard_normal((6, 7)).astype(np.float32)
        b = RNG.standard_normal((7, 6)).astype(np.float32)
        out = matmul(bt(a, (4, 3)), bt(b, (5, 4)))  # pads 7→9 vs 7→10
        np.testing.assert_allclose(dense(out), a @ b, rtol=1e-5)

    def test_matmul_t_and_t_matmul(self):
        a = RNG.standard_normal((7, 5)).astype(np.float32)
        b = RNG.standard_normal((9, 5)).astype(np.float32)
        np.testing.assert_allclose(dense(matmul_t(bt(a, (4, 4)), bt(b, (4, 4)))),
                                   a @ b.T, rtol=1e-5)
        c = RNG.standard_normal((5, 7)).astype(np.float32)
        d = RNG.standard_normal((5, 9)).astype(np.float32)
        np.testing.assert_allclose(dense(t_matmul(bt(c, (4, 4)), bt(d, (4, 4)))),
                                   c.T @ d, rtol=1e-5)

    def test_gram(self):
        x = RNG.standard_normal((20, 6)).astype(np.float32)
        np.testing.assert_allclose(dense(gram(bt(x, (8, 4)))), x.T @ x, rtol=1e-4)

    def test_shape_mismatch_raises(self):
        with pytest.raises(ValueError):
            matmul(bt(np.ones((2, 3)), (2, 2)), bt(np.ones((4, 2)), (2, 2)))


class TestNN:
    def test_bias_relu(self):
        x = RNG.standard_normal((7, 5)).astype(np.float32)
        b = RNG.standard_normal((7,)).astype(np.float32)
        out = nn_ops.bias_relu(bt(x, (4, 4)), bt(b.reshape(7, 1), (4, 1)))
        np.testing.assert_allclose(dense(out), np.maximum(x + b[:, None], 0),
                                   rtol=1e-6)

    def test_bias_sigmoid_margin_zero(self):
        x = RNG.standard_normal((7, 5)).astype(np.float32)
        b = np.zeros((7, 1), np.float32)
        out = nn_ops.bias_sigmoid(bt(x, (4, 4)), bt(b, (4, 1)))
        np.testing.assert_allclose(dense(out), 1 / (1 + np.exp(-x)), rtol=1e-5)
        raw = np.asarray(out.data)
        assert raw[7:, :].sum() == 0 and raw[:, 5:].sum() == 0

    def test_row_sum_col_sum(self):
        x = RNG.standard_normal((7, 5)).astype(np.float32)
        np.testing.assert_allclose(dense(nn_ops.row_sum(bt(x, (4, 4)))),
                                   x.sum(1, keepdims=True), rtol=1e-5)
        np.testing.assert_allclose(dense(nn_ops.col_sum(bt(x, (4, 4)))),
                                   x.sum(0, keepdims=True), rtol=1e-5)

    def test_softmax_masked(self):
        x = RNG.standard_normal((7, 5)).astype(np.float32)
        out = nn_ops.softmax(bt(x, (4, 4)), axis=0)
        expect = np.exp(x) / np.exp(x).sum(0, keepdims=True)
        np.testing.assert_allclose(dense(out), expect, rtol=1e-5)
        # columns sum to 1 over the LOGICAL extent only
        np.testing.assert_allclose(dense(out).sum(0), np.ones(5), rtol=1e-5)

    def test_ff_output_layer_matches_softmax_of_biased(self):
        y = RNG.standard_normal((6, 5)).astype(np.float32)
        b = RNG.standard_normal((6, 1)).astype(np.float32)
        out = nn_ops.ff_output_layer(bt(y, (4, 4)), bt(b, (4, 1)), axis=0)
        z = y + b
        expect = np.exp(z) / np.exp(z).sum(0, keepdims=True)
        np.testing.assert_allclose(dense(out), expect, rtol=1e-5)

    def test_dropout_scales(self):
        x = np.ones((8, 8), np.float32)
        b = np.zeros((8, 1), np.float32)
        out = nn_ops.bias_relu(bt(x, (4, 4)), bt(b, (4, 1)), dropout_rate=0.5,
                               key=jax.random.key(0))
        vals = dense(out)
        assert set(np.unique(vals)).issubset({0.0, 2.0})


class TestLinalg:
    x = RNG.standard_normal((7, 5)).astype(np.float32)
    y = RNG.standard_normal((7, 5)).astype(np.float32)

    def test_elementwise(self):
        a, b = bt(self.x, (4, 4)), bt(self.y, (4, 4))
        np.testing.assert_allclose(dense(la.add(a, b)), self.x + self.y, rtol=1e-6)
        np.testing.assert_allclose(dense(la.subtract(a, b)), self.x - self.y,
                                   rtol=1e-6)
        np.testing.assert_allclose(dense(la.scale_multiply(a, b)),
                                   self.x * self.y, rtol=1e-6)
        np.testing.assert_allclose(dense(la.scalar_multiply(a, 2.5)),
                                   self.x * 2.5, rtol=1e-6)

    def test_transpose(self):
        t = la.transpose(bt(self.x, (4, 4)))
        np.testing.assert_array_equal(dense(t), self.x.T)
        assert t.shape == (5, 7)

    def test_global_reductions_ignore_padding(self):
        # make padding the would-be extremum: all-negative matrix, pad=0
        neg = -np.abs(self.x) - 1
        a = bt(neg, (4, 4))
        assert float(la.max_element(a)) == pytest.approx(neg.max(), rel=1e-6)
        pos = np.abs(self.x) + 1
        assert float(la.min_element(bt(pos, (4, 4)))) == pytest.approx(
            pos.min(), rel=1e-6)

    def test_row_col_reductions(self):
        a = bt(self.x, (4, 4))
        np.testing.assert_allclose(dense(la.row_max(a)),
                                   self.x.max(1, keepdims=True), rtol=1e-6)
        np.testing.assert_allclose(dense(la.row_min(a)),
                                   self.x.min(1, keepdims=True), rtol=1e-6)
        np.testing.assert_allclose(dense(la.col_max(a)),
                                   self.x.max(0, keepdims=True), rtol=1e-6)
        np.testing.assert_allclose(dense(la.col_min(a)),
                                   self.x.min(0, keepdims=True), rtol=1e-6)
        np.testing.assert_allclose(dense(la.col_sum(a)),
                                   self.x.sum(0, keepdims=True), rtol=1e-5)

    def test_duplicate_row_col(self):
        v = bt(self.x[:1, :], (1, 4))
        d = la.duplicate_row(v, 6, 3)
        np.testing.assert_array_equal(dense(d), np.tile(self.x[:1, :], (6, 1)))
        c = bt(self.x[:, :1], (4, 1))
        d2 = la.duplicate_col(c, 6, 3)
        np.testing.assert_array_equal(dense(d2), np.tile(self.x[:, :1], (1, 6)))

    def test_constructors(self):
        np.testing.assert_array_equal(dense(la.identity(5, 2)), np.eye(5))
        assert dense(la.zeros(3, 4, 2, 2)).sum() == 0
        assert dense(la.ones(3, 4, 2, 2)).sum() == 12

    def test_inverse(self):
        m = RNG.standard_normal((6, 6)).astype(np.float32)
        m = m @ m.T + 6 * np.eye(6, dtype=np.float32)  # well-conditioned
        inv = la.inverse(bt(m, (4, 4)))
        np.testing.assert_allclose(dense(inv) @ m, np.eye(6), atol=1e-3)

    def test_dsl_sample03_nn_composition(self):
        # i = min(rowSum(D %*% M * D)), D = X - duplicateRow(t, n, bn)
        X = RNG.standard_normal((10, 4)).astype(np.float32)
        t_vec = RNG.standard_normal((1, 4)).astype(np.float32)
        M = RNG.standard_normal((4, 4)).astype(np.float32)
        D = la.subtract(bt(X, (3, 3)), la.duplicate_row(bt(t_vec, (1, 3)), 10, 3))
        DM = matmul(D, bt(M, (3, 3)))
        prod = la.scale_multiply(DM, D.reblock(DM.meta.block_shape))
        result = float(la.min_element(la.row_sum(prod)))
        d_np = X - t_vec
        expect = ((d_np @ M) * d_np).sum(1).min()
        assert result == pytest.approx(expect, rel=1e-4)


class TestConv:
    def test_direct_matches_im2col(self):
        imgs = RNG.standard_normal((2, 3, 12, 12)).astype(np.float32)
        ker = RNG.standard_normal((4, 3, 3, 3)).astype(np.float32)
        bias = RNG.standard_normal((4,)).astype(np.float32)
        d = conv_ops.conv2d_direct(imgs, ker, bias, (1, 1), "VALID", "relu")
        f = conv_ops.conv2d_im2col(imgs, ker, bias, (1, 1), "VALID", "relu",
                                   block_shape=(16, 16))
        np.testing.assert_allclose(np.asarray(d), np.asarray(f), rtol=1e-4,
                                   atol=1e-5)

    def test_direct_matches_manual_conv(self):
        imgs = RNG.standard_normal((1, 2, 5, 5)).astype(np.float32)
        ker = RNG.standard_normal((3, 2, 2, 2)).astype(np.float32)
        out = np.asarray(conv_ops.conv2d_direct(imgs, ker))
        manual = np.zeros((1, 3, 4, 4), np.float32)
        for o in range(3):
            for y in range(4):
                for x in range(4):
                    manual[0, o, y, x] = (
                        imgs[0, :, y:y + 2, x:x + 2] * ker[o]).sum()
        np.testing.assert_allclose(out, manual, rtol=1e-4, atol=1e-5)

    def test_same_padding_and_stride(self):
        imgs = RNG.standard_normal((2, 3, 8, 8)).astype(np.float32)
        ker = RNG.standard_normal((5, 3, 3, 3)).astype(np.float32)
        d = conv_ops.conv2d_direct(imgs, ker, None, (2, 2), "SAME")
        f = conv_ops.conv2d_im2col(imgs, ker, None, (2, 2), "SAME",
                                   block_shape=(16, 16))
        assert d.shape == (2, 5, 4, 4)
        np.testing.assert_allclose(np.asarray(d), np.asarray(f), rtol=1e-4,
                                   atol=1e-5)


class TestLSTM:
    def _params(self, nin, nh, block):
        def w(shape):
            return bt(RNG.standard_normal(shape) * 0.3, block)

        return lstm_ops.LSTMParams(
            w_i=w((nh, nin)), w_f=w((nh, nin)), w_c=w((nh, nin)), w_o=w((nh, nin)),
            u_i=w((nh, nh)), u_f=w((nh, nh)), u_c=w((nh, nh)), u_o=w((nh, nh)),
            b_i=bt(RNG.standard_normal((nh, 1)), (block[0], 1)),
            b_f=bt(RNG.standard_normal((nh, 1)), (block[0], 1)),
            b_c=bt(RNG.standard_normal((nh, 1)), (block[0], 1)),
            b_o=bt(RNG.standard_normal((nh, 1)), (block[0], 1)),
        )

    def test_cell_vs_numpy(self):
        nin, nh, batch = 5, 7, 3
        p = self._params(nin, nh, (4, 4))
        x = bt(RNG.standard_normal((nin, batch)), (4, 4))
        h = bt(np.zeros((nh, batch)), (4, 4))
        c = bt(np.zeros((nh, batch)), (4, 4))
        h2, c2 = lstm_ops.lstm_cell(p, x, h, c)

        def sig(v):
            return 1 / (1 + np.exp(-v))

        xd, hd = dense(x), dense(h)
        gi = sig(dense(p.w_i) @ xd + dense(p.u_i) @ hd + dense(p.b_i))
        gf = sig(dense(p.w_f) @ xd + dense(p.u_f) @ hd + dense(p.b_f))
        gg = np.tanh(dense(p.w_c) @ xd + dense(p.u_c) @ hd + dense(p.b_c))
        go = sig(dense(p.w_o) @ xd + dense(p.u_o) @ hd + dense(p.b_o))
        c_np = gf * dense(c) + gi * gg
        h_np = go * np.tanh(c_np)
        np.testing.assert_allclose(dense(c2), c_np, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(dense(h2), h_np, rtol=1e-4, atol=1e-5)
        # margin invariant
        assert np.abs(np.asarray(h2.data)[nh:, :]).sum() == 0

    def test_unroll_matches_stepping(self):
        nin, nh, batch, T = 4, 6, 2, 3
        p = self._params(nin, nh, (4, 4))
        h = bt(np.zeros((nh, batch)), (4, 4))
        c = bt(np.zeros((nh, batch)), (4, 4))
        xs_np = RNG.standard_normal((T, nin, batch)).astype(np.float32)
        xs_padded = jnp.stack(
            [bt(xs_np[t], (4, 4)).data for t in range(T)])
        hT, cT, hs = lstm_ops.lstm_unroll(p, xs_padded, h, c)
        h_step, c_step = h, c
        for t in range(T):
            h_step, c_step = lstm_ops.lstm_cell(p, bt(xs_np[t], (4, 4)),
                                                h_step, c_step)
        np.testing.assert_allclose(dense(hT), dense(h_step), rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(dense(cT), dense(c_step), rtol=1e-4,
                                   atol=1e-5)


class TestEmbedding:
    def test_matmul_equals_gather(self):
        vocab, dim, batch = 11, 6, 4
        w = bt(RNG.standard_normal((vocab, dim)), (4, 4))
        ids = np.array([0, 3, 10, 7])
        onehot = bt(np.asarray(emb_ops.one_hot_matrix(jnp.asarray(ids), vocab)),
                    (4, 4))
        via_mm = dense(emb_ops.embedding_matmul(w, onehot))
        via_gather = np.asarray(emb_ops.embedding_lookup(w, jnp.asarray(ids)))
        np.testing.assert_allclose(via_mm, via_gather[:, :dim], rtol=1e-5,
                                   atol=1e-6)

    def test_sparse_combiners(self):
        w = bt(RNG.standard_normal((9, 5)), (4, 4))
        ids = jnp.array([1, 2, 3, 4, 5])
        segs = jnp.array([0, 0, 1, 1, 1])
        table = dense(w)
        out_mean = np.asarray(
            emb_ops.embedding_lookup_sparse(w, ids, segs, 2, "mean"))[:, :5]
        np.testing.assert_allclose(out_mean[0], table[[1, 2]].mean(0), rtol=1e-5)
        np.testing.assert_allclose(out_mean[1], table[[3, 4, 5]].mean(0),
                                   rtol=1e-5)
        out_sum = np.asarray(
            emb_ops.embedding_lookup_sparse(w, ids, segs, 2, "sum"))[:, :5]
        np.testing.assert_allclose(out_sum[1], table[[3, 4, 5]].sum(0), rtol=1e-5)


def test_direct_matches_torch():
    import torch
    import jax.numpy as jnp

    from netsdb_tpu.ops.conv import conv2d_direct

    rng = np.random.default_rng(4)
    x = rng.standard_normal((2, 3, 12, 12)).astype(np.float32)
    w = rng.standard_normal((5, 3, 3, 3)).astype(np.float32)
    ours = np.asarray(conv2d_direct(jnp.asarray(x), jnp.asarray(w)))
    with torch.no_grad():
        ref = torch.conv2d(torch.from_numpy(x), torch.from_numpy(w)).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-4)

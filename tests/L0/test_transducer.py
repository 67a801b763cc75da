"""Transducer joint + loss tests.

Mirrors reference tests contrib/test/transducer/test_transducer_{joint,loss}.py:
the wavefront DP + analytic fused backward are checked against a naive
per-cell implementation (the role transducer_ref.py plays in the reference),
both for values and for gradients (via AD through the naive version).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.transducer import (
    TransducerJoint,
    TransducerLoss,
    transducer_joint,
    transducer_loss,
)

B, T, U, V = 3, 6, 5, 8  # U = max y_len + 1
BLANK = 0


def _case(seed=0):
    key = jax.random.PRNGKey(seed)
    kx, kl = jax.random.split(key)
    x = jax.random.normal(kx, (B, T, U, V), jnp.float32)
    label = jax.random.randint(kl, (B, U - 1), 1, V)  # labels never blank
    f_len = jnp.asarray([T, T - 2, T - 1])
    y_len = jnp.asarray([U - 1, U - 3, U - 2])
    return x, label, f_len, y_len


def _naive_loss(x, label, f_len, y_len, blank):
    """Cell-by-cell alpha DP (the spec the reference encodes in
    transducer_ref.py), differentiable via plain AD. Python loops — tiny
    shapes only."""
    y = jax.nn.log_softmax(x, axis=-1)
    losses = []
    for b in range(x.shape[0]):
        fl, yl = int(f_len[b]), int(y_len[b])
        a = {(0, 0): jnp.asarray(0.0)}
        for t in range(1, fl):
            a[(t, 0)] = a[(t - 1, 0)] + y[b, t - 1, 0, blank]
        for u in range(1, yl + 1):
            a[(0, u)] = a[(0, u - 1)] + y[b, 0, u - 1, label[b, u - 1]]
        for t in range(1, fl):
            for u in range(1, yl + 1):
                a[(t, u)] = jnp.logaddexp(
                    a[(t - 1, u)] + y[b, t - 1, u, blank],
                    a[(t, u - 1)] + y[b, t, u - 1, label[b, u - 1]],
                )
        losses.append(-(a[(fl - 1, yl)] + y[b, fl - 1, yl, blank]))
    return jnp.stack(losses)


class TestTransducerLoss:
    # slow since PR 22: pays for test_tpu_lowering / test_chip_smoke in tier-1
    @pytest.mark.slow
    def test_matches_naive(self):
        x, label, f_len, y_len = _case()
        got = transducer_loss(x, label, f_len, y_len, BLANK)
        want = _naive_loss(x, label, f_len, y_len, BLANK)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.slow  # wavefront-DP grad parity vs AD (ISSUE 2 CI satellite)
    def test_grad_matches_naive_ad(self):
        """The analytic fused-softmax backward (custom_vjp) equals plain AD
        through the naive DP — the check the reference does against
        transducer_ref's hand-written backward."""
        x, label, f_len, y_len = _case(1)
        w = jax.random.normal(jax.random.PRNGKey(5), (B,))  # per-seq weights

        g_fused = jax.grad(
            lambda x: jnp.sum(w * transducer_loss(x, label, f_len, y_len, BLANK))
        )(x)
        g_naive = jax.grad(
            lambda x: jnp.sum(w * _naive_loss(x, label, f_len, y_len, BLANK))
        )(x)
        np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_naive),
                                   rtol=1e-4, atol=1e-5)

    def test_jits_and_bf16(self):
        x, label, f_len, y_len = _case(2)
        f = jax.jit(lambda x: transducer_loss(x, label, f_len, y_len, BLANK))
        out = f(x.astype(jnp.bfloat16))
        assert jnp.all(jnp.isfinite(out))
        g = jax.jit(jax.grad(lambda x: jnp.sum(
            transducer_loss(x, label, f_len, y_len, BLANK))))(x.astype(jnp.bfloat16))
        assert g.dtype == jnp.bfloat16
        assert jnp.all(jnp.isfinite(g.astype(jnp.float32)))

    def test_debug_alpha_beta_consistency(self):
        """alpha[b,t,u] + beta[b,t,u] marginalises to the total path mass:
        at (0,0), beta[0,0] = -loss (reference debug_list contract,
        transducer.py:113-116,142-144)."""
        x, label, f_len, y_len = _case(3)
        dbg = []
        loss_mod = TransducerLoss()
        loss = loss_mod(x, label, f_len, y_len, BLANK, debug_list=dbg)
        alpha, beta = dbg
        np.testing.assert_allclose(np.asarray(-beta[:, 0, 0]), np.asarray(loss),
                                   rtol=1e-6)
        # total mass is the same viewed from either end
        term = alpha[jnp.arange(B), f_len - 1, y_len] + jax.nn.log_softmax(
            x, -1)[jnp.arange(B), f_len - 1, y_len, BLANK]
        np.testing.assert_allclose(np.asarray(term), np.asarray(beta[:, 0, 0]),
                                   rtol=1e-5)

    def test_packed_input_matches_dense(self):
        x, label, f_len, y_len = _case(4)
        g_len = y_len + 1
        batch_offset = jnp.cumsum(f_len * g_len)
        packed_n = int(batch_offset[-1])
        # pack x the way a packed joint would produce it
        valid = (jnp.arange(T)[None, :, None] < f_len[:, None, None]) & (
            jnp.arange(U)[None, None, :] < g_len[:, None, None])
        from apex_tpu.contrib.transducer.transducer import _pack
        x_packed = _pack(x, f_len, g_len, batch_offset, packed_n, valid)

        dense = transducer_loss(x, label, f_len, y_len, BLANK)
        packed = transducer_loss(
            x_packed, label, f_len, y_len, BLANK,
            packed_input=True, batch_offset=batch_offset, max_f_len=T)
        np.testing.assert_allclose(np.asarray(packed), np.asarray(dense),
                                   rtol=1e-5, atol=1e-5)


class TestTransducerJoint:
    def _fg(self, seed=0):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        H = 16
        f = jax.random.normal(k1, (B, T, H))
        g = jax.random.normal(k2, (B, U, H))
        f_len = jnp.asarray([T, T - 2, T - 1])
        g_len = jnp.asarray([U, U - 2, U - 1])
        return f, g, f_len, g_len

    def test_matches_broadcast_add(self):
        f, g, f_len, g_len = self._fg()
        h = transducer_joint(f, g, f_len, g_len)
        want = f[:, :, None, :] + g[:, None, :, :]
        for b in range(B):
            np.testing.assert_allclose(
                np.asarray(h[b, : f_len[b], : g_len[b]]),
                np.asarray(want[b, : f_len[b], : g_len[b]]), rtol=1e-6)
        # don't-care region is zeroed (reference leaves it unwritten)
        assert float(jnp.abs(h[1, f_len[1]:]).max()) == 0.0

    def test_relu_and_grads(self):
        f, g, f_len, g_len = self._fg(1)
        def total(f, g):
            return jnp.sum(transducer_joint(f, g, f_len, g_len, relu=True))
        h = transducer_joint(f, g, f_len, g_len, relu=True)
        assert float(h.min()) >= 0.0
        df, dg = jax.grad(total, argnums=(0, 1))(f, g)
        assert df.shape == f.shape and dg.shape == g.shape
        # grads only flow from valid cells
        assert float(jnp.abs(df[1, f_len[1]:]).max()) == 0.0

    def test_pack_output_matches_dense(self):
        f, g, f_len, g_len = self._fg(2)
        batch_offset = jnp.cumsum(f_len * g_len)
        packed_n = int(batch_offset[-1])
        joint = TransducerJoint(pack_output=True)
        hp = joint(f, g, f_len, g_len, batch_offset=batch_offset,
                   packed_batch=packed_n)
        assert hp.shape == (packed_n, f.shape[-1])
        dense = transducer_joint(f, g, f_len, g_len)
        # batch 1 cells live at offset batch_offset[0]
        row = int(batch_offset[0])
        np.testing.assert_allclose(np.asarray(hp[row]), np.asarray(dense[1, 0, 0]),
                                   rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(hp[row + int(g_len[1])]), np.asarray(dense[1, 1, 0]), rtol=1e-6)

    def test_dropout(self):
        f, g, f_len, g_len = self._fg(3)
        joint = TransducerJoint(dropout=True, dropout_prob=0.5)
        h = joint(f, g, f_len, g_len, dropout_key=jax.random.PRNGKey(0))
        dense = transducer_joint(f, g, f_len, g_len)
        kept = h != 0
        # kept entries are scaled by 1/(1-p)
        np.testing.assert_allclose(
            np.asarray(h[kept]), np.asarray((dense * 2.0)[kept]), rtol=1e-5)
        frac = float(jnp.mean(kept[0, : f_len[0], : g_len[0]].astype(jnp.float32)))
        assert 0.35 < frac < 0.65
        # eval mode: no dropout
        h_eval = joint(f, g, f_len, g_len, training=False)
        np.testing.assert_allclose(np.asarray(h_eval), np.asarray(dense), rtol=1e-6)

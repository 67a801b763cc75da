"""Fused attention tests: flash kernel vs naive reference, ring attention
across the 8-device mesh, contrib MHA modules.

Mirrors reference tests: contrib/test/fmha/test_fmha.py (fused vs py
reference), multihead_attn tests, plus the new long-context tier.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.contrib.multihead_attn import EncdecMultiheadAttn, SelfMultiheadAttn
from apex_tpu.ops.attention import (
    flash_attention,
    flash_attention_qkv,
    ring_attention,
)


def _unpack_qkv(qkv, nh, hn):
    """[b, s, nh*(q|k|v)] interleaved projection layout -> three
    [b, nh, s, hn] tensors (the packed-QKV reference construction)."""
    b, s, _ = qkv.shape
    return tuple(t.transpose(0, 2, 1, 3) for t in jnp.split(
        qkv.reshape(b, s, nh, 3 * hn), 3, axis=-1))


def _naive(q, k, v, causal=False, mask_bias=None, scale=None):
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if mask_bias is not None:
        s = s + mask_bias
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        tri = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(tri, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v)


class TestFlashAttention:
    def test_matches_naive(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 32))
        k = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32))
        v = jax.random.normal(jax.random.PRNGKey(2), (4, 64, 32))
        np.testing.assert_allclose(
            flash_attention(q, k, v), _naive(q, k, v), rtol=1e-4, atol=1e-5)

    def test_causal_matches_naive(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 16))
        np.testing.assert_allclose(
            flash_attention(q, k, v, causal=True), _naive(q, k, v, True),
            rtol=1e-4, atol=1e-5)

    def test_packed_qkv_matches_naive(self):
        # the r5 transpose-free entry point: [b, s, nh*(q|k|v)] in the
        # Megatron interleaved projection layout -> context [b, s, h].
        # On CPU this exercises the fallback route; the packed Pallas
        # kernels are parity-tested against it on hardware.
        b, s, nh, hn = 2, 64, 4, 16
        qkv = jax.random.normal(jax.random.PRNGKey(0), (b, s, nh * 3 * hn))
        ctx = flash_attention_qkv(qkv, nh, causal=True, block=32)
        q, k, v = _unpack_qkv(qkv, nh, hn)
        ref = _naive(q, k, v, causal=True)
        ref = ref.transpose(0, 2, 1, 3).reshape(b, s, nh * hn)
        np.testing.assert_allclose(ctx, ref, rtol=1e-4, atol=1e-5)

        def loss(qkv):
            return jnp.sum(flash_attention_qkv(qkv, nh, causal=True,
                                               block=32) ** 2)

        def loss_ref(qkv):
            q, k, v = _unpack_qkv(qkv, nh, hn)
            return jnp.sum(_naive(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss)(qkv)
        g2 = jax.grad(loss_ref)(qkv)
        np.testing.assert_allclose(g1, g2, rtol=1e-3, atol=1e-4)

    def test_packed_qkv_kernels_interpret_mode(self):
        # CI coverage for the packed Pallas kernels themselves (the
        # public wrapper routes to the fallback off-TPU): drive the
        # fwd + bwd pallas_calls in interpret mode and compare against
        # the fallback math — exercises the per-head lane slicing, the
        # joint dqkv store, and the dense lse arrangement
        from apex_tpu.ops.attention import (
            _flash_qkv_bwd_pallas, _flash_qkv_fwd_pallas)

        b, s, nh, hn = 2, 64, 2, 64  # group=2 at hn=64
        scale = 1.0 / np.sqrt(hn)
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, nh * 3 * hn), jnp.float32)
        dctx = jax.random.normal(jax.random.PRNGKey(1), (b, s, nh * hn),
                                 jnp.float32)
        ctx, lse = _flash_qkv_fwd_pallas(qkv, 0, nh, hn, scale, True,
                                         32, 0.0)
        q, k, v = _unpack_qkv(qkv, nh, hn)
        ref = _naive(q, k, v, causal=True)
        ref = ref.transpose(0, 2, 1, 3).reshape(b, s, nh * hn)
        np.testing.assert_allclose(ctx, ref, rtol=1e-4, atol=1e-5)

        dqkv = _flash_qkv_bwd_pallas(qkv, 0, ctx, lse, dctx, nh, hn,
                                     scale, True, 32, 0.0)

        def loss_ref(qkv):
            q, k, v = _unpack_qkv(qkv, nh, hn)
            out = _naive(q, k, v, causal=True)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, nh * hn)
            return jnp.sum(out * dctx)

        dref = jax.grad(loss_ref)(qkv)
        np.testing.assert_allclose(dqkv, dref, rtol=1e-3, atol=1e-4)

    def test_qkv_packed_gate_uses_caller_dtype(self, monkeypatch):
        # ADVICE r5: the VMEM estimate must price the CALLER's itemsize.
        # At the 350M shape (s=1024, hn=64, block=512) bf16 fits the
        # budget but fp32 does not — with the old hardcoded itemsize of
        # 2, fp32 passed the gate and failed Mosaic allocation on chip
        # instead of routing to the fallback.
        from apex_tpu.ops import attention as attn_mod

        monkeypatch.setattr(attn_mod.jax, "default_backend",
                            lambda: "tpu")
        args = (8, 1024, 16, 64, 512, True, 0.0)
        assert attn_mod._qkv_packed_ok(*args, jnp.bfloat16)
        assert not attn_mod._qkv_packed_ok(*args, jnp.float32)

    def test_qkv_packed_block_autoshrink(self, monkeypatch):
        # the d=128/seq-2048 flagship shape exceeds the budget at the
        # default block of 512 but fits at 256: the selector must shrink
        # rather than silently dropping the flagship to the generic
        # kernels (ISSUE 2 tentpole d).  The 350M shape keeps its
        # measured-best 512, and fp32 at the 350M shape shrinks to 256.
        from apex_tpu.ops import attention as attn_mod

        monkeypatch.setattr(attn_mod.jax, "default_backend",
                            lambda: "tpu")
        pick = attn_mod._qkv_packed_block
        assert pick(4, 2048, 16, 128, 512, True, 0.0, jnp.bfloat16) == 256
        assert pick(8, 1024, 16, 64, 512, True, 0.0, jnp.bfloat16) == 512
        assert pick(8, 1024, 16, 64, 512, True, 0.0, jnp.float32) == 256
        # an unalignable shape yields None (generic path)
        assert pick(8, 1000, 16, 64, 512, True, 0.0, jnp.bfloat16) is None

    def test_packed_qkv_lse_residual_is_logical_size(self):
        # ADVICE r5: the attn_res remat policy used to save the raw
        # [b, n_hg, group, n_b, 8, block] lse slab — an 8x residual from
        # the sublane broadcast.  The fwd rule now slices row 0 before
        # checkpoint_name; the residual must be logical-size (sublane
        # dim 1) and the backward must consume it and still match the
        # reference grads.
        from apex_tpu.ops.attention import (
            _flash_qkv_bwd_rule, _flash_qkv_fwd_rule)

        b, s, nh, hn, block = 2, 64, 2, 64, 32  # group=2 at hn=64
        scale = 1.0 / np.sqrt(hn)
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, nh * 3 * hn), jnp.float32)
        ctx, res = _flash_qkv_fwd_rule(qkv, None, None, 0, nh, hn, scale,
                                       True, block, 0.0)
        lse = res[5]
        n_hg, group, n_b = 1, 2, s // block
        assert lse.shape == (b, n_hg, group, n_b, 1, block), lse.shape

        dctx = jax.random.normal(jax.random.PRNGKey(1), (b, s, nh * hn),
                                 jnp.float32)
        dqkv, _, _, _ = _flash_qkv_bwd_rule(nh, hn, scale, True, block,
                                            0.0, res, dctx)

        def loss_ref(qkv):
            q, k, v = _unpack_qkv(qkv, nh, hn)
            out = _naive(q, k, v, causal=True)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, nh * hn)
            return jnp.sum(out * dctx)

        dref = jax.grad(loss_ref)(qkv)
        np.testing.assert_allclose(dqkv, dref, rtol=1e-3, atol=1e-4)

    def test_bwd_tiles_gate_lane_alignment(self, monkeypatch):
        # ADVICE r5: the unrolled-tiles backward slices lse on the LANE
        # dim at offsets qi = qb*block_q — unaligned for sub-128 blocks
        # with more than one q-block; such shapes must route to the grid
        # fallback, while single-q-block and 128-multiple blocks keep
        # the tiles kernel.
        from apex_tpu.ops import attention as attn_mod

        monkeypatch.setattr(attn_mod.jax, "default_backend",
                            lambda: "tpu")
        sd = lambda sq: jax.ShapeDtypeStruct((4, sq, 64), jnp.bfloat16)
        ok = attn_mod._bwd_tiles_ok
        # block_q=16 with sq=64 -> 4 q-blocks at lane-unaligned offsets
        assert not ok(sd(64), sd(64), None, 16, 16)
        # sq == block_q: single q-block, offset 0 — allowed
        assert ok(sd(64), sd(64), None, 64, 64)
        # 128-multiple block with several q-blocks — allowed
        assert ok(sd(512), sd(512), None, 128, 128)

    def test_causal_sq_longer_than_sk(self):
        # causal cross-attention with sq > sk: the leading q rows attend
        # to nothing (fully masked) — the unrolled-tiles kernels must
        # emit zeros for statically-invisible q-blocks, not crash
        # (r5 review finding)
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 16))
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        ref = _naive(q, k, v, causal=True)
        # rows whose causal window is empty are zero by flash convention
        empty = jnp.arange(64) + (32 - 64) < 0
        ref = jnp.where(empty[None, :, None], 0.0, ref)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
        g = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, block_q=16, block_k=16) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for t in g:
            assert np.isfinite(np.asarray(t)).all()

    def test_4d_and_cross_lengths(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 16, 8))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 32, 8))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 32, 8))
        np.testing.assert_allclose(
            flash_attention(q, k, v), _naive(q, k, v), rtol=1e-4, atol=1e-5)

    def test_additive_mask(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (3, 16, 8))
        k = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 8))
        v = jax.random.normal(jax.random.PRNGKey(2), (3, 16, 8))
        bias = jnp.where(
            jax.random.bernoulli(jax.random.PRNGKey(3), 0.3, (3, 16, 16)),
            -10000.0, 0.0)
        np.testing.assert_allclose(
            flash_attention(q, k, v, mask_bias=bias),
            _naive(q, k, v, mask_bias=bias), rtol=1e-4, atol=1e-5)

    def test_grads_match_naive(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 16))

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        def f_naive(q, k, v):
            return jnp.sum(_naive(q, k, v, True) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)

    def test_grads_with_blocked_bwd(self):
        # force multi-block bwd (block_k < sk)
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 8))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 8))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 8))

        def f(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, block_q=16, block_k=16) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(_naive(q, k, v) ** 2)

        g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)

    def test_pallas_interpret_path_matches(self):
        # exercise the Pallas kernel in interpret mode explicitly
        from apex_tpu.ops.attention import _flash_fwd_pallas
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 128))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 128))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 128))
        o, lse = _flash_fwd_pallas(q, k, v, None, None, None, 0,
                                   1.0 / np.sqrt(128.0), True, 128, 128,
                                   0.0)
        np.testing.assert_allclose(o, _naive(q, k, v, True), rtol=1e-4,
                                   atol=1e-5)
        assert lse.shape == (2, 256)

    @pytest.mark.parametrize("causal,with_mask,with_seg", [
        (False, False, False),
        (True, False, False),
        (False, True, False),
        (False, False, True),
        (True, True, False),
        # per-head mask [bh,...] + shared segments [1,...] together: the
        # batch selectors of the two BlockSpec families must not cross
        (False, True, True),
    ])
    def test_pallas_bwd_interpret_matches(self, causal, with_mask, with_seg):
        """The Pallas dq/dkv kernels (interpret mode) against jax.grad of
        the naive reference — every mask/seg/causal combination."""
        from apex_tpu.ops.attention import (
            _flash_bwd_pallas, _flash_fwd_pallas)
        bh, s, d = 2, 64, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (bh, s, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (bh, s, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (bh, s, d))
        do = jax.random.normal(jax.random.PRNGKey(3), (bh, s, d))
        bias = jnp.where(
            jax.random.bernoulli(jax.random.PRNGKey(4), 0.3, (bh, s, s)),
            -10000.0, 0.0) if with_mask else None
        seg = (jnp.concatenate([jnp.zeros((1, 24), jnp.int32),
                                jnp.ones((1, 40), jnp.int32)], axis=1)
               if with_seg else None)
        scale = 1.0 / np.sqrt(d)
        o, lse = _flash_fwd_pallas(q, k, v, bias, seg, seg, 0, scale,
                                   causal, 16, 16, 0.0)
        dq, dk, dv = _flash_bwd_pallas(q, k, v, bias, seg, seg, 0, o, lse,
                                       do, scale, causal, 16, 16, 0.0)

        def ref(q, k, v):
            s_ = jnp.einsum("bqd,bkd->bqk", q, k) * scale
            if bias is not None:
                s_ = s_ + bias
            if seg is not None:
                s_ = jnp.where(seg[:, :, None] == seg[:, None, :], s_, -1e30)
            if causal:
                tri = jnp.tril(jnp.ones((s, s), bool))
                s_ = jnp.where(tri, s_, -1e30)
            return jnp.sum(jax.nn.softmax(s_, -1) @ v * do)

        gq, gk, gv = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(dq, gq, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(dk, gk, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(dv, gv, rtol=1e-3, atol=1e-4)

    def test_segment_ids_public_api(self):
        """segment_ids masks cross-segment attention — equal to running the
        two segments separately."""
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 8))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 8))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 8))
        seg = jnp.array([0] * 12 + [1] * 20)
        out = flash_attention(q, k, v, segment_ids=seg)
        a = _naive(q[:, :12], k[:, :12], v[:, :12])
        b = _naive(q[:, 12:], k[:, 12:], v[:, 12:])
        np.testing.assert_allclose(out, jnp.concatenate([a, b], axis=1),
                                   rtol=1e-4, atol=1e-5)

    def test_segment_ids_grads(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 8))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 8))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 8))
        seg = jnp.array([0] * 8 + [1] * 16)

        def f(q, k, v):
            return jnp.sum(flash_attention(q, k, v, segment_ids=seg) ** 2)

        def f_ref(q, k, v):
            a = jnp.sum(_naive(q[:, :8], k[:, :8], v[:, :8]) ** 2)
            b = jnp.sum(_naive(q[:, 8:], k[:, 8:], v[:, 8:]) ** 2)
            return a + b

        g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


class TestVarlenFastPath:
    """The r7 varlen fast path (ISSUE 5 tentpole): block-skip index,
    varlen/stream_skip/grid_skip kernels, packed-QKV segment masking,
    and the routing decisions that select them."""

    def _tpu(self, monkeypatch):
        from apex_tpu.ops import attention as attn_mod

        monkeypatch.setattr(attn_mod.jax, "default_backend",
                            lambda: "tpu")
        return attn_mod

    def test_routing_varlen_selects_fast_kernels(self, monkeypatch):
        # L0 routing satellite: varlen/padding shapes now select the
        # fast kernels; gates failing falls back correctly
        attn_mod = self._tpu(monkeypatch)
        sd = lambda s, d=64: jax.ShapeDtypeStruct((8, s, d), jnp.bfloat16)
        r = attn_mod.flash_attention_route(sd(512), segment_ids=True,
                                           block_q=128, block_k=128)
        assert r == {"fwd": "varlen", "bwd": "grid_skip"}
        # no segments: the r5 routes are unchanged
        r = attn_mod.flash_attention_route(sd(512), block_q=128,
                                           block_k=128)
        assert r == {"fwd": "tiles", "bwd": "tiles"}
        # a working set past the whole-sequence VMEM gate: the varlen
        # forward falls back to the grid kernel WITH the skip index
        r = attn_mod.flash_attention_route(sd(16384, 256),
                                           segment_ids=True,
                                           block_q=512, block_k=512)
        assert r["fwd"] == "stream_skip"
        # unalignable shape: everything falls to the XLA path
        r = attn_mod.flash_attention_route(sd(1000), segment_ids=True,
                                           block_q=128, block_k=128)
        assert r == {"fwd": "xla", "bwd": "xla"}

    def test_routing_qkv_packed_varlen(self, monkeypatch):
        attn_mod = self._tpu(monkeypatch)
        route = attn_mod.flash_attention_qkv_route
        assert route(8, 512, 16, 64, has_segments=True) == "packed_varlen"
        assert route(8, 512, 16, 64) == "packed"
        # gate failure (unaligned seq) falls back to the generic path
        assert route(8, 1000, 16, 64, has_segments=True) == "generic"

    def test_qkv_gate_prices_caller_dtype(self, monkeypatch):
        """ADVICE r5 #1 / ROADMAP maintenance regression pin: the
        packed-QKV VMEM gate must price the CALLER's qkv itemsize, not
        a hardcoded bf16.  At the flagship d=128/s=2048 shape the
        resident set is ~11 MB in bf16 (fits the 12 MB budget at the
        auto-shrunk block 256) and ~2x that in fp32 — a near-budget
        fp32 qkv must route to the generic fallback instead of passing
        the gate and failing Mosaic VMEM allocation."""
        import jax.numpy as jnp

        attn_mod = self._tpu(monkeypatch)
        gate = attn_mod._qkv_packed_ok
        assert gate(8, 2048, 16, 128, 256, True, 0.0, jnp.bfloat16)
        assert not gate(8, 2048, 16, 128, 256, True, 0.0, jnp.float32)
        route = attn_mod.flash_attention_qkv_route
        assert route(8, 2048, 16, 128, block=256,
                     dtype=jnp.bfloat16) == "packed"
        assert route(8, 2048, 16, 128, block=256,
                     dtype=jnp.float32) == "generic"
        # the public wrapper threads the real qkv.dtype into the gate:
        # tracing an fp32 qkv takes the generic (transposed) path, whose
        # jaxpr transposes the heads — the packed kernel's does not
        qkv32 = jnp.zeros((1, 2048, 16 * 3 * 128), jnp.float32)
        jaxpr = str(jax.make_jaxpr(
            lambda x: attn_mod.flash_attention_qkv(x, 16, block=256))(
                qkv32))
        assert "transpose" in jaxpr

    def test_routing_override_forces_generic(self, monkeypatch):
        attn_mod = self._tpu(monkeypatch)
        sd = jax.ShapeDtypeStruct((8, 512, 64), jnp.bfloat16)
        with attn_mod.routing_override(fwd="stream", bwd="grid"):
            r = attn_mod.flash_attention_route(sd, segment_ids=True,
                                               block_q=128, block_k=128)
        assert r == {"fwd": "stream", "bwd": "grid"}
        # override does not leak
        r = attn_mod.flash_attention_route(sd, segment_ids=True,
                                           block_q=128, block_k=128)
        assert r["fwd"] == "varlen"

    def test_segment_block_bounds_conservative(self):
        """The skip index may keep a dead tile but must NEVER skip a
        live one — checked against brute-force equality on random ids,
        plus tightness on the two shapes that matter (ascending packing,
        descending key-padding)."""
        from apex_tpu.ops.attention import _segment_block_bounds

        rng = np.random.RandomState(0)
        for _ in range(5):
            seg_q = jnp.asarray(rng.randint(0, 4, (2, 64)), jnp.int32)
            seg_k = jnp.asarray(rng.randint(0, 4, (2, 64)), jnp.int32)
            lq, lk = _segment_block_bounds(seg_q, seg_k, 16, 8)
            live = (np.asarray(seg_q)[:, :, None]
                    == np.asarray(seg_k)[:, None, :])
            for b in range(2):
                for qb in range(4):
                    rows = slice(qb * 16, qb * 16 + 16)
                    for kb in range(8):
                        cols = slice(kb * 8, kb * 8 + 8)
                        if live[b, rows, cols].any():
                            lo, hi = np.asarray(lq)[b, qb]
                            assert lo <= kb < hi, (b, qb, kb, lo, hi)
        # tightness on a padding tail: all-pad k-blocks are outside
        seg_q = jnp.ones((1, 64), jnp.int32)
        seg_k = jnp.asarray([[1] * 40 + [0] * 24], jnp.int32)
        lq, _ = _segment_block_bounds(seg_q, seg_k, 16, 8)
        assert np.asarray(lq)[0, 0].tolist() == [0, 5]  # 40/8 = 5 blocks

    @pytest.mark.parametrize("route", ["varlen", "stream_skip"])
    def test_varlen_fwd_kernels_interpret_match(self, route):
        from apex_tpu.ops.attention import _flash_fwd_pallas

        bh, s, d = 2, 64, 16
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (bh, s, d))
                   for i in range(3))
        seg = jnp.asarray([[0] * 24 + [1] * 24 + [2] * 16,
                           [0] * 40 + [1] * 8 + [2] * 16], jnp.int32)
        scale = 1.0 / np.sqrt(d)
        o, lse = _flash_fwd_pallas(q, k, v, None, seg, seg, 0, scale,
                                   False, 16, 16, 0.0, route=route)
        ref = _naive_seg(q, k, v, seg, scale)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        assert lse.shape == (bh, s)

    def test_varlen_grid_skip_bwd_interpret_matches(self):
        from apex_tpu.ops.attention import (_flash_bwd_pallas,
                                            _flash_fwd_pallas)

        bh, s, d = 2, 64, 16
        q, k, v, do = (jax.random.normal(jax.random.PRNGKey(i),
                                         (bh, s, d)) for i in range(4))
        seg = jnp.asarray([[0] * 24 + [1] * 40], jnp.int32)
        scale = 1.0 / np.sqrt(d)
        o, lse = _flash_fwd_pallas(q, k, v, None, seg, seg, 0, scale,
                                   False, 16, 16, 0.0, route="varlen")
        dq, dk, dv = _flash_bwd_pallas(q, k, v, None, seg, seg, 0, o,
                                       lse, do, scale, False, 16, 16,
                                       0.0, route="grid_skip")
        gq, gk, gv = jax.grad(
            lambda q, k, v: jnp.sum(_naive_seg(q, k, v, seg, scale) * do),
            argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(dq, gq, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(dk, gk, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(dv, gv, rtol=1e-3, atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_packed_qkv_varlen_interpret_matches(self, causal):
        """In-kernel segment masking on the packed-QKV kernels (the
        tentpole's fast tile schedule) vs the generic reference —
        interpret-mode parity, fwd and bwd, incl. the dynamic
        block-skip carry loop."""
        from apex_tpu.ops.attention import (_flash_qkv_bwd_pallas,
                                            _flash_qkv_fwd_pallas)

        b, s, nh, hn = 2, 64, 2, 64  # group=2 at hn=64
        scale = 1.0 / np.sqrt(hn)
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, nh * 3 * hn), jnp.float32)
        seg = jnp.asarray([[0] * 24 + [1] * 40,
                           [0] * 40 + [7] * 24], jnp.int32)

        def ref(qkv):
            q, k, v = _unpack_qkv(qkv, nh, hn)
            s_ = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            s_ = jnp.where(seg[:, None, :, None] == seg[:, None, None, :],
                           s_, -1e30)
            if causal:
                tri = jnp.tril(jnp.ones((s, s), bool))
                s_ = jnp.where(tri, s_, -1e30)
            out = jax.nn.softmax(s_, -1) @ v
            return out.transpose(0, 2, 1, 3).reshape(b, s, nh * hn)

        ctx, lse = _flash_qkv_fwd_pallas(qkv, 0, nh, hn, scale, causal,
                                         16, 0.0, seg_q=seg, seg_k=seg)
        np.testing.assert_allclose(ctx, ref(qkv), rtol=1e-4, atol=1e-5)
        dctx = jax.random.normal(jax.random.PRNGKey(1), ctx.shape)
        dqkv = _flash_qkv_bwd_pallas(qkv, 0, ctx, lse, dctx, nh, hn,
                                     scale, causal, 16, 0.0,
                                     seg_q=seg, seg_k=seg)
        dref = jax.grad(lambda x: jnp.sum(ref(x) * dctx))(qkv)
        np.testing.assert_allclose(dqkv, dref, rtol=1e-3, atol=1e-4)

    def test_qkv_wrapper_segments_fallback_matches(self):
        """Public flash_attention_qkv(segment_ids=...) — off-TPU this
        takes the generic fallback with identical math; grads flow."""
        b, s, nh, hn = 2, 32, 2, 8
        qkv = jax.random.normal(jax.random.PRNGKey(0), (b, s, nh * 3 * hn))
        seg = jnp.asarray([[0] * 12 + [1] * 20, [0] * 20 + [1] * 12],
                          jnp.int32)

        def ref(qkv):
            q, k, v = _unpack_qkv(qkv, nh, hn)
            s_ = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hn)
            s_ = jnp.where(seg[:, None, :, None] == seg[:, None, None, :],
                           s_, -1e30)
            out = jax.nn.softmax(s_, -1) @ v
            return out.transpose(0, 2, 1, 3).reshape(b, s, nh * hn)

        ctx = flash_attention_qkv(qkv, nh, causal=False, block=16,
                                  segment_ids=seg)
        np.testing.assert_allclose(ctx, ref(qkv), rtol=1e-4, atol=1e-5)
        g = jax.grad(lambda x: jnp.sum(flash_attention_qkv(
            x, nh, causal=False, block=16, segment_ids=seg) ** 2))(qkv)
        gr = jax.grad(lambda x: jnp.sum(ref(x) ** 2))(qkv)
        np.testing.assert_allclose(g, gr, rtol=1e-3, atol=1e-4)

    def test_varlen_fully_masked_block_emits_zeros(self):
        """A q-block whose segment has no matching keys anywhere gets a
        zero-trip skip loop: zeros out, -inf lse, finite (zero) grads —
        the l == 0 convention of every other kernel."""
        from apex_tpu.ops.attention import (_flash_bwd_pallas,
                                            _flash_fwd_pallas)

        bh, s, d = 1, 48, 16
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (bh, s, d))
                   for i in range(3))
        seg_q = jnp.asarray([[0] * 16 + [9] * 16 + [1] * 16], jnp.int32)
        seg_k = jnp.asarray([[0] * 16 + [2] * 16 + [1] * 16], jnp.int32)
        scale = 1.0 / np.sqrt(d)
        o, lse = _flash_fwd_pallas(q, k, v, None, seg_q, seg_k, 0,
                                   scale, False, 16, 16, 0.0,
                                   route="varlen")
        assert np.allclose(np.asarray(o)[0, 16:32], 0.0)
        assert np.all(np.asarray(lse)[0, 16:32] < -1e29)
        do = jnp.ones_like(q)
        dq, dk, dv = _flash_bwd_pallas(q, k, v, None, seg_q, seg_k, 0,
                                       o, lse, do, scale, False, 16, 16,
                                       0.0, route="grid_skip")
        for t in (dq, dk, dv):
            assert np.isfinite(np.asarray(t)).all()
        assert np.allclose(np.asarray(dq)[0, 16:32], 0.0)


def _naive_seg(q, k, v, seg, scale):
    s_ = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    s_ = jnp.where(seg[:, :, None] == seg[:, None, :], s_, -1e30)
    p = jax.nn.softmax(s_, -1)
    # rows with no visible key are zero under the flash l==0 convention
    dead = (seg[:, :, None] == seg[:, None, :]).sum(-1) == 0
    return jnp.where(dead[..., None], 0.0, p @ v)


class TestVarlen:
    """flash_attention_varlen — the reference FMHA's BERT-style packed
    interface (contrib/fmha/fmha.py:33-75), mapped to segment-id masking."""

    def test_matches_per_sequence(self):
        h, d = 2, 8
        lens = [5, 11, 8]
        total = 32  # includes 8 padding tokens
        cu = jnp.array([0, 5, 16, 24], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(0), (total, h, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (total, h, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (total, h, d))
        from apex_tpu.ops.attention import flash_attention_varlen
        out = flash_attention_varlen(q, k, v, cu)
        assert out.shape == (total, h, d)
        start = 0
        for n in lens:
            sl = slice(start, start + n)
            ref = _naive(q[sl].transpose(1, 0, 2), k[sl].transpose(1, 0, 2),
                         v[sl].transpose(1, 0, 2))
            np.testing.assert_allclose(out[sl].transpose(1, 0, 2), ref,
                                       rtol=1e-4, atol=1e-5)
            start += n

    def test_causal_varlen(self):
        h, d = 1, 8
        cu = jnp.array([0, 6, 16], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(0), (16, h, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (16, h, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (16, h, d))
        from apex_tpu.ops.attention import flash_attention_varlen
        out = flash_attention_varlen(q, k, v, cu, causal=True)
        for sl in (slice(0, 6), slice(6, 16)):
            ref = _naive(q[sl].transpose(1, 0, 2), k[sl].transpose(1, 0, 2),
                         v[sl].transpose(1, 0, 2), causal=True)
            np.testing.assert_allclose(out[sl].transpose(1, 0, 2), ref,
                                       rtol=1e-4, atol=1e-5)


class TestRingAttention:
    @pytest.fixture(scope="class")
    def mesh(self):
        return Mesh(np.array(jax.devices()[:8]), ("sp",))

    def test_matches_full_attention(self, mesh):
        # sequence 64 sharded 8 ways
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 16))

        def run(q, k, v):
            return ring_attention(q, k, v, "sp")

        out = shard_map(run, mesh=mesh,
                        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
                        out_specs=P(None, "sp"), check_rep=False)(q, k, v)
        np.testing.assert_allclose(out, _naive(q, k, v), rtol=1e-4, atol=1e-5)

    def test_causal_matches_full(self, mesh):
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 16))

        def run(q, k, v):
            return ring_attention(q, k, v, "sp", causal=True)

        out = shard_map(run, mesh=mesh,
                        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
                        out_specs=P(None, "sp"), check_rep=False)(q, k, v)
        np.testing.assert_allclose(out, _naive(q, k, v, causal=True),
                                   rtol=1e-4, atol=1e-5)

    def test_grads_flow_through_ring(self, mesh):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 8))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 8))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 8))

        def loss(q, k, v):
            def run(q, k, v):
                o = ring_attention(q, k, v, "sp", causal=True)
                return jax.lax.psum(jnp.sum(o ** 2), "sp")
            return shard_map(run, mesh=mesh,
                             in_specs=(P(None, "sp"),) * 3,
                             out_specs=P(), check_rep=False)(q, k, v)

        def loss_ref(q, k, v):
            return jnp.sum(_naive(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)

    def test_backward_memory_flat_in_world_size(self):
        """The custom-VJP second ring pass must not save rotated K/V blocks:
        per-device temp memory of the compiled grad stays flat as the ring
        grows 2 → 8 devices at constant local shard (VERDICT r1 weak #4)."""

        def temp_bytes(n_dev, s_local):
            m = Mesh(np.array(jax.devices()[:n_dev]), ("sp",))
            qg = jnp.zeros((2, s_local * n_dev, 16))

            def loss(q, k, v):
                def run(q, k, v):
                    o = ring_attention(q, k, v, "sp", causal=True)
                    return jax.lax.psum(jnp.sum(o ** 2), "sp")
                return shard_map(run, mesh=m, in_specs=(P(None, "sp"),) * 3,
                                 out_specs=P(), check_rep=False)(q, k, v)

            c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                qg, qg, qg).compile()
            stats = c.memory_analysis()
            assert stats is not None and stats.temp_size_in_bytes > 0
            return stats.temp_size_in_bytes

        b2 = temp_bytes(2, 32)
        b8 = temp_bytes(8, 32)
        assert b8 < b2 * 2.0, (b2, b8)


class TestMultiheadAttnModules:
    def test_self_attn_matches_naive(self):
        m = SelfMultiheadAttn(32, 4, bias=True)
        p = m.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (10, 2, 32))
        out = m.apply(p, x, is_training=False)
        # reference: same projections + standard attention
        qkv = x @ p["in_proj_weight"].T + p["in_proj_bias"]
        q, k, v = jnp.split(qkv, 3, -1)

        def heads(t):
            return t.reshape(10, 2 * 4, 8).transpose(1, 0, 2)

        ctx = _naive(heads(q), heads(k), heads(v), scale=8 ** -0.5)
        ref = (ctx.transpose(1, 0, 2).reshape(10, 2, 32)
               @ p["out_proj_weight"].T + p["out_proj_bias"])
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_self_attn_padding_mask(self):
        m = SelfMultiheadAttn(16, 2)
        p = m.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 16))
        mask = jnp.array([[False] * 4 + [True] * 2,
                          [False] * 6])
        out = m.apply(p, x, key_padding_mask=mask, is_training=False)
        assert out.shape == (6, 2, 16)
        assert bool(jnp.all(jnp.isfinite(out)))

    def test_norm_add_variant(self):
        m = SelfMultiheadAttn(16, 2, include_norm_add=True)
        p = m.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 16))
        out = m.apply(p, x, is_training=False)
        # residual path present: zero attention weights would return x
        assert out.shape == x.shape

    def test_encdec(self):
        m = EncdecMultiheadAttn(16, 2, bias=True)
        p = m.init(jax.random.PRNGKey(0))
        dec = jax.random.normal(jax.random.PRNGKey(1), (5, 2, 16))
        enc = jax.random.normal(jax.random.PRNGKey(2), (9, 2, 16))
        out = m.apply(p, dec, enc, is_training=False)
        assert out.shape == (5, 2, 16)

    def test_dropout_changes_output(self):
        m = SelfMultiheadAttn(16, 2, dropout=0.5)
        p = m.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 16))
        o1 = m.apply(p, x, is_training=True,
                     dropout_rng=jax.random.PRNGKey(10))
        o2 = m.apply(p, x, is_training=False)
        assert not np.allclose(o1, o2)


def test_trainable_mask_bias_gets_gradient():
    """mask_is_constant=False must produce a real (nonzero) bias gradient
    (ADVICE r2: the default path silently returns zeros for it)."""
    from apex_tpu.ops.attention import flash_attention

    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(k1, (2, 16, 8))
    k = jax.random.normal(k2, (2, 16, 8))
    v = jax.random.normal(k3, (2, 16, 8))
    bias = jax.random.normal(k4, (1, 16, 16)) * 0.1

    def loss(b):
        return jnp.sum(flash_attention(q, k, v, mask_bias=b,
                                       mask_is_constant=False) ** 2)

    g = jax.grad(loss)(bias)
    assert jnp.abs(g).max() > 0
    # and the default (constant-mask) path still returns zeros, documented
    def loss_const(b):
        return jnp.sum(flash_attention(q, k, v, mask_bias=b) ** 2)
    g0 = jax.grad(loss_const)(bias)
    assert jnp.abs(g0).max() == 0


class TestKernelDropout:
    """In-kernel attention dropout (reference FMHA's Philox in-kernel
    dropout): counter-based hash masks, bit-identical across the Pallas
    tilings and the XLA fallback, replayed (not stored) in backward."""

    def _qkv(self, bh=4, s=32, d=8):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        return [jax.random.normal(k, (bh, s, d)) for k in ks]

    def test_keep_rate_statistics(self):
        from apex_tpu.ops.attention import _dropout_keep_full

        keep = _dropout_keep_full(jnp.int32(123), 8, 64, 64, 0.3)
        assert abs(float(keep.mean()) - 0.7) < 0.01

    def test_deterministic_and_seed_sensitivity(self):
        from apex_tpu.ops.attention import flash_attention

        q, k, v = self._qkv()
        a = flash_attention(q, k, v, dropout_rate=0.2, dropout_seed=5)
        b = flash_attention(q, k, v, dropout_rate=0.2, dropout_seed=5)
        c = flash_attention(q, k, v, dropout_rate=0.2, dropout_seed=6)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_matches_dense_reference_with_same_mask(self):
        from apex_tpu.ops.attention import (_dropout_keep_full,
                                            flash_attention)

        q, k, v = self._qkv()
        rate, seed = 0.25, 42
        out = flash_attention(q, k, v, causal=True, dropout_rate=rate,
                              dropout_seed=seed)
        s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
        tri = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(tri, s, -1e30)
        p = jax.nn.softmax(s, -1)
        keep = _dropout_keep_full(jnp.int32(seed), *p.shape, rate)
        pd = jnp.where(keep, p, 0.0) / (1 - rate)
        ref = jnp.einsum("bqk,bkd->bqd", pd, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_grads_match_dense_reference(self):
        from apex_tpu.ops.attention import (_dropout_keep_full,
                                            flash_attention)

        q, k, v = self._qkv(bh=2, s=16, d=8)
        rate, seed = 0.3, 9

        def loss_fused(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, dropout_rate=rate,
                dropout_seed=seed) ** 2)

        def loss_ref(q, k, v):
            s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
            tri = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
            s = jnp.where(tri, s, -1e30)
            p = jax.nn.softmax(s, -1)
            keep = _dropout_keep_full(jnp.int32(seed), *p.shape, rate)
            pd = jnp.where(keep, p, 0.0) / (1 - rate)
            return jnp.sum(jnp.einsum("bqk,bkd->bqd", pd, v) ** 2)

    # the custom-vjp backward replays the mask; AD of the dense
    # reference materialises it — gradients must agree
        gf = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)

    def test_rate_without_seed_raises(self):
        from apex_tpu.ops.attention import flash_attention

        q, k, v = self._qkv()
        with pytest.raises(ValueError):
            flash_attention(q, k, v, dropout_rate=0.1)


def test_pallas_dropout_kernels_interpret_match_dense():
    """The Pallas fwd + dq/dkv kernels WITH in-kernel dropout (interpret
    mode) against the dense masked reference using the same hash mask —
    different tile sizes than the mask helper, proving global-coordinate
    replay."""
    from apex_tpu.ops.attention import (
        _dropout_keep_full, _flash_bwd_pallas, _flash_fwd_pallas)

    bh, s, d = 2, 64, 16
    rate, seed = 0.3, 1234
    q = jax.random.normal(jax.random.PRNGKey(0), (bh, s, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, s, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, s, d))
    do = jax.random.normal(jax.random.PRNGKey(3), (bh, s, d))
    scale = 1.0 / np.sqrt(d)
    o, lse = _flash_fwd_pallas(q, k, v, None, None, None, seed, scale,
                               True, 16, 32, rate)
    dq, dk, dv = _flash_bwd_pallas(q, k, v, None, None, None, seed, o,
                                   lse, do, scale, True, 32, 16, rate)

    def ref(q, k, v):
        s_ = jnp.einsum("bqd,bkd->bqk", q, k) * scale
        tri = jnp.tril(jnp.ones((s, s), bool))
        s_ = jnp.where(tri, s_, -1e30)
        p = jax.nn.softmax(s_, -1)
        keep = _dropout_keep_full(jnp.int32(seed), bh, s, s, rate)
        pd = jnp.where(keep, p, 0.0) / (1 - rate)
        return jnp.einsum("bqk,bkd->bqd", pd, v)

    np.testing.assert_allclose(np.asarray(o), np.asarray(ref(q, k, v)),
                               rtol=1e-4, atol=1e-5)
    rq, rk, rv = jax.grad(
        lambda q, k, v: jnp.sum(ref(q, k, v) * do), argnums=(0, 1, 2))(
        q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv),
                               rtol=1e-4, atol=1e-5)


def test_bwd_vmem_guard_falls_back_for_large_shapes():
    """Shapes whose fused-backward resident set exceeds the core VMEM
    budget must route to the XLA blockwise backward (the guard added
    with the one-pass kernel) — and small shapes must not."""
    from apex_tpu.ops.attention import _BWD_VMEM_BUDGET, _pallas_bwd_ok

    class Arr:
        def __init__(self, shape, dtype=jnp.bfloat16):
            self.shape = shape
            self.dtype = jnp.dtype(dtype)

    big = Arr((1, 16384, 256))
    assert not _pallas_bwd_ok(big, big, None, 512, 512)
    # estimate for the big shape really is over budget
    small = Arr((8, 1024, 64))
    # off-TPU _pallas_ok is False; assert only the budget arithmetic by
    # checking the big shape trips even if the backend check passed
    sq, d = big.shape[1], big.shape[2]
    resident_min = 3 * sq * d * 2 + sq * d * 4
    assert resident_min > _BWD_VMEM_BUDGET
    assert small.shape[1] * small.shape[2] * 8 < _BWD_VMEM_BUDGET

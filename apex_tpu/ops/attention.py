"""Fused (flash) attention and ring attention.

TPU-native replacement for the reference's two fused-attention stacks:

* **FMHA** (reference apex/contrib/fmha/fmha.py:33-75, kernels
  apex/contrib/csrc/fmha/ ~5,900 LoC sm80 CUDA): fp16, seqlen ∈
  {128,256,384,512}, head dim 64, BERT-style varlen packing via
  cu_seqlens.
* **fast multihead attn** (reference apex/contrib/multihead_attn/, 8 CUDA
  extensions): self/encdec × {plain, bias, norm-add, additive-mask}
  variants that fuse mask+softmax+dropout and remove transposes.

Here ONE Pallas flash-attention kernel family covers every case — any
sequence length (no 512 cap), any head dim, bf16/fp32, causal or additive
masks, varlen packing via segment ids — with online-softmax accumulation
so the S×S score matrix never materialises in HBM.  Both forward AND
backward are Pallas kernels (flash-attention-2 backward: delta trick,
blockwise recompute of p).  The backward is a fused ONE-PASS kernel:
dq, dk, and dv all come out of a single grid over (batch-head, k-block),
with dq accumulated in persistent fp32 VMEM scratch — each score tile is
recomputed once, not twice.  Off-TPU, or for shapes below the TPU tiling
grain, a blockwise XLA path computes identical math.

Varlen/masked fast path (r7): segment-id and key-padding shapes no
longer drop to the generic grid schedule.  A **block-skip index**
(:func:`_segment_block_bounds`) bounds every kernel's k-loop to the
[lo, hi) block range that can contain a visible (seg_q == seg_k) pair,
so padding tails and cross-segment tiles under packing are *skipped*,
not computed-and-masked; the equality predicate stays fused into the
online-softmax mask for the tiles the range keeps.  Routing is a
named, testable decision (:func:`flash_attention_route`,
:func:`flash_attention_qkv_route`, ``routing_override``).

Mosaic (TPU kernel compiler) rules honored throughout, validated by
compiling on a real chip:

- no sub-ref creation (``.at[0]``) — only loads/stores with explicit
  ``[0, ...]`` indexing, which Mosaic handles with lane padding;
- dynamic slices on the sublane dim only, except the additive-mask lane
  slice which is gated on 128-alignment;
- no ``lax.cond`` in-kernel; causal masking is a flat ``jnp.where``
  (VPU-cheap), with the *trip count* of the k-block loop still shortened
  for causal (the MXU work is halved, like the reference's upper-triang
  kernel).

``mask_bias`` is treated as a constant (non-differentiable), matching the
reference where additive masks encode padding (-10000.0 fills), never
trainable parameters.

Long-context / sequence parallelism (SURVEY.md §5.7 — absent in the
2021 reference, first-class here): :func:`ring_attention` shards the
sequence axis across a mesh axis and rotates K/V blocks with
``lax.ppermute``.  Its backward is a **custom VJP running a second ring
pass** — each (k, v) chunk travels the ring again together with its
(dk, dv) accumulators — so AD never saves the rotated blocks and live
memory is O(s_local), flat in world size.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas import LANE, use_interpret

# needed even in interpret mode: the fused backward's accumulators are
# pltpu.VMEM scratch (the import resolves on every backend)
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _masked_exp(s, m):
    """exp(s - m) with fully-masked rows (m still at _NEG_INF) forced to 0
    so l stays 0 and the l_safe guard yields zeros instead of mean(V)."""
    return jnp.where(m <= _NEG_INF / 2, 0.0, jnp.exp(s - m))


# ---------------------------------------------------------------------------
# In-kernel dropout: counter-based hash RNG (the reference FMHA's design —
# cuRAND Philox keyed by per-element counters, fmha_fprop/dgrad kernels —
# mapped to a murmur3-finalizer hash of (seed, batch-head, global row,
# global col) in plain uint32 jnp ops, so the SAME bits are generated in
# the forward kernel, both backward kernels, and the XLA fallback path,
# on any backend, with zero mask storage.
# ---------------------------------------------------------------------------


def _keep_from_coords(rows, cols, b, seed, rate):
    """keep = hash(seed, b, row, col) >= rate·2³², elementwise uint32."""
    x = (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    x = x ^ (jnp.asarray(seed).astype(jnp.uint32)
             + jnp.asarray(b).astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # round, don't truncate: a tiny positive rate must not silently
    # become a no-op threshold of 0 (ADVICE r3)
    thresh = jnp.uint32(min(round(rate * 2.0 ** 32), 2 ** 32 - 1))
    return x >= thresh  # P[keep] = 1 - rate


def _dropout_keep(seed, b, qi, ki, bq, bk, rate):
    """Boolean keep-mask [bq, bk] for the score tile whose top-left corner
    is global (qi, ki) of batch-head ``b``.  ``seed`` is a traced int32
    scalar; ``rate`` is static.  Coordinates are GLOBAL, so any tiling
    (forward, dq, dkv, or the untiled XLA path) replays the same bits."""
    rows = qi + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return _keep_from_coords(rows, cols, b, seed, rate)


def _dropout_keep_full(seed, bh, sq, sk, rate):
    """[bh, sq, sk] keep-mask, bitwise identical to the tiled kernels'
    masks — the XLA fallback's dropout therefore matches the Pallas path
    exactly on every backend."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bh, sq, sk), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bh, sq, sk), 2)
    b = jax.lax.broadcasted_iota(jnp.int32, (bh, sq, sk), 0)
    return _keep_from_coords(rows, cols, b, seed, rate)


# ---------------------------------------------------------------------------
# Block-skip index (varlen fast path, r7): per q-block, the [lo, hi)
# range of k-blocks that can contain ANY visible (seg_q == seg_k) pair.
# Tiles outside the range — padding tails, cross-segment tiles under
# packing — are never entered by the skip-aware kernels, instead of
# being computed and masked to -inf.  The reference FMHA gets the same
# effect from its cu_seqlens launch geometry (one CUDA block per real
# sequence); on TPU the fixed-shape kernels take the index as a tiny
# int32 operand and shorten their k-loop trip counts with it.
# ---------------------------------------------------------------------------


def _segment_block_bounds(seg_q, seg_k, block_q, block_k):
    """(lohi_q [sbh, n_qb, 2], lohi_k [sbh, n_kb, 2]) int32 block ranges.

    A (q-block, k-block) tile is *possibly live* iff the segment-id
    intervals [min, max] of the two blocks intersect — conservative: a
    tile outside the returned range provably has NO equal (seg_q, seg_k)
    pair (disjoint intervals admit no equality), so skipping it is
    exact; a dead tile *inside* the range is still masked by the fused
    in-kernel predicate.  For the two shapes that matter the cover is
    tight: packed varlen ids are ascending and key-padding ids
    (1=real, 0=pad tail) are descending, so per block the live set IS a
    contiguous range.  ``lohi_k`` is the transposed index (q-block range
    per k-block) the one-pass backward grid consumes."""
    sbh, sq = seg_q.shape
    sk = seg_k.shape[1]
    n_qb, n_kb = sq // block_q, sk // block_k
    q = seg_q.reshape(sbh, n_qb, block_q)
    k = seg_k.reshape(sbh, n_kb, block_k)
    qmin, qmax = q.min(axis=-1), q.max(axis=-1)
    kmin, kmax = k.min(axis=-1), k.max(axis=-1)
    live = ((qmin[:, :, None] <= kmax[:, None, :])
            & (kmin[:, None, :] <= qmax[:, :, None]))  # [sbh, n_qb, n_kb]

    def lohi(m, n):
        any_ = m.any(axis=-1)
        lo = jnp.where(any_, jnp.argmax(m, axis=-1), 0)
        hi = jnp.where(any_, n - jnp.argmax(m[..., ::-1], axis=-1), 0)
        return jnp.stack([lo, hi], axis=-1).astype(jnp.int32)

    return lohi(live, n_kb), lohi(live.swapaxes(1, 2), n_qb)


def _skip_spec_arg(lohi, gridded, n_rows):
    """(specs, args) tail for a block-skip index operand.

    Every grid step takes the whole (1, n_rows, 2) table of its
    batch-head: Mosaic requires a block's last two dims to be tile
    multiples or the full array extent, so a per-row (1, 1, 2) block is
    not lowerable once ``n_rows > 1``.  ``gridded`` True: the grid's
    second dim walks the rows (fwd q-blocks / bwd k-blocks), the index
    map ignores it (the table is fetched once per batch-head) and the
    kernel reads row ``pl.program_id(1)``.  False: one grid step per
    batch-head (the varlen whole-sequence kernels).  ``lohi`` batch dim
    ∈ {bh, 1} broadcasting like the seg operands."""
    if lohi is None:
        return [], []
    one = lohi.shape[0] == 1
    if gridded:
        index_map = lambda b, i, o=one: (0 if o else b, 0, 0)
    else:
        index_map = lambda b, o=one: (0 if o else b, 0, 0)
    return [pl.BlockSpec((1, n_rows, 2), index_map)], [lohi]


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _assemble_scores(q, k, qi, ki, *, scale, causal, sq, sk,
                     mask=None, seg_q=None, seg_k=None, window=None):
    """The score block all four kernels share: q·kᵀ·scale, then additive
    mask, segment mask, and causal mask.  ``qi``/``ki`` are the absolute
    row/col offsets of this (q block, k block) tile; mask/seg operands are
    already sliced to the tile.  ``window`` (causal only): a row also
    loses the columns more than ``window - 1`` behind it."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = s + mask
    if seg_q is not None:
        s = jnp.where(seg_q[:, None] == seg_k[None, :], s, _NEG_INF)
    if causal:
        rows = qi + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ki + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows + (sk - sq) >= cols, s, _NEG_INF)
        if window is not None:
            s = jnp.where(cols > rows + (sk - sq) - window, s, _NEG_INF)
    return s


def _window_first_block(qi, sq, sk, window, block_k):
    """The first k block a q block starting at row ``qi`` can see under
    a sliding window: its first row's oldest visible column."""
    return jnp.maximum(0, qi + (sk - sq) - window + 1) // block_k


def _make_fwd_kernel(*, scale, causal, block_q, block_k, sq, sk,
                     has_mask, has_seg, dropout_rate, has_skip=False,
                     window=None):
    """Online-softmax forward (grid over q blocks) — the streaming form
    for shapes whose whole-sequence working set exceeds VMEM (the
    static-tiles kernel covers the rest).  A grouped-unroll variant
    (tree-merged local partials per loop iteration, the tiles kernel's
    ILP grafted onto this streaming form) was built and MEASURED
    LOSING at the deep-k shapes that reach this path — s4096/d128 fwd
    dropped 93.4 -> 86.4 TF at group size 2 (d=128 keeps the MXU fed
    already; causal edge-group waste and the extra rescale outweigh the
    pipelining) — so the classic one-exp-per-score carry body stays."""
    n_kb_s = sk // block_k

    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        mask_ref = next(it) if has_mask else None
        segq_ref = next(it) if has_seg else None
        segk_ref = next(it) if has_seg else None
        skip_ref = next(it) if has_skip else None
        seed_ref = next(it) if dropout_rate > 0 else None
        o_ref, lse_ref = next(it), next(it)

        bh_idx = pl.program_id(0)
        qi = pl.program_id(1) * block_q
        q = q_ref[0]  # [block_q, d]
        d = q.shape[-1]

        m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q,), jnp.float32)
        acc0 = jnp.zeros((block_q, d), jnp.float32)
        kb_lo = 0
        n_grp = n_kb_s
        if has_skip:
            # block-skip index: only k blocks in [lo, hi) can contain a
            # visible (seg_q == seg_k) pair for this q block — padding
            # tails and cross-segment blocks never enter the loop
            kb_lo = skip_ref[0, pl.program_id(1), 0]
            n_grp = skip_ref[0, pl.program_id(1), 1]
        if causal:
            # dynamic trip count: skip k blocks strictly above this q
            # block's last row (fully masked) — halves the MXU work
            last_row = qi + block_q - 1 + (sk - sq)
            n_grp = jnp.minimum(n_grp, last_row // block_k + 1)
        if window is not None:
            kb_lo = jnp.maximum(kb_lo, _window_first_block(
                qi, sq, sk, window, block_k))

        seg_q = segq_ref[0, :, 0] if has_seg else None  # [block_q]

        def scores_for(kb):
            ki = kb * block_k
            k = k_ref[0, pl.ds(ki, block_k), :]
            v = v_ref[0, pl.ds(ki, block_k), :]
            s = _assemble_scores(
                q, k, qi, ki, scale=scale, causal=causal,
                sq=sq, sk=sk,
                mask=(mask_ref[0, :, pl.ds(ki, block_k)]
                      if has_mask else None),
                seg_q=seg_q,
                seg_k=(segk_ref[0, pl.ds(ki, block_k), 0]
                       if has_seg else None), window=window)
            return s, v

        def dropped(p, kb):
            if dropout_rate > 0:
                keep = _dropout_keep(seed_ref[0, 0], bh_idx, qi,
                                     kb * block_k, block_q, block_k,
                                     dropout_rate)
                p = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
            return p

        def body(kb, carry):
            m, l, acc = carry
            s, v = scores_for(kb)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = _masked_exp(s, m_new[:, None])
            alpha = jnp.exp(m - m_new)
            # l accumulates UNDROPPED p: normalization must match the
            # softmax (dropout applies to the normalized probs)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            pv = jax.lax.dot_general(
                dropped(p, kb).astype(v.dtype), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc * alpha[:, None] + pv

        m, l, acc = jax.lax.fori_loop(kb_lo, n_grp, body, (m0, l0, acc0))
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        # dense [8, bq] row-broadcast lse block (see the tiles kernel's
        # layout note — a trailing-singleton output tiles at 128x cost)
        lse_row = jnp.where(l == 0, _NEG_INF, m + jnp.log(l_safe))
        lse_ref[0, 0] = jnp.broadcast_to(lse_row[None, :], (8, block_q))

    return kernel


def _merge_parts(parts):
    """Pairwise tree-merge of local-softmax partial states
    ``(m_i, l_i, acc_i)`` into one ``(m, l, acc)``.  Log-depth: the merge
    chain stays short while every tile's two MXU dots remain mutually
    independent — the scheduler can overlap VPU softmax work of one tile
    with MXU dots of another (measured: independent d=64 dots run at
    ~95 TF on v5e vs 47 TF when chained, r5)."""
    while len(parts) > 1:
        nxt = []
        for a in range(0, len(parts) - 1, 2):
            m1, l1, acc1 = parts[a]
            m2, l2, acc2 = parts[a + 1]
            m = jnp.maximum(m1, m2)
            a1 = jnp.where(m1 <= _NEG_INF / 2, 0.0, jnp.exp(m1 - m))
            a2 = jnp.where(m2 <= _NEG_INF / 2, 0.0, jnp.exp(m2 - m))
            nxt.append((m, a1 * l1 + a2 * l2,
                        a1[:, None] * acc1 + a2[:, None] * acc2))
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


def _make_fwd_kernel_tiles(*, scale, causal, block_q, block_k, sq, sk,
                           has_mask, has_seg, dropout_rate):
    """Fully-unrolled forward: ONE grid step per batch-head; every
    (q-block, k-block) tile is python-static.

    This generalizes the r4 split-merge kernel (which covered <=2 k
    blocks) to arbitrary tile counts:

    * causal tiles above the diagonal are skipped AT COMPILE TIME — no
      wasted MXU work (the online kernel's dynamic trip count, but
      static);
    * all visible tiles are mutually independent — no per-k-block
      rescale carry chain — so Mosaic can pipeline their dots and
      overlap the VPU softmax of one tile with the MXU dots of another;
    * per q-block, partial (m, l, acc) states combine by log-depth
      pairwise tree merge (:func:`_merge_parts`).

    Use is gated by :func:`_tiles_ok` (whole-sequence q/k/v plus live
    partials must fit VMEM)."""
    n_qb, n_kb = sq // block_q, sk // block_k

    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        mask_ref = next(it) if has_mask else None
        segq_ref = next(it) if has_seg else None
        segk_ref = next(it) if has_seg else None
        seed_ref = next(it) if dropout_rate > 0 else None
        o_ref, lse_ref = next(it), next(it)

        bh_idx = pl.program_id(0)
        for qb in range(n_qb):
            qi = qb * block_q
            q = q_ref[0, pl.ds(qi, block_q), :]
            seg_q = segq_ref[0, pl.ds(qi, block_q), 0] if has_seg else None
            parts = []
            for kb in range(n_kb):
                ki = kb * block_k
                if causal and qi + block_q - 1 + (sk - sq) < ki:
                    continue  # statically invisible tile
                k = k_ref[0, pl.ds(ki, block_k), :]
                v = v_ref[0, pl.ds(ki, block_k), :]
                s = _assemble_scores(
                    q, k, qi, ki, scale=scale, causal=causal,
                    sq=sq, sk=sk,
                    mask=(mask_ref[0, pl.ds(qi, block_q),
                                   pl.ds(ki, block_k)]
                          if has_mask else None),
                    seg_q=seg_q,
                    seg_k=(segk_ref[0, pl.ds(ki, block_k), 0]
                           if has_seg else None))
                m_i = jnp.max(s, axis=-1)
                p = _masked_exp(s, m_i[:, None])
                l_i = jnp.sum(p, axis=-1)
                if dropout_rate > 0:
                    keep = _dropout_keep(seed_ref[0, 0], bh_idx, qi, ki,
                                         block_q, block_k, dropout_rate)
                    p = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
                acc_i = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                parts.append((m_i, l_i, acc_i))
            if not parts:
                # causal with sq > sk can statically mask a whole
                # q-block: its rows attend to nothing — zeros out,
                # lse = -inf (matching the online kernel's l==0 guard)
                o_ref[0, pl.ds(qi, block_q), :] = jnp.zeros(
                    (block_q, q.shape[-1]), o_ref.dtype)
                lse_ref[0, qb] = jnp.full((8, block_q), _NEG_INF,
                                          jnp.float32)
                continue
            m, l, acc = _merge_parts(parts)
            l_safe = jnp.where(l == 0, 1.0, l)
            o_ref[0, pl.ds(qi, block_q), :] = (
                acc / l_safe[:, None]).astype(o_ref.dtype)
            # lse goes to a DENSE [n_qb, 8, bq] arrangement (row-
            # broadcast): a [sq, 1] trailing-singleton output would get
            # the (8,128)-tile layout with 128x physical amplification —
            # measured as multi-ms "broadcast" copies in the GPT step
            lse_row = jnp.where(l == 0, _NEG_INF, m + jnp.log(l_safe))
            lse_ref[0, qb] = jnp.broadcast_to(lse_row[None, :],
                                              (8, block_q))

    return kernel


_FWD_VMEM_BUDGET = 12 * 1024 * 1024


def _tiles_ok(q, k, mask_bias, block_q, block_k):
    """The unrolled-tiles forward holds whole-sequence q/k/v (and mask)
    per batch-head plus the live partial states of one q-block row in
    VMEM; estimate the resident set and refuse when it would not fit
    (the dispatcher then falls back to the online-carry kernel)."""
    sq, d = q.shape[1], q.shape[2]
    sk = k.shape[1]
    item = q.dtype.itemsize
    bq, bk = min(block_q, sq), min(block_k, sk)
    n_kb = sk // bk
    resident = (
        2 * sq * d * item          # q stream ×2 pipeline buffers
        + 2 * 2 * sk * d * item    # k, v streams ×2
        + 2 * sq * d * item        # o out ×2
        + 2 * 8 * sq * 4           # lse out (dense [n_qb,8,bq] rows) ×2
        + n_kb * (bq * d * 4 + 2 * bq * 4)  # partial (acc, m, l) states
        + 2 * bq * bk * 4          # transient score/p tiles in flight
    )
    if mask_bias is not None:
        resident += 2 * sq * sk * mask_bias.dtype.itemsize
    return resident <= _FWD_VMEM_BUDGET


def _make_fwd_kernel_varlen(*, scale, causal, block_q, block_k, sq, sk,
                            has_mask, dropout_rate, window=None):
    """Varlen fast forward (r7): the tiles kernel's whole-sequence
    residency (ONE grid step per batch-head, python-static q-blocks) but
    with each q-block's k-loop bounded by the block-skip index — a
    dynamic ``fori_loop`` over [lo, hi) with the online-softmax carry.

    vs the unrolled-tiles kernel: trades the static tree-merge ILP for
    *runtime* tile skipping, which static unrolling cannot express
    (segment ids are data).  At BERT-class padding ratios (~25% tail)
    the skip removes ~25% of the MXU work per padded row; under packing
    with R sequences per row it removes the ~(1-1/R) cross-segment
    tiles.  The segment-equality predicate stays fused into the masked
    exp for the tiles the range does keep.  Gated by
    :func:`_varlen_tiles_ok`; larger working sets take the grid-
    scheduled streaming kernel, which reads the same index."""
    n_qb = sq // block_q

    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        mask_ref = next(it) if has_mask else None
        segq_ref, segk_ref, skip_ref = next(it), next(it), next(it)
        seed_ref = next(it) if dropout_rate > 0 else None
        o_ref, lse_ref = next(it), next(it)

        bh_idx = pl.program_id(0)
        d = q_ref.shape[-1]
        for qb in range(n_qb):
            qi = qb * block_q
            q = q_ref[0, pl.ds(qi, block_q), :]
            seg_q = segq_ref[0, pl.ds(qi, block_q), 0]
            kb_lo = skip_ref[0, qb, 0]
            kb_hi = skip_ref[0, qb, 1]
            if causal:
                last_row = qi + block_q - 1 + (sk - sq)
                kb_hi = jnp.minimum(kb_hi, last_row // block_k + 1)
            if window is not None:
                kb_lo = jnp.maximum(kb_lo, _window_first_block(
                    qi, sq, sk, window, block_k))

            def body(kb, carry, qi=qi, q=q, seg_q=seg_q):
                m, l, acc = carry
                ki = kb * block_k
                k = k_ref[0, pl.ds(ki, block_k), :]
                v = v_ref[0, pl.ds(ki, block_k), :]
                s = _assemble_scores(
                    q, k, qi, ki, scale=scale, causal=causal,
                    sq=sq, sk=sk,
                    mask=(mask_ref[0, pl.ds(qi, block_q),
                                   pl.ds(ki, block_k)]
                          if has_mask else None),
                    seg_q=seg_q,
                    seg_k=segk_ref[0, pl.ds(ki, block_k), 0],
                    window=window)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = _masked_exp(s, m_new[:, None])
                alpha = jnp.exp(m - m_new)
                l_new = alpha * l + jnp.sum(p, axis=-1)
                if dropout_rate > 0:
                    keep = _dropout_keep(seed_ref[0, 0], bh_idx, qi, ki,
                                         block_q, block_k, dropout_rate)
                    p = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc * alpha[:, None] + pv

            m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
            l0 = jnp.zeros((block_q,), jnp.float32)
            acc0 = jnp.zeros((block_q, d), jnp.float32)
            # zero-trip range (a fully-dead q-block, e.g. an all-padding
            # row under a mask that empties it): carry stays (m0, l0=0,
            # 0), so the l==0 guard below emits zeros and lse = -inf —
            # the same convention as the other kernels
            m, l, acc = jax.lax.fori_loop(kb_lo, kb_hi, body,
                                          (m0, l0, acc0))
            l_safe = jnp.where(l == 0, 1.0, l)
            o_ref[0, pl.ds(qi, block_q), :] = (
                acc / l_safe[:, None]).astype(o_ref.dtype)
            lse_row = jnp.where(l == 0, _NEG_INF, m + jnp.log(l_safe))
            lse_ref[0, qb] = jnp.broadcast_to(lse_row[None, :],
                                              (8, block_q))

    return kernel


def _varlen_tiles_ok(q, k, mask_bias, block_q, block_k):
    """VMEM gate for the varlen fast forward: whole-sequence q/k/v (and
    mask) per batch-head like the tiles kernel, but the k loop is an
    online carry — no per-tile partial states resident, just one
    q-block's (m, l, acc) plus the tiny seg/skip streams."""
    sq, d = q.shape[1], q.shape[2]
    sk = k.shape[1]
    item = q.dtype.itemsize
    bq, bk = min(block_q, sq), min(block_k, sk)
    resident = (
        2 * sq * d * item          # q stream ×2 pipeline buffers
        + 2 * 2 * sk * d * item    # k, v streams ×2
        + 2 * sq * d * item        # o out ×2
        + 2 * 8 * sq * 4           # lse out ×2
        + bq * d * 4 + 2 * bq * 4  # carry (acc, m, l)
        + 2 * bq * bk * 4          # transient score/p tiles in flight
        + 2 * 2 * (sq + sk) * 4    # seg-id streams ×2
        + 2 * 2 * (sq // bq) * 2 * 4   # skip index ×2
    )
    if mask_bias is not None:
        resident += 2 * sq * sk * mask_bias.dtype.itemsize
    return resident <= _FWD_VMEM_BUDGET


def _mask_seg_specs(mask_bias, seg_q, seg_k, block_q_spec, sk, gridded_q):
    """in_specs/args tail for the optional mask + segment inputs.

    gridded_q: True when grid dim 1 walks q blocks (fwd/dq kernels); False
    when it walks k blocks and the q extent is taken whole (dkv kernel —
    then ``block_q_spec`` is the full sq and mask/seg_k index by k block);
    None for the unrolled-tiles kernels (grid=(bh,), every operand whole —
    then ``block_q_spec`` is the full sq).
    """
    specs, args = [], []
    if gridded_q is None:
        if mask_bias is not None:
            # default-arg binding, not closure: see the gridded branches
            mb1 = mask_bias.shape[0] == 1
            specs.append(pl.BlockSpec(
                (1, block_q_spec, sk),
                lambda b, one=mb1: (0 if one else b, 0, 0)))
            args.append(mask_bias)
        if seg_q is not None:
            sb1 = seg_q.shape[0] == 1
            specs.append(pl.BlockSpec(
                (1, block_q_spec, 1),
                lambda b, one=sb1: (0 if one else b, 0, 0)))
            specs.append(pl.BlockSpec(
                (1, sk, 1), lambda b, one=sb1: (0 if one else b, 0, 0)))
            args.append(seg_q[..., None].astype(jnp.int32))
            args.append(seg_k[..., None].astype(jnp.int32))
        return specs, args
    if mask_bias is not None:
        # bind the batch selector as a default arg: a late-binding closure
        # here would silently pick up the *segment* selector below
        mb1 = mask_bias.shape[0] == 1
        if gridded_q:
            specs.append(pl.BlockSpec(
                (1, block_q_spec, sk),
                lambda b, i, one=mb1: (0 if one else b, i, 0)))
        else:
            specs.append(pl.BlockSpec(
                (1, block_q_spec, sk),
                lambda b, j, one=mb1: (0 if one else b, 0, j)))
        args.append(mask_bias)
    if seg_q is not None:
        sb1 = seg_q.shape[0] == 1
        if gridded_q:
            specs.append(pl.BlockSpec(
                (1, block_q_spec, 1),
                lambda b, i, one=sb1: (0 if one else b, i, 0)))
            specs.append(pl.BlockSpec(
                (1, sk, 1), lambda b, i, one=sb1: (0 if one else b, 0, 0)))
        else:
            specs.append(pl.BlockSpec(
                (1, block_q_spec, 1),
                lambda b, j, one=sb1: (0 if one else b, 0, 0)))
            specs.append(pl.BlockSpec(
                (1, sk, 1), lambda b, j, one=sb1: (0 if one else b, j, 0)))
        args.append(seg_q[..., None].astype(jnp.int32))
        args.append(seg_k[..., None].astype(jnp.int32))
    return specs, args


def _seed_spec_arg(dropout_rate, dropout_seed):
    """(specs, args) tail for the dropout seed: a (1, 1) int32 operand
    every grid cell reads whole."""
    if dropout_rate <= 0:
        return [], []
    seed = jnp.asarray(dropout_seed, jnp.int32).reshape(1, 1)
    return [pl.BlockSpec((1, 1), lambda *_: (0, 0))], [seed]


# ---------------------------------------------------------------------------
# Routing (r7): the kernel choice is a named, testable decision.
#
# Forward routes: "varlen" (whole-sequence + block-skip — the varlen
# fast path), "tiles" (static unrolled + tree merge), "stream_skip"
# (grid-scheduled online kernel reading the skip index), "stream" (the
# generic grid kernel), "xla" (blockwise fallback).  Backward routes:
# "tiles", "grid_skip", "grid", "xla".  ``flash_attention_route``
# exposes the decision for tests; ``routing_override`` forces one
# (the reference side of a parity test or of ``chip_smoke.py``'s A/B).
# ---------------------------------------------------------------------------

_ROUTE_OVERRIDE = {"fwd": None, "bwd": None, "decode": None}


@contextlib.contextmanager
def routing_override(fwd=None, bwd=None, decode=None):
    """Force the fwd/bwd/decode kernel route inside the block
    (trace-time effect; use around ``jax.jit`` tracing, e.g. a
    parity test's forced reference route).  Values: fwd ∈ {"varlen",
    "tiles", "stream_skip", "stream", "xla"}, bwd ∈ {"tiles",
    "grid_skip", "grid", "xla"}, decode ∈ {"decode", "xla"}.  A forced
    Pallas fwd/bwd route still requires the shape to be
    Pallas-compilable (``_pallas_ok``); a forced "decode" route only
    requires the *shape* gate (``_decode_shape_ok``), not the TPU
    backend — off-TPU it runs the kernel in interpret mode, which is
    how the serving parity tests A/B the decode kernel against the
    generic paged-XLA baseline on identical pages."""
    prev = dict(_ROUTE_OVERRIDE)
    _ROUTE_OVERRIDE.update(fwd=fwd, bwd=bwd, decode=decode)
    try:
        yield
    finally:
        _ROUTE_OVERRIDE.update(prev)


def _fwd_pallas_route(q, k, mask_bias, has_seg, block_q, block_k):
    """Kernel choice among the Pallas forwards (backend already OK)."""
    if has_seg and _varlen_tiles_ok(q, k, mask_bias, block_q, block_k):
        return "varlen"
    if not has_seg and _tiles_ok(q, k, mask_bias, block_q, block_k):
        return "tiles"
    return "stream_skip" if has_seg else "stream"


def _fwd_route(q, k, mask_bias, has_seg, block_q, block_k):
    if _ROUTE_OVERRIDE["fwd"] is not None:
        forced = _ROUTE_OVERRIDE["fwd"]
        if forced == "xla":
            return forced
        if not _pallas_ok(q, k, mask_bias, block_q, block_k):
            return "xla"
        # a forced whole-sequence-resident route must still pass its
        # VMEM gate — degrade to the grid schedule instead of handing
        # Mosaic an over-budget kernel (mirrors _bwd_route's checks)
        if forced in ("varlen", "stream_skip") and not has_seg:
            # a skip route needs segments to build the index from —
            # report the downgrade the dispatcher will actually take
            forced = "stream"
        if forced == "tiles" and not _tiles_ok(q, k, mask_bias,
                                               block_q, block_k):
            return "stream"
        if forced == "varlen" and not _varlen_tiles_ok(
                q, k, mask_bias, block_q, block_k):
            return "stream_skip"
        return forced
    if not _pallas_ok(q, k, mask_bias, block_q, block_k):
        return "xla"
    return _fwd_pallas_route(q, k, mask_bias, has_seg, block_q, block_k)


def _bwd_route(q, k, mask_bias, has_seg, block_q, block_k):
    if _ROUTE_OVERRIDE["bwd"] is not None:
        forced = _ROUTE_OVERRIDE["bwd"]
        if forced == "xla":
            return forced
        if forced == "grid_skip" and not has_seg:
            forced = "grid"  # no segments to build the skip index from
        if forced == "tiles" and not _bwd_tiles_ok(q, k, mask_bias,
                                                   block_q, block_k):
            return "xla"
        if forced in ("grid", "grid_skip") and not _pallas_bwd_ok(
                q, k, mask_bias, block_q, block_k):
            return "xla"
        return forced
    if not _pallas_bwd_ok(q, k, mask_bias, block_q, block_k):
        return "xla"
    if has_seg:
        # varlen/padding backward: the one-pass grid kernel bounded by
        # the (transposed) block-skip index — under packing the skip
        # removes the cross-segment tiles the static-tiles kernel would
        # compute-and-mask, which outweighs the tiles kernel's ILP
        return "grid_skip"
    if _bwd_tiles_ok(q, k, mask_bias, block_q, block_k):
        return "tiles"
    return "grid"


def flash_attention_route(q, k=None, *, mask_bias=None, segment_ids=None,
                          block_q: int = 512, block_k: int = 1024):
    """{"fwd": ..., "bwd": ...} — the kernels :func:`flash_attention`
    would dispatch to for these operands (arrays or ShapeDtypeStructs,
    [bh, s, d]).  ``segment_ids`` may be the actual ids or any truthy
    marker; only presence matters for routing."""
    if k is None:
        k = q
    has_seg = segment_ids is not None
    bq, bk = min(block_q, q.shape[1]), min(block_k, k.shape[1])
    return {"fwd": _fwd_route(q, k, mask_bias, has_seg, bq, bk),
            "bwd": _bwd_route(q, k, mask_bias, has_seg, bq, bk)}


def _flash_fwd_pallas(q, k, v, mask_bias, seg_q, seg_k, dropout_seed,
                      scale, causal, block_q, block_k, dropout_rate,
                      route=None, group=1, window=None):
    """q [bh, sq, d], k/v [bh, sk, d] → (o [bh, sq, d], lse [bh, sq]).

    mask_bias: [mbh, sq, sk] additive (mbh ∈ {bh, 1}) or None.
    seg_q/seg_k: [sbh, sq]/[sbh, sk] int segment ids (sbh ∈ {bh, 1}) or
    None — scores across segments are masked (varlen packing).
    ``route`` picks the kernel (None = auto, see ``_fwd_pallas_route``).

    ``group`` > 1 (grouped-query heads, forward only): k/v are
    ``[bh // group, sk, d]`` and batch-head ``n`` of q reads row
    ``n // group`` of them — through the block index alone, so that
    consecutive grid steps of one group find their K/V block already
    in VMEM.  ``window``: the causal sliding window (see
    ``_assemble_scores``); the k loops start at the window's first
    block.  The static-tiles kernel knows neither and hands such a
    call to the streaming one.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if route is None:
        route = _fwd_pallas_route(q, k, mask_bias, seg_q is not None,
                                  block_q, block_k)
    if seg_q is None and route in ("varlen", "stream_skip"):
        # a skip route needs segments to build the index from — a
        # forced override on an unsegmented call downgrades
        route = "stream"
    if route == "tiles" and (group > 1 or window is not None):
        route = "stream"
    kw_window = {} if window is None else {"window": int(window)}
    if group > 1:
        kv1 = lambda b: (b // group, 0, 0)
        kv2 = lambda b, i: (b // group, 0, 0)
    else:
        kv1 = lambda b: (b, 0, 0)
        kv2 = lambda b, i: (b, 0, 0)
    seed_specs, seed_args = _seed_spec_arg(dropout_rate, dropout_seed)
    n_qb = sq // block_q
    kwargs = dict(
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        sq=sq, sk=sk, has_mask=mask_bias is not None,
        has_seg=seg_q is not None, dropout_rate=dropout_rate)

    skip_q = None
    if route in ("varlen", "stream_skip"):
        skip_q, _ = _segment_block_bounds(
            seg_q.astype(jnp.int32), seg_k.astype(jnp.int32),
            block_q, block_k)

    if route == "varlen":
        # varlen fast path: whole-sequence residency + block-skip index
        in_specs = [
            pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), kv1),
            pl.BlockSpec((1, sk, d), kv1),
        ]
        tail_specs, tail_args = _mask_seg_specs(
            mask_bias, seg_q, seg_k, sq, sk, gridded_q=None)
        skip_specs, skip_args = _skip_spec_arg(skip_q, gridded=False,
                                               n_rows=n_qb)
        kw = dict(kwargs)
        del kw["has_seg"]
        o, lse = pl.pallas_call(
            _make_fwd_kernel_varlen(**kw, **kw_window),
            grid=(bh,),
            in_specs=in_specs + tail_specs + skip_specs + seed_specs,
            out_specs=[
                pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
                pl.BlockSpec((1, n_qb, 8, block_q),
                             lambda b: (b, 0, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, n_qb, 8, block_q),
                                     jnp.float32),
            ],
            name="flash_fwd_varlen",
            interpret=use_interpret(),
        )(q, k, v, *tail_args, *skip_args, *seed_args)
        return o, lse[:, :, 0, :].reshape(bh, sq)

    if route == "tiles":
        # unrolled-tiles kernel: one grid step per batch-head, static
        # causal tile skip, tree merge (no rescale carry chain)
        in_specs = [
            pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
        ]
        tail_specs, tail_args = _mask_seg_specs(
            mask_bias, seg_q, seg_k, sq, sk, gridded_q=None)
        o, lse = pl.pallas_call(
            _make_fwd_kernel_tiles(**kwargs),
            grid=(bh,),
            in_specs=in_specs + tail_specs + seed_specs,
            out_specs=[
                pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
                pl.BlockSpec((1, n_qb, 8, block_q),
                             lambda b: (b, 0, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, n_qb, 8, block_q),
                                     jnp.float32),
            ],
            name="flash_fwd_tiles",
            interpret=use_interpret(),
        )(q, k, v, *tail_args, *seed_args)
        return o, lse[:, :, 0, :].reshape(bh, sq)

    # grid-scheduled streaming kernel ("stream"); with the skip index
    # appended ("stream_skip") each (bh, q-block) cell's k-loop runs
    # [lo, hi) instead of [0, n_kb)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, sk, d), kv2),
        pl.BlockSpec((1, sk, d), kv2),
    ]
    tail_specs, tail_args = _mask_seg_specs(
        mask_bias, seg_q, seg_k, block_q, sk, gridded_q=True)
    skip_specs, skip_args = ([], [])
    if route == "stream_skip":
        skip_specs, skip_args = _skip_spec_arg(skip_q, gridded=True,
                                               n_rows=n_qb)
    o, lse = pl.pallas_call(
        _make_fwd_kernel(**kwargs, has_skip=route == "stream_skip",
                         **kw_window),
        grid=(bh, n_qb),
        in_specs=in_specs + tail_specs + skip_specs + seed_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda b, i: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, n_qb, 8, block_q), jnp.float32),
        ],
        name="flash_fwd",
        interpret=use_interpret(),
    )(q, k, v, *tail_args, *skip_args, *seed_args)
    return o, lse[:, :, 0, :].reshape(bh, sq)


# ---------------------------------------------------------------------------
# Pallas backward kernel — fused ONE-PASS dq+dk+dv (flash-attention-2:
# delta trick, blockwise recompute of p).  Replaces the r1-r3 two-pass
# (separate dq and dkv kernels): each (q-block, k-block) score tile is now
# recomputed ONCE and feeds all five backward matmuls, and the q/do/lse/
# delta streams are read once instead of twice.  Measured on v5e at the
# GPT-350M shape (bh=128, s=1024, d=64): 1.10 ms vs 1.49 ms two-pass
# (39 vs 29 TF); at s=4096/d=128: 130 TF, 62% of the chip roof.
#
# Structure: grid (bh, k-blocks); k/v blocks gridded; q/do/lse/delta taken
# whole per batch-head; dk/dv accumulate in fp32 VMEM scratch within a
# grid step; dq accumulates in a persistent fp32 VMEM scratch across the
# k-block steps of one batch-head (the TPU grid is sequential) and is
# flushed on the last k-block.
# ---------------------------------------------------------------------------


def _make_fused_bwd_kernel(*, scale, causal, block_q, block_k, sq, sk,
                           has_mask, has_seg, dropout_rate, n_qb, n_kb,
                           has_skip=False):
    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (
            next(it), next(it), next(it), next(it), next(it), next(it))
        mask_ref = next(it) if has_mask else None
        segq_ref = next(it) if has_seg else None
        segk_ref = next(it) if has_seg else None
        skip_ref = next(it) if has_skip else None
        seed_ref = next(it) if dropout_rate > 0 else None
        dq_ref, dk_ref, dv_ref = next(it), next(it), next(it)
        dq_acc, dk_acc, dv_acc = next(it), next(it), next(it)

        bh_idx = pl.program_id(0)
        j = pl.program_id(1)
        ki = j * block_k
        k = k_ref[0]
        v = v_ref[0]
        seg_k = segk_ref[0, :, 0] if has_seg else None

        @pl.when(j == 0)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

        # first q block that sees this k block (causal): rows r attend to
        # col c iff r + (sk - sq) >= c
        qb0 = jnp.maximum((ki - (sk - sq)) // block_q, 0) if causal else 0
        qb1 = n_qb
        if has_skip:
            # transposed block-skip index: only q blocks in [lo, hi) can
            # hold a visible pair with this k block — a skipped tile
            # contributes 0 to dk/dv here AND to its own dq (identical
            # to the computed-and-masked result, minus the MXU work)
            qb0 = jnp.maximum(qb0, skip_ref[0, j, 0])
            qb1 = skip_ref[0, j, 1]

        def body(qb, _):
            qi = qb * block_q
            q = q_ref[0, pl.ds(qi, block_q), :]
            do = do_ref[0, pl.ds(qi, block_q), :]
            lse = lse_ref[0, pl.ds(qi, block_q), 0]
            delta = delta_ref[0, pl.ds(qi, block_q), 0]
            s = _assemble_scores(
                q, k, qi, ki, scale=scale, causal=causal, sq=sq, sk=sk,
                mask=(mask_ref[0, pl.ds(qi, block_q), :]
                      if has_mask else None),
                seg_q=(segq_ref[0, pl.ds(qi, block_q), 0]
                       if has_seg else None),
                seg_k=seg_k)
            p = _masked_exp(s, lse[:, None])
            # dp is a bf16xbf16 MXU dot: both operands arrive as bf16, so
            # fp32 upcasting would only slow the MXU without adding bits
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dropout_rate > 0:
                # same (row, col) coordinates as the forward tile — the
                # counter-hash replays bit-exactly
                keep = _dropout_keep(seed_ref[0, 0], bh_idx, qi, ki,
                                     block_q, block_k, dropout_rate)
                inv = 1.0 / (1.0 - dropout_rate)
                p_drop = jnp.where(keep, p, 0.0) * inv
                dp = jnp.where(keep, dp, 0.0) * inv
            else:
                p_drop = p
            dv_acc[...] += jax.lax.dot_general(
                p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, None]) * scale
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_acc[pl.ds(qi, block_q), :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(qb0, qb1, body, 0)
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

        @pl.when(j == n_kb - 1)
        def _():
            dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

    return kernel


def _tree_sum(terms):
    """Pairwise tree-sum: log-depth accumulator chain so the summed
    tiles' dots stay schedulable in parallel."""
    while len(terms) > 1:
        terms = [a + b for a, b in zip(terms[::2], terms[1::2])] + (
            [terms[-1]] if len(terms) % 2 else [])
    return terms[0]


def _make_bwd_kernel_tiles(*, scale, causal, block_q, block_k, sq, sk,
                           has_mask, has_seg, dropout_rate):
    """Fully-unrolled one-pass backward: ONE grid step per batch-head,
    python-static (q-block, k-block) tiles with compile-time causal
    skip — the backward counterpart of :func:`_make_fwd_kernel_tiles`.

    Each visible tile recomputes its score block once and feeds all five
    backward dots; dq/dk/dv partial contributions are combined by
    log-depth tree-sum instead of a serialized accumulator chain, so the
    per-tile dot groups (which have no cross-tile dependencies) pipeline
    on the MXU while another tile's VPU softmax/ds math runs.  Gated by
    :func:`_bwd_tiles_ok` (whole-sequence streams + live partials must
    fit VMEM).

    Alignment rule (ADVICE r5): lse arrives as a dense ``[1, sq]`` LANE
    row and each q-block reads it via the static slice
    ``lse_ref[0, 0, qi:qi+block_q]`` — a *lane*-dimension offset, legal
    in Mosaic only when every ``qi = qb·block_q`` is a multiple of the
    128-lane width.  The gate therefore requires ``block_q % 128 == 0``
    or ``sq == block_q`` (single q-block: the only offset is 0);
    sub-128 caller blocks with multiple q-blocks take the
    grid-scheduled fallback, whose ``[sq, 1]`` sublane arrangement has
    no such constraint.  Larger shapes use the same fallback for VMEM
    reasons."""
    n_qb, n_kb = sq // block_q, sk // block_k

    def visible(qi, ki):
        return not (causal and qi + block_q - 1 + (sk - sq) < ki)

    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref = (
            next(it), next(it), next(it), next(it), next(it), next(it))
        mask_ref = next(it) if has_mask else None
        segq_ref = next(it) if has_seg else None
        segk_ref = next(it) if has_seg else None
        seed_ref = next(it) if dropout_rate > 0 else None
        dq_ref, dk_ref, dv_ref = next(it), next(it), next(it)

        bh_idx = pl.program_id(0)
        # delta = rowsum(do * o), computed IN-KERNEL per q-block from
        # the saved o: passing it as a [bh, sq, 1] operand (like lse
        # used to be) forces a trailing-singleton layout whose (8,128)
        # tiling amplifies it 128x physically — measured as multi-ms
        # copies in the GPT step.  lse arrives as a dense [1, sq] lane
        # row instead, statically sliced per q-block.
        deltas = [
            jnp.sum(do_ref[0, pl.ds(qb * block_q, block_q), :].astype(
                jnp.float32)
                * o_ref[0, pl.ds(qb * block_q, block_q), :].astype(
                    jnp.float32), axis=-1)
            for qb in range(n_qb)]
        dq_parts = [[] for _ in range(n_qb)]
        for kb in range(n_kb):
            ki = kb * block_k
            k = k_ref[0, pl.ds(ki, block_k), :]
            v = v_ref[0, pl.ds(ki, block_k), :]
            seg_k = (segk_ref[0, pl.ds(ki, block_k), 0]
                     if has_seg else None)
            dk_parts, dv_parts = [], []
            for qb in range(n_qb):
                qi = qb * block_q
                if not visible(qi, ki):
                    continue
                q = q_ref[0, pl.ds(qi, block_q), :]
                do = do_ref[0, pl.ds(qi, block_q), :]
                lse = lse_ref[0, 0, qi:qi + block_q]
                delta = deltas[qb]
                s = _assemble_scores(
                    q, k, qi, ki, scale=scale, causal=causal,
                    sq=sq, sk=sk,
                    mask=(mask_ref[0, pl.ds(qi, block_q),
                                   pl.ds(ki, block_k)]
                          if has_mask else None),
                    seg_q=(segq_ref[0, pl.ds(qi, block_q), 0]
                           if has_seg else None),
                    seg_k=seg_k)
                p = _masked_exp(s, lse[:, None])
                dp = jax.lax.dot_general(
                    do, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if dropout_rate > 0:
                    keep = _dropout_keep(seed_ref[0, 0], bh_idx, qi, ki,
                                         block_q, block_k, dropout_rate)
                    inv = 1.0 / (1.0 - dropout_rate)
                    p_drop = jnp.where(keep, p, 0.0) * inv
                    dp = jnp.where(keep, dp, 0.0) * inv
                else:
                    p_drop = p
                dv_parts.append(jax.lax.dot_general(
                    p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                ds = p * (dp - delta[:, None]) * scale
                dk_parts.append(jax.lax.dot_general(
                    ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                dq_parts[qb].append(jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            d_ = k.shape[-1]
            if dk_parts:
                dk_ref[0, pl.ds(ki, block_k), :] = _tree_sum(
                    dk_parts).astype(dk_ref.dtype)
                dv_ref[0, pl.ds(ki, block_k), :] = _tree_sum(
                    dv_parts).astype(dv_ref.dtype)
            else:  # unreachable for causal sq<=sk; guard for sq>sk edge
                dk_ref[0, pl.ds(ki, block_k), :] = jnp.zeros(
                    (block_k, d_), dk_ref.dtype)
                dv_ref[0, pl.ds(ki, block_k), :] = jnp.zeros(
                    (block_k, d_), dv_ref.dtype)
        for qb in range(n_qb):
            if dq_parts[qb]:
                dq_ref[0, pl.ds(qb * block_q, block_q), :] = _tree_sum(
                    dq_parts[qb]).astype(dq_ref.dtype)
            else:
                # a statically fully-masked q-block (causal, sq > sk)
                # contributes no tiles: its dq is zero
                dq_ref[0, pl.ds(qb * block_q, block_q), :] = jnp.zeros(
                    (block_q, q_ref.shape[-1]), dq_ref.dtype)

    return kernel


def _bwd_tiles_ok(q, k, mask_bias, block_q, block_k):
    """VMEM estimate for the unrolled-tiles backward: whole-sequence
    q/k/v/do/lse/delta and dq/dk/dv plus the live dq partials of every
    q-block and one k-block's dk/dv partials.  Also enforces the
    kernel's lane-alignment rule (see :func:`_make_bwd_kernel_tiles`):
    the per-q-block lse lane slice needs ``block_q % 128 == 0`` unless
    there is only one q-block."""
    if not _pallas_ok(q, k, mask_bias, block_q, block_k):
        return False
    sq, d = q.shape[1], q.shape[2]
    sk = k.shape[1]
    item = q.dtype.itemsize
    bq, bk = min(block_q, sq), min(block_k, sk)
    if bq % 128 != 0 and sq != bq:
        # lane-unaligned lse slice offsets (qi = qb·bq not a multiple of
        # the 128-lane width with >1 q-block): Mosaic lowering is
        # unverified for this case — route to the grid fallback
        return False
    n_qb, n_kb = sq // bq, sk // bk
    resident = (
        2 * 3 * sq * d * item      # q, do, o streams ×2 buffers
        + 2 * 2 * sk * d * item    # k, v streams ×2
        + 2 * 8 * sq * 4           # lse lane-row ([1, sq], 8x tiling) ×2
        + sq * 4                   # in-kernel delta rows
        + 2 * sq * d * item        # dq output ×2
        + 2 * 2 * sk * d * item    # dk/dv outputs ×2
        + n_kb * sq * d * 4        # dq tile partials, live to final sum
        + 2 * bk * d * 4           # one k-block's dk/dv partial sums
        + 3 * bq * bk * 4          # transient score/p/ds tiles in flight
    )
    if mask_bias is not None:
        resident += 2 * sq * sk * mask_bias.dtype.itemsize
    return resident <= _BWD_VMEM_BUDGET


def _flash_bwd_pallas(q, k, v, mask_bias, seg_q, seg_k, dropout_seed,
                      o, lse, do, scale, causal, block_q, block_k,
                      dropout_rate, route=None):
    """Returns (dq, dk, dv) in input dtypes — one fused kernel pass.
    ``route`` picks the kernel ("tiles" | "grid" | "grid_skip"; None =
    tiles when it fits, grid otherwise — the pre-varlen behavior)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_qb, n_kb = sq // block_q, sk // block_k
    has_mask = mask_bias is not None
    has_seg = seg_q is not None
    seed_specs, seed_args = _seed_spec_arg(dropout_rate, dropout_seed)
    kw = dict(scale=scale, causal=causal, block_q=block_q,
              block_k=block_k, sq=sq, sk=sk, has_mask=has_mask,
              has_seg=has_seg, dropout_rate=dropout_rate)
    if route is None:
        route = ("tiles" if _bwd_tiles_ok(q, k, mask_bias, block_q,
                                          block_k) else "grid")
    if seg_q is None and route == "grid_skip":
        route = "grid"  # no segments to build the skip index from

    if route == "tiles":
        in_specs = [pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, 1, sq), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0))]
        tail_specs, tail_args = _mask_seg_specs(
            mask_bias, seg_q, seg_k, sq, sk, gridded_q=None)
        dq, dk, dv = pl.pallas_call(
            _make_bwd_kernel_tiles(**kw),
            grid=(bh,),
            in_specs=in_specs + tail_specs + seed_specs,
            out_specs=[
                pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
                pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
                pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            name="flash_bwd_tiles",
            interpret=use_interpret(),
        )(q, k, v, do, lse[:, None, :], o, *tail_args, *seed_args)
        return dq, dk, dv

    # grid-scheduled fallback: lse/delta stay [bh, sq, 1] operands (the
    # fori-loop q index needs a sublane-dim dynamic slice, which the
    # dense lane-row arrangement of the tiles kernel cannot provide)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [bh, sq, 1]
    lse3 = lse[..., None]
    in_specs = [
        pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),        # q
        pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),   # k
        pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),   # v
        pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),        # do
        pl.BlockSpec((1, sq, 1), lambda b, j: (b, 0, 0)),        # lse
        pl.BlockSpec((1, sq, 1), lambda b, j: (b, 0, 0)),        # delta
    ]
    tail_specs, tail_args = _mask_seg_specs(
        mask_bias, seg_q, seg_k, sq, block_k, gridded_q=False)
    skip_specs, skip_args = ([], [])
    if route == "grid_skip":
        # transposed skip index: per k-block, the live q-block range
        _, skip_k = _segment_block_bounds(
            seg_q.astype(jnp.int32), seg_k.astype(jnp.int32),
            block_q, block_k)
        skip_specs, skip_args = _skip_spec_arg(skip_k, gridded=True,
                                               n_rows=n_kb)
    dq, dk, dv = pl.pallas_call(
        _make_fused_bwd_kernel(
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            sq=sq, sk=sk, has_mask=has_mask, has_seg=has_seg,
            dropout_rate=dropout_rate, n_qb=n_qb, n_kb=n_kb,
            has_skip=route == "grid_skip"),
        grid=(bh, n_kb),
        in_specs=in_specs + tail_specs + skip_specs + seed_specs,
        out_specs=[
            pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((sq, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        name="flash_bwd",
        interpret=use_interpret(),
    )(q, k, v, do, lse3, delta, *tail_args, *skip_args, *seed_args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Blockwise XLA path (off-TPU / sub-tiling-grain shapes) + dispatch
# ---------------------------------------------------------------------------


def _apply_masks(s, mask_bias, seg_q, seg_k, causal, window=None):
    if mask_bias is not None:
        s = s + mask_bias
    if seg_q is not None:
        s = jnp.where(seg_q[..., :, None] == seg_k[..., None, :],
                      s, _NEG_INF)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        tri = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            tri &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        s = jnp.where(tri, s, _NEG_INF)
    return s


def _blockwise_fwd_xla(q, k, v, scale, causal, mask_bias, seg_q, seg_k,
                       dropout_seed=None, dropout_rate=0.0, group=1,
                       window=None):
    """Plain-XLA forward with identical math (used off-TPU and for shapes
    below the TPU tiling grain — where the S×S score matrix is small).
    ``group`` > 1: every row of k/v serves ``group`` rows of q."""
    if group > 1:
        k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = _apply_masks(s, mask_bias, seg_q, seg_k, causal, window)
    m = jnp.max(s, axis=-1)
    p = _masked_exp(s, m[..., None])
    l = jnp.sum(p, axis=-1)
    if dropout_rate > 0:
        keep = _dropout_keep_full(dropout_seed, *p.shape, dropout_rate)
        pv = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
    else:
        pv = p
    o = jnp.einsum("bqk,bkd->bqd", pv, v.astype(jnp.float32))
    o = o / jnp.where(l == 0, 1.0, l)[..., None]
    lse = jnp.where(l == 0, _NEG_INF, m + jnp.log(jnp.where(l == 0, 1.0, l)))
    return o.astype(q.dtype), lse


def _blockwise_bwd_xla(q, k, v, mask_bias, seg_q, seg_k, o, lse, do,
                       scale, causal, block_k,
                       dropout_seed=None, dropout_rate=0.0):
    """XLA backward: lax.scan over k blocks, S×block_k live at a time."""
    q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))
    do32 = do.astype(jnp.float32)
    delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)  # [bh, sq]
    sq, sk = q.shape[1], k.shape[1]
    bh = q.shape[0]
    bk = min(block_k, sk)
    n_kb = sk // bk if sk % bk == 0 else 1
    if sk % bk != 0:
        bk = sk

    def kblock(dq_acc, kb):
        ks = jax.lax.dynamic_slice_in_dim(k32, kb * bk, bk, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v32, kb * bk, bk, axis=1)
        s = jnp.einsum("bqd,bkd->bqk", q32, ks) * scale
        if mask_bias is not None:
            mb = jax.lax.dynamic_slice_in_dim(mask_bias, kb * bk, bk, axis=-1)
            s = s + mb
        if seg_q is not None:
            sks = jax.lax.dynamic_slice_in_dim(seg_k, kb * bk, bk, axis=-1)
            s = jnp.where(seg_q[..., :, None] == sks[..., None, :],
                          s, _NEG_INF)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (sq, bk), 0)
            cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (sq, bk), 1)
            s = jnp.where((rows + (sk - sq))[None] >= cols[None], s, _NEG_INF)
        p = _masked_exp(s, lse[..., None])
        dp = jnp.einsum("bqd,bkd->bqk", do32, vs)
        if dropout_rate > 0:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bh, sq, bk), 1)
            cols = kb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bh, sq, bk), 2)
            bb = jax.lax.broadcasted_iota(jnp.int32, (bh, sq, bk), 0)
            keep = _keep_from_coords(rows, cols, bb, dropout_seed,
                                     dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            p_drop = jnp.where(keep, p, 0.0) * inv
            dp = jnp.where(keep, dp, 0.0) * inv
        else:
            p_drop = p
        dv = jnp.einsum("bqk,bqd->bkd", p_drop, do32)
        ds = p * (dp - delta[..., None]) * scale
        dk = jnp.einsum("bqk,bqd->bkd", ds, q32)
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, ks)
        return dq_acc, (dk, dv)

    dq, (dks, dvs) = jax.lax.scan(kblock, jnp.zeros_like(q32),
                                  jnp.arange(n_kb))
    dk = jnp.moveaxis(dks, 0, 1).reshape(k.shape[0], sk, k.shape[2])
    dv = jnp.moveaxis(dvs, 0, 1).reshape(v.shape[0], sk, v.shape[2])
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


def _pallas_ok(q, k, mask_bias, block_q, block_k):
    """Whether the Pallas kernel path is compilable for these shapes
    (Mosaic alignment rules; see module docstring)."""
    if jax.default_backend() != "tpu":
        return False
    sq, sk = q.shape[1], k.shape[1]
    bq, bk = min(block_q, sq), min(block_k, sk)
    if sq % bq or sk % bk:
        return False
    if bq % 16 or bk % 16:  # sublane dynamic-slice grain (bf16: 16)
        return False
    if mask_bias is not None and (bk % LANE or sk % LANE):
        return False  # mask is lane-sliced inside the kernel
    return True


_BWD_VMEM_BUDGET = 12 * 1024 * 1024  # leave headroom of the ~16 MB/core


def _pallas_bwd_ok(q, k, mask_bias, block_q, block_k):
    """The fused one-pass backward additionally holds the whole q/do
    streams, a whole-sq fp32 dq accumulator, and the dq output block in
    VMEM per batch-head — shapes that fit the two-pass or forward kernel
    can exceed the ~16 MB core VMEM here, so estimate the resident
    footprint and fall back to the XLA blockwise backward when it would
    not fit."""
    if not _pallas_ok(q, k, mask_bias, block_q, block_k):
        return False
    sq, d = q.shape[1], q.shape[2]
    sk = k.shape[1]
    bk = min(block_k, sk)
    item = q.dtype.itemsize
    resident = (
        # whole-bh streams are pipeline double-buffered across the bh
        # grid dimension, same as the blocked operands below
        2 * 2 * sq * d * item  # q, do streams (whole per batch-head) ×2
        + sq * d * 4           # dq fp32 accumulator scratch (not piped)
        + 2 * sq * d * item    # dq output block ×2 buffers
        + 2 * 2 * sq * 4       # lse + delta ×2 buffers
        + 2 * (4 * bk * d * item + 2 * bk * d * 4)  # k/v/dk/dv ×2 buffers
    )
    if mask_bias is not None:
        resident += 2 * sq * bk * mask_bias.dtype.itemsize
    return resident <= _BWD_VMEM_BUDGET


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _flash_attention(q, k, v, mask_bias, seg_q, seg_k, dropout_seed,
                     scale, causal, block_q, block_k, dropout_rate):
    o, _ = _flash_fwd_dispatch(q, k, v, mask_bias, seg_q, seg_k,
                               dropout_seed, scale, causal, block_q,
                               block_k, dropout_rate)
    return o


def _flash_fwd_dispatch(q, k, v, mask_bias, seg_q, seg_k, dropout_seed,
                        scale, causal, block_q, block_k, dropout_rate):
    bq, bk = min(block_q, q.shape[1]), min(block_k, k.shape[1])
    route = _fwd_route(q, k, mask_bias, seg_q is not None, bq, bk)
    if route != "xla":
        return _flash_fwd_pallas(q, k, v, mask_bias, seg_q, seg_k,
                                 dropout_seed, scale, causal, block_q,
                                 block_k, dropout_rate, route=route)
    return _blockwise_fwd_xla(q, k, v, scale, causal, mask_bias,
                              seg_q, seg_k, dropout_seed, dropout_rate)


def _flash_fwd_grouped(q, k, v, seg_q, seg_k, scale, block_q, block_k,
                       group, window):
    """The forward of a call with grouped-query heads or a sliding
    window: causal, no additive mask, no dropout, and no VJP (the
    serving path never differentiates; training keeps the kernels and
    the custom VJP above exactly as they were).  Same routes as
    :func:`_flash_fwd_dispatch`, decided on q's shape."""
    bq, bk = min(block_q, q.shape[1]), min(block_k, k.shape[1])
    route = _fwd_route(q, k, None, seg_q is not None, bq, bk)
    if route != "xla":
        o, _ = _flash_fwd_pallas(q, k, v, None, seg_q, seg_k, None, scale,
                                 True, block_q, block_k, 0.0, route=route,
                                 group=group, window=window)
        return o
    o, _ = _blockwise_fwd_xla(q, k, v, scale, True, None, seg_q, seg_k,
                              group=group, window=window)
    return o


def _flash_fwd_rule(q, k, v, mask_bias, seg_q, seg_k, dropout_seed,
                    scale, causal, block_q, block_k, dropout_rate):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _flash_fwd_dispatch(q, k, v, mask_bias, seg_q, seg_k,
                                 dropout_seed, scale, causal, block_q,
                                 block_k, dropout_rate)
    # remat hook: under jax.checkpoint, the backward regenerates these
    # residuals by RERUNNING the forward kernel — naming them lets a
    # save_only_these_names policy keep (o, lse) and skip that rerun
    # (GPTConfig remat_policy="attn_res"); the tags are inert otherwise
    o = checkpoint_name(o, "flash_attn_out")
    lse = checkpoint_name(lse, "flash_attn_lse")
    return o, (q, k, v, mask_bias, seg_q, seg_k, dropout_seed, o, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, dropout_rate,
                    res, do):
    q, k, v, mask_bias, seg_q, seg_k, dropout_seed, o, lse = res
    bq, bk = min(block_q, q.shape[1]), min(block_k, k.shape[1])
    route = _bwd_route(q, k, mask_bias, seg_q is not None, bq, bk)
    if route != "xla":
        dq, dk, dv = _flash_bwd_pallas(
            q, k, v, mask_bias, seg_q, seg_k, dropout_seed, o, lse, do,
            scale, causal, block_q, block_k, dropout_rate, route=route)
    else:
        dq, dk, dv = _blockwise_bwd_xla(
            q, k, v, mask_bias, seg_q, seg_k, o, lse, do,
            scale, causal, block_k, dropout_seed, dropout_rate)
    dmask = None if mask_bias is None else jnp.zeros_like(mask_bias)
    f0 = jax.dtypes.float0
    dsegq = None if seg_q is None else np.zeros(seg_q.shape, f0)
    dsegk = None if seg_k is None else np.zeros(seg_k.shape, f0)
    dseed = np.zeros((), f0)  # int32 scalar: symbolic-zero cotangent
    return (dq, dk, dv, dmask, dsegq, dsegk, dseed)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# Packed-QKV self-attention: transpose-free kernels over the projection
# layout.
#
# The GPT path pays ~10 ms/step (B=8, s=1024) of pure layout churn
# around the [bh, s, d] kernels: transposes of q/k/v ([b,s,np,hn] ->
# [b,np,s,hn]) in forward AND in the attn_res recompute, plus the
# reshape copies of dq/dk/dv back to [b, s, h] (r5 trace).
# These kernels instead consume the qkv projection output DIRECTLY in
# its Megatron-interleaved layout — [b, s, np*(q64|k64|v64)] — slicing
# each head's q/k/v statically from the lane dimension (64-granularity
# static lane slices measured at full MXU rate on v5e), and emit dqkv
# in the same layout, feeding the projection backward with zero
# transposes in either direction.  Heads are processed in groups whose
# lane width is a multiple of 128 (pairs at hn=64) so every HBM-facing
# block store stays 128-lane aligned.  Self-attention only (dq/dk/dv
# share the sequence axis, letting one [bq, group*3*hn] store carry all
# three per row block).  Varlen/padding shapes stay ON this path (r7):
# segment ids ride in as per-batch int32 streams, the equality
# predicate is fused into the masked exp, and the forward's k-loop is
# bounded by the block-skip index; only cross-attention and additive-
# mask shapes use the generic kernels.
# ---------------------------------------------------------------------------


def _qkv_group(hn):
    """Heads per kernel instance: smallest count making the per-group
    lane width (group*3*hn) a multiple of 128."""
    for g in (1, 2, 4):
        if (g * 3 * hn) % LANE == 0:
            return g
    return None


def _make_fwd_kernel_qkv(*, scale, causal, block, s, hn, group,
                         num_heads, dropout_rate, has_seg=False):
    """Packed-QKV forward.  Without segments: python-static tiles with
    the log-depth tree merge (unchanged r5 schedule).  With segments
    (``has_seg`` — the varlen fast path on the packed layout, r7): each
    q-block runs a dynamic ``fori_loop`` over the block-skip index's
    [lo, hi) k-range with the online-softmax carry and the segment
    predicate fused into the masked exp — cross-segment and padding-
    tail tiles are never entered, on the same transpose-free layout."""
    n_b = s // block
    w = 3 * hn

    def kernel(*refs):
        it = iter(refs)
        qkv_ref = next(it)
        segq_ref = next(it) if has_seg else None
        segk_ref = next(it) if has_seg else None
        skip_ref = next(it) if has_seg else None
        seed_ref = next(it) if dropout_rate > 0 else None
        o_ref, lse_ref = next(it), next(it)

        b_idx = pl.program_id(0)
        hg = pl.program_id(1)
        for qb in range(n_b):
            qi = qb * block
            seg_q = segq_ref[0, pl.ds(qi, block), 0] if has_seg else None
            if has_seg:
                kb_lo = skip_ref[0, qb, 0]
                kb_hi = skip_ref[0, qb, 1]
                if causal:
                    kb_hi = jnp.minimum(kb_hi, qb + 1)
            o_cols, lse_rows = [], []
            for j in range(group):
                base = j * w
                bh_idx = b_idx * num_heads + hg * group + j
                q = qkv_ref[0, pl.ds(qi, block), base:base + hn]
                if has_seg:
                    def body(kb, carry, qi=qi, q=q, seg_q=seg_q,
                             base=base, bh_idx=bh_idx):
                        m, l, acc = carry
                        ki = kb * block
                        k = qkv_ref[0, pl.ds(ki, block),
                                    base + hn:base + 2 * hn]
                        v = qkv_ref[0, pl.ds(ki, block),
                                    base + 2 * hn:base + 3 * hn]
                        sc = _assemble_scores(
                            q, k, qi, ki, scale=scale, causal=causal,
                            sq=s, sk=s, seg_q=seg_q,
                            seg_k=segk_ref[0, pl.ds(ki, block), 0])
                        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
                        p = _masked_exp(sc, m_new[:, None])
                        alpha = jnp.exp(m - m_new)
                        l_new = alpha * l + jnp.sum(p, axis=-1)
                        if dropout_rate > 0:
                            keep = _dropout_keep(seed_ref[0, 0], bh_idx,
                                                 qi, ki, block, block,
                                                 dropout_rate)
                            p = jnp.where(keep, p, 0.0) / (
                                1.0 - dropout_rate)
                        pv = jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        return m_new, l_new, acc * alpha[:, None] + pv

                    init = (jnp.full((block,), _NEG_INF, jnp.float32),
                            jnp.zeros((block,), jnp.float32),
                            jnp.zeros((block, hn), jnp.float32))
                    m, l, acc = jax.lax.fori_loop(kb_lo, kb_hi, body,
                                                  init)
                else:
                    parts = []
                    for kb in range(n_b):
                        ki = kb * block
                        if causal and qi < ki:
                            continue
                        k = qkv_ref[0, pl.ds(ki, block),
                                    base + hn:base + 2 * hn]
                        v = qkv_ref[0, pl.ds(ki, block),
                                    base + 2 * hn:base + 3 * hn]
                        sc = _assemble_scores(q, k, qi, ki, scale=scale,
                                              causal=causal, sq=s, sk=s)
                        m_i = jnp.max(sc, axis=-1)
                        p = _masked_exp(sc, m_i[:, None])
                        l_i = jnp.sum(p, axis=-1)
                        if dropout_rate > 0:
                            keep = _dropout_keep(seed_ref[0, 0], bh_idx,
                                                 qi, ki, block, block,
                                                 dropout_rate)
                            p = jnp.where(keep, p, 0.0) / (
                                1.0 - dropout_rate)
                        acc_i = jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        parts.append((m_i, l_i, acc_i))
                    m, l, acc = _merge_parts(parts)
                l_safe = jnp.where(l == 0, 1.0, l)
                o_cols.append((acc / l_safe[:, None]).astype(o_ref.dtype))
                lse_rows.append(
                    jnp.where(l == 0, _NEG_INF, m + jnp.log(l_safe)))
            o_ref[0, pl.ds(qi, block), :] = jnp.concatenate(o_cols, -1)
            for j, row in enumerate(lse_rows):
                lse_ref[0, 0, j, qb] = jnp.broadcast_to(
                    row[None, :], (8, block))

    return kernel


def _make_bwd_kernel_qkv(*, scale, causal, block, s, hn, group,
                         num_heads, dropout_rate, has_seg=False):
    """Packed-QKV backward: python-static tiles, per-head grads held for
    the 128-lane-aligned joint store.  With ``has_seg`` the segment
    predicate is fused into the recomputed score block (compute-and-
    mask: the static tile structure the joint store depends on cannot
    take runtime trip counts, so the varlen *backward* skip lives in
    the grid one-pass kernel — see ``_bwd_route`` — while this kernel
    keeps the transpose-free layout; dead tiles contribute exact
    zeros)."""
    n_b = s // block
    w = 3 * hn

    def kernel(*refs):
        it = iter(refs)
        qkv_ref, do_ref, o_ref, lse_ref = (next(it), next(it), next(it),
                                           next(it))
        segq_ref = next(it) if has_seg else None
        segk_ref = next(it) if has_seg else None
        seed_ref = next(it) if dropout_rate > 0 else None
        dqkv_ref = next(it)

        b_idx = pl.program_id(0)
        hg = pl.program_id(1)
        # per head: final dq/dk/dv per row block, held until the joint
        # [bq, group*3*hn] store keeps every write 128-lane aligned
        head_grads = []
        for j in range(group):
            base = j * w
            ob = j * hn
            bh_idx = b_idx * num_heads + hg * group + j
            deltas = [
                jnp.sum(do_ref[0, pl.ds(i * block, block),
                               ob:ob + hn].astype(jnp.float32)
                        * o_ref[0, pl.ds(i * block, block),
                                ob:ob + hn].astype(jnp.float32), axis=-1)
                for i in range(n_b)]

            def blocksum(parts):
                # cast each block's fp32 tree-sum to the OUTPUT dtype
                # here rather than at the joint store: the held
                # per-head grads are the largest resident term of
                # _qkv_packed_ok's VMEM estimate, and the cast happens
                # either way (bitwise-identical result, half the bytes
                # held for bf16)
                return (_tree_sum(parts).astype(dqkv_ref.dtype) if parts
                        else jnp.zeros((block, hn), dqkv_ref.dtype))

            dq_parts = [[] for _ in range(n_b)]
            dks, dvs = [], []
            for kb in range(n_b):
                ki = kb * block
                k = qkv_ref[0, pl.ds(ki, block), base + hn:base + 2 * hn]
                v = qkv_ref[0, pl.ds(ki, block),
                            base + 2 * hn:base + 3 * hn]
                seg_k = (segk_ref[0, pl.ds(ki, block), 0]
                         if has_seg else None)
                dk_parts, dv_parts = [], []
                for qb in range(n_b):
                    qi = qb * block
                    if causal and qi < ki:
                        continue
                    q = qkv_ref[0, pl.ds(qi, block), base:base + hn]
                    do = do_ref[0, pl.ds(qi, block), ob:ob + hn]
                    lse = lse_ref[0, 0, j, qb, 0, :]
                    sc = _assemble_scores(
                        q, k, qi, ki, scale=scale, causal=causal,
                        sq=s, sk=s,
                        seg_q=(segq_ref[0, pl.ds(qi, block), 0]
                               if has_seg else None),
                        seg_k=seg_k)
                    p = _masked_exp(sc, lse[:, None])
                    dp = jax.lax.dot_general(
                        do, v, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    if dropout_rate > 0:
                        keep = _dropout_keep(seed_ref[0, 0], bh_idx, qi,
                                             ki, block, block,
                                             dropout_rate)
                        inv = 1.0 / (1.0 - dropout_rate)
                        p_drop = jnp.where(keep, p, 0.0) * inv
                        dp = jnp.where(keep, dp, 0.0) * inv
                    else:
                        p_drop = p
                    dv_parts.append(jax.lax.dot_general(
                        p_drop.astype(do.dtype), do,
                        (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                    ds = p * (dp - deltas[qb][:, None]) * scale
                    dk_parts.append(jax.lax.dot_general(
                        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                    dq_parts[qb].append(jax.lax.dot_general(
                        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                # this k-block's dk/dv are complete once its q-blocks
                # are walked: sum them now, so the fp32 partials of one
                # k-block are live at a time and not those of all
                dks.append(blocksum(dk_parts))
                dvs.append(blocksum(dv_parts))

            head_grads.append(([blocksum(p) for p in dq_parts], dks, dvs))
        for i in range(n_b):
            cols = []
            for dqs, dks, dvs in head_grads:
                cols += [dqs[i], dks[i], dvs[i]]
            dqkv_ref[0, pl.ds(i * block, block), :] = jnp.concatenate(
                cols, -1).astype(dqkv_ref.dtype)

    return kernel


_QKV_VMEM_BUDGET = 12 * 1024 * 1024
# What the backward may actually allocate.  The gate above prices the
# pipeline buffers and the held output-dtype grads; the compiler also
# keeps the unrolled tiles' fp32 dq partials on its stack until each
# q-block's tree-sum, which the estimate cannot see.  At the 1.3B
# flagship shape (s=2048, d=128, block 256) Mosaic asks for 16.3 MiB
# against an estimate of 10.4 and its own default limit of 16 (a v5e
# core has 128), so the limit is named here, at twice the default.
_QKV_BWD_VMEM_LIMIT = 32 * 1024 * 1024


def _qkv_packed_ok(b, s, num_heads, hn, block, causal, dropout_rate,
                   dtype=jnp.bfloat16, has_seg=False):
    """Gate for the packed path: TPU backend, aligned shapes, and the
    backward's resident set (the larger of the two) within VMEM.

    ``dtype`` is the CALLER's qkv dtype — the estimate must use its real
    itemsize (ADVICE r5: a hardcoded bf16 itemsize gated fp32 inputs
    against half their footprint, so near-budget fp32 shapes passed the
    gate and then failed Mosaic VMEM allocation instead of routing to
    the fallback)."""
    del causal, dropout_rate
    if jax.default_backend() != "tpu":
        return False
    group = _qkv_group(hn)
    if group is None or num_heads % group or num_heads < group:
        return False
    if s % block or block % 16 or hn % 64:
        return False
    item = jnp.dtype(dtype).itemsize
    n_b = s // block
    resident = (
        2 * s * 3 * hn * group * item   # qkv block ×2 buffers
        + 2 * 2 * s * hn * group * item  # do + o blocks ×2
        + 2 * group * n_b * 8 * block * 4  # lse slab ×2
        + 2 * s * 3 * hn * group * item  # dqkv out ×2
        + group * 3 * s * hn * item     # held per-head block grads
        #                                 (cast to out dtype at blocksum)
        + 3 * block * block * 4         # transient score tiles
    )
    if has_seg:
        # two int32 seg streams + the skip index, double-buffered
        resident += 2 * 2 * s * 4 + 2 * (s // block) * 2 * 4
    return resident <= _QKV_VMEM_BUDGET


def _qkv_packed_block(b, s, num_heads, hn, block, causal, dropout_rate,
                      dtype=jnp.bfloat16, has_seg=False):
    """Largest block size ≤ the requested one for which the packed
    kernels fit VMEM, or None when no candidate fits.

    The flagship d=128/s=2048 shape exceeds the budget at the library
    default block of 512 (whole-sequence streams at 3·hn lanes) but fits
    at 256 — without this shrink the gate silently dropped exactly the
    shape class the packed path exists for to the generic grid kernels
    plus their transposes.  Smaller-than-requested candidates stop at
    128 (the lane width; score tiles below that underfill the MXU)."""
    cands = [block] + [c for c in (256, 128) if c < block]
    for cand in cands:
        if _qkv_packed_ok(b, s, num_heads, hn, cand, causal,
                          dropout_rate, dtype, has_seg):
            return cand
    return None


def _qkv_seg_specs(seg_q, seg_k, s, block, n_b):
    """(specs, args) tail for the packed kernels' segment operands:
    per-batch [b, s, 1] int32 seg_q/seg_k streams (shared across the
    head-group grid dim) plus the [b, n_b, 2] block-skip index."""
    if seg_q is None:
        return [], []
    sq32 = seg_q.astype(jnp.int32)
    sk32 = seg_k.astype(jnp.int32)
    skip_q, _ = _segment_block_bounds(sq32, sk32, block, block)
    one = seg_q.shape[0] == 1
    sel = lambda bi, g, o=one: (0 if o else bi, 0, 0)
    specs = [pl.BlockSpec((1, s, 1), sel),
             pl.BlockSpec((1, s, 1), sel),
             pl.BlockSpec((1, n_b, 2), sel)]
    return specs, [sq32[..., None], sk32[..., None], skip_q]


def _flash_qkv_fwd_pallas(qkv, dropout_seed, num_heads, hn, scale,
                          causal, block, dropout_rate,
                          seg_q=None, seg_k=None):
    b, s, _ = qkv.shape
    group = _qkv_group(hn)
    n_hg = num_heads // group
    n_b = s // block
    w = group * 3 * hn
    seg_specs, seg_args = _qkv_seg_specs(seg_q, seg_k, s, block, n_b)
    seed_specs, seed_args = _seed_spec_arg(dropout_rate, dropout_seed)
    ctx, lse = pl.pallas_call(
        _make_fwd_kernel_qkv(scale=scale, causal=causal, block=block,
                             s=s, hn=hn, group=group,
                             num_heads=num_heads,
                             dropout_rate=dropout_rate,
                             has_seg=seg_q is not None),
        grid=(b, n_hg),
        in_specs=[pl.BlockSpec((1, s, w), lambda bi, g: (bi, 0, g))]
        + seg_specs + seed_specs,
        out_specs=[
            pl.BlockSpec((1, s, group * hn), lambda bi, g: (bi, 0, g)),
            pl.BlockSpec((1, 1, group, n_b, 8, block),
                         lambda bi, g: (bi, g, 0, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, num_heads * hn), qkv.dtype),
            jax.ShapeDtypeStruct((b, n_hg, group, n_b, 8, block),
                                 jnp.float32),
        ],
        name="flash_qkv_fwd",
        interpret=use_interpret(),
    )(qkv, *seg_args, *seed_args)
    return ctx, lse


def _flash_qkv_bwd_pallas(qkv, dropout_seed, ctx, lse, dctx, num_heads,
                          hn, scale, causal, block, dropout_rate,
                          seg_q=None, seg_k=None):
    b, s, _ = qkv.shape
    group = _qkv_group(hn)
    n_hg = num_heads // group
    n_b = s // block
    w = group * 3 * hn
    # the saved residual carries only sublane row 0 of the forward's
    # 8-row lse slab (the fwd rule slices before checkpoint_name); the
    # kernel reads row 0 either way, so size the stream to what arrives
    lse_rows = lse.shape[4]
    seg_specs, seg_args = _qkv_seg_specs(seg_q, seg_k, s, block, n_b)
    if seg_specs:
        seg_specs, seg_args = seg_specs[:2], seg_args[:2]  # no skip idx
    seed_specs, seed_args = _seed_spec_arg(dropout_rate, dropout_seed)
    dqkv = pl.pallas_call(
        _make_bwd_kernel_qkv(scale=scale, causal=causal, block=block,
                             s=s, hn=hn, group=group,
                             num_heads=num_heads,
                             dropout_rate=dropout_rate,
                             has_seg=seg_q is not None),
        grid=(b, n_hg),
        in_specs=[
            pl.BlockSpec((1, s, w), lambda bi, g: (bi, 0, g)),
            pl.BlockSpec((1, s, group * hn), lambda bi, g: (bi, 0, g)),
            pl.BlockSpec((1, s, group * hn), lambda bi, g: (bi, 0, g)),
            pl.BlockSpec((1, 1, group, n_b, lse_rows, block),
                         lambda bi, g: (bi, g, 0, 0, 0, 0)),
        ] + seg_specs + seed_specs,
        out_specs=pl.BlockSpec((1, s, w), lambda bi, g: (bi, 0, g)),
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_QKV_BWD_VMEM_LIMIT),
        name="flash_qkv_bwd",
        interpret=use_interpret(),
    )(qkv, dctx, ctx, lse, *seg_args, *seed_args)
    return dqkv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention_qkv(qkv, seg_q, seg_k, dropout_seed, num_heads,
                         hn, scale, causal, block, dropout_rate):
    ctx, _ = _flash_qkv_fwd_pallas(qkv, dropout_seed, num_heads, hn,
                                   scale, causal, block, dropout_rate,
                                   seg_q=seg_q, seg_k=seg_k)
    return ctx


def _flash_qkv_fwd_rule(qkv, seg_q, seg_k, dropout_seed, num_heads, hn,
                        scale, causal, block, dropout_rate):
    from jax.ad_checkpoint import checkpoint_name

    ctx, lse = _flash_qkv_fwd_pallas(qkv, dropout_seed, num_heads, hn,
                                     scale, causal, block, dropout_rate,
                                     seg_q=seg_q, seg_k=seg_k)
    # same names as the generic path so remat_policy="attn_res" works.
    # The kernel emits lse as a [b, n_hg, group, n_b, 8, block] slab
    # whose 8 sublane rows are identical broadcasts (the (8,128)-tiled
    # store layout); checkpointing the raw slab saved an 8x residual
    # (~8 MB/layer at the 1.3B flagship's b=4/s=2048 — ADVICE r5).
    # Slice row 0 BEFORE checkpoint_name: one small copy per layer, and
    # the attn_res policy saves the logical-size lse only.  The backward
    # kernel reads row 0 regardless, so it consumes either slab height.
    lse = lse[..., :1, :]
    ctx = checkpoint_name(ctx, "flash_attn_out")
    lse = checkpoint_name(lse, "flash_attn_lse")
    return ctx, (qkv, seg_q, seg_k, dropout_seed, ctx, lse)


def _flash_qkv_bwd_rule(num_heads, hn, scale, causal, block,
                        dropout_rate, res, dctx):
    qkv, seg_q, seg_k, dropout_seed, ctx, lse = res
    dqkv = _flash_qkv_bwd_pallas(qkv, dropout_seed, ctx, lse, dctx,
                                 num_heads, hn, scale, causal, block,
                                 dropout_rate, seg_q=seg_q, seg_k=seg_k)
    f0 = jax.dtypes.float0
    dsegq = None if seg_q is None else np.zeros(seg_q.shape, f0)
    dsegk = None if seg_k is None else np.zeros(seg_k.shape, f0)
    return (dqkv, dsegq, dsegk, np.zeros((), f0))


_flash_attention_qkv.defvjp(_flash_qkv_fwd_rule, _flash_qkv_bwd_rule)


def _normalize_qkv_segments(segment_ids, b, s):
    """segment_ids (int [s] / [b, s] or a (seg_q, seg_k) pair of those)
    → (seg_q, seg_k) int32 arrays with batch dim ∈ {b, 1}, or (None,
    None)."""
    if segment_ids is None:
        return None, None
    if isinstance(segment_ids, tuple):
        seg_q, seg_k = segment_ids
    else:
        seg_q = seg_k = segment_ids
    seg_q = jnp.asarray(seg_q, jnp.int32)
    seg_k = jnp.asarray(seg_k, jnp.int32)
    if seg_q.ndim == 1:
        seg_q = seg_q[None]
    if seg_k.ndim == 1:
        seg_k = seg_k[None]
    if seg_q.shape[-1] != s or seg_k.shape[-1] != s:
        raise ValueError(
            f"segment_ids length {seg_q.shape[-1]}/{seg_k.shape[-1]} "
            f"!= sequence length {s} (packed QKV is self-attention)")
    for name, a in (("seg_q", seg_q), ("seg_k", seg_k)):
        if a.shape[0] not in (1, b):
            raise ValueError(
                f"segment_ids {name} batch dim {a.shape[0]} is neither "
                f"1 nor the qkv batch {b}")
    return seg_q, seg_k


def flash_attention_qkv_route(b, s, num_heads, hn, *, block: int = 512,
                              block_k: Optional[int] = None,
                              causal: bool = True,
                              dropout_rate: float = 0.0,
                              dtype=jnp.bfloat16,
                              has_segments: bool = False) -> str:
    """The path :func:`flash_attention_qkv` takes for this shape:
    "packed_varlen" (packed kernels with in-kernel segment masking +
    block-skip), "packed", or "generic" (transposed views through
    :func:`flash_attention`)."""
    if block_k not in (None, block) or use_interpret():
        # the packed kernels tile both axes with one block size; an
        # explicit differing block_k routes generic (wrapper gate)
        return "generic"
    picked = _qkv_packed_block(b, s, num_heads, hn, min(block, s),
                               causal, dropout_rate, dtype, has_segments)
    if picked is None:
        return "generic"
    return "packed_varlen" if has_segments else "packed"


def flash_attention_qkv(
    qkv: jnp.ndarray, num_heads: int,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block: int = 512,
    block_k: Optional[int] = None,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[Union[int, jnp.ndarray]] = None,
    segment_ids: Optional[Union[jnp.ndarray,
                                Tuple[jnp.ndarray, jnp.ndarray]]] = None,
) -> jnp.ndarray:
    """Self-attention straight from the QKV projection output.

    ``qkv``: [b, s, num_heads*3*hn] in the Megatron interleaved layout
    (per head: hn q lanes, hn k lanes, hn v lanes — what
    ``ColumnParallelLinear`` emits for the fused QKV weight, reference
    standalone_gpt.py ParallelAttention :283).  Returns the attention
    context [b, s, num_heads*hn], ready for the output projection.

    On TPU (aligned shapes) this runs the packed Pallas kernels, which
    read/write the projection layouts directly — no head transposes or
    gradient reshape copies.  Elsewhere, or for unaligned shapes, it
    falls back to :func:`flash_attention` on the transposed views
    (identical math and dropout bits — both paths index the counter
    hash by ``b*num_heads + head``).

    ``segment_ids`` (r7 varlen fast path): int [s] or [b, s] packing
    ids, or a ``(seg_q, seg_k)`` pair of those — e.g. ``(ones, keep)``
    for a BERT key-padding mask.  Scores across segments are masked
    inside the packed kernels and the forward skips fully-masked
    k-blocks via the block-skip index, so varlen/padding shapes stay on
    the transpose-free path instead of dropping to the generic grid
    kernels (the r5 gap VERDICT r5 Weak #4 names)."""
    b, s, three_h = qkv.shape
    hn = three_h // (3 * num_heads)
    if three_h != 3 * num_heads * hn:
        raise ValueError(
            f"qkv last dim {three_h} is not 3*num_heads*head_dim "
            f"(num_heads={num_heads})")
    if scale is None:
        scale = 1.0 / math.sqrt(hn)
    # same validation as the generic wrapper — the packed path must not
    # silently accept what flash_attention rejects (review finding: a
    # defaulted seed of 0 would drop the SAME positions every step)
    if dropout_rate > 0:
        if not 0.0 < dropout_rate < 1.0:
            raise ValueError(f"dropout_rate {dropout_rate} not in (0, 1)")
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
    seg_q, seg_k = _normalize_qkv_segments(segment_ids, b, s)
    # the packed kernels tile both axes with ONE block size; an explicit
    # differing block_k routes to the generic path
    if block_k in (None, block) and not use_interpret():
        packed_block = _qkv_packed_block(b, s, num_heads, hn,
                                         min(block, s), causal,
                                         dropout_rate, qkv.dtype,
                                         seg_q is not None)
        if packed_block is not None:
            seed = 0 if dropout_seed is None else dropout_seed
            return _flash_attention_qkv(qkv, seg_q, seg_k, seed,
                                        num_heads, hn, float(scale),
                                        causal, packed_block,
                                        float(dropout_rate))
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (  # [b, np, s, hn]
        jnp.split(qkv.reshape(b, s, num_heads, 3 * hn), 3, axis=-1)))
    seg_arg = None if seg_q is None else (seg_q, seg_k)
    ctx = flash_attention(q, k, v, causal=causal, scale=scale,
                          segment_ids=seg_arg,
                          block_q=block,
                          block_k=block if block_k is None else block_k,
                          dropout_rate=dropout_rate,
                          dropout_seed=dropout_seed)
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, num_heads * hn)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *,
    causal: bool = False,
    mask_bias: Optional[jnp.ndarray] = None,
    segment_ids: Optional[Union[jnp.ndarray,
                                Tuple[jnp.ndarray, jnp.ndarray]]] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    mask_is_constant: bool = True,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[Union[int, jnp.ndarray]] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Fused attention over [b, h, s, d] (or [bh, s, d]) tensors.

    Grouped-query heads: ``k``/``v`` may carry fewer heads than ``q``
    (``h_q = g * h_kv``; query head ``n`` reads K/V head ``n // g``).
    ``window`` (with ``causal``): a position sees itself and the
    ``window - 1`` before it.  Either makes the call forward-only (no
    VJP), causal, without additive mask or dropout: the serving
    prefill.  With one K/V head per query head and no window the call
    is the one it always was.

    ``dropout_rate`` > 0 applies attention-probability dropout INSIDE the
    kernels (the reference FMHA's Philox in-kernel dropout,
    fmha_api.cpp p_dropout): masks come from a counter-based hash of
    (seed, batch-head, row, col), replayed bit-exactly in the backward
    kernels and the XLA fallback — nothing is stored.  ``dropout_seed``
    (int or traced int32 scalar) selects the stream; derive it per step
    and per TP rank (see tensor_parallel.random) for training.

    Drop-in for the reference's ``fmha.FMHAFun`` (fmha.py:33) and the core
    of every ``fast_*_multihead_attn`` — without its seq-len/head-dim
    restrictions.  ``mask_bias`` is an *additive* mask (the
    additive-mask-softmax variants); boolean masks should be converted
    with ``jnp.where(mask, -10000.0, 0.0)``.  By default it is treated as
    a constant under differentiation (the reference's masks encode
    padding, never parameters) — pass ``mask_is_constant=False`` for a
    *trainable* additive bias (learned ALiBi/relative-position style):
    that routes through a plain differentiable XLA path (materialises the
    S×S scores; the Pallas kernels do not emit a mask gradient) so AD
    produces the bias gradient instead of silent zeros.  ``segment_ids``
    masks attention across segment boundaries (varlen packing): an int
    array [s] or [b, s] for self-attention, or a ``(seg_q, seg_k)`` pair
    for cross-length cases.
    """
    squeeze = False
    seg_q = seg_k = None
    if segment_ids is not None:
        if isinstance(segment_ids, tuple):
            seg_q, seg_k = segment_ids
        else:
            seg_q = seg_k = segment_ids
        if seg_q.ndim == 1:
            seg_q = seg_q[None]
        if seg_k.ndim == 1:
            seg_k = seg_k[None]
    if q.ndim == 4:
        b, h, sq, d = q.shape
        q = q.reshape(b * h, sq, d)
        k = k.reshape(b * k.shape[1], k.shape[2], d)
        v = v.reshape(b * v.shape[1], v.shape[2], d)
        if mask_bias is not None and mask_bias.ndim == 4:
            mask_bias = jnp.broadcast_to(
                mask_bias, (b, h, sq, k.shape[1])).reshape(
                b * h, sq, k.shape[1])
        if seg_q is not None and seg_q.shape[0] == b and b > 1:
            # per-batch segments replicate across heads
            seg_q = jnp.repeat(seg_q, h, axis=0)
            seg_k = jnp.repeat(seg_k, h, axis=0)
        squeeze = (b, h)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    rate = float(dropout_rate)
    if not 0.0 <= rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {rate}")
    if rate > 0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    seed = jnp.asarray(dropout_seed if dropout_seed is not None else 0,
                       jnp.int32)
    group = q.shape[0] // k.shape[0]
    if group != 1 or window is not None:
        if (not causal or mask_bias is not None or rate > 0
                or group * k.shape[0] != q.shape[0]
                or (window is not None and window < 1)):
            raise ValueError(
                "grouped-query heads and a sliding window are for causal "
                "calls without mask_bias or dropout, q heads a multiple "
                "of k/v heads, window >= 1")
        o = _flash_fwd_grouped(q, k, v, seg_q, seg_k, float(scale),
                               int(block_q), int(block_k), group, window)
    elif mask_bias is not None and not mask_is_constant:
        # differentiable-bias path: same math, no custom_vjp, so AD
        # derives d(mask_bias) — the kernels only handle constant masks
        o, _ = _blockwise_fwd_xla(q, k, v, float(scale), bool(causal),
                                  mask_bias, seg_q, seg_k, seed, rate)
    else:
        if mask_bias is not None:
            mask_bias = jax.lax.stop_gradient(mask_bias)
        o = _flash_attention(q, k, v, mask_bias, seg_q, seg_k, seed,
                             float(scale), bool(causal),
                             int(block_q), int(block_k), rate)
    if squeeze:
        b, h = squeeze
        o = o.reshape(b, h, o.shape[1], o.shape[2])
    return o


def flash_attention_varlen(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    cu_seqlens_q: jnp.ndarray,
    cu_seqlens_k: Optional[jnp.ndarray] = None,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Packed variable-length attention — the reference FMHA's BERT-style
    interface (fmha.py:33-75): sequences concatenated along one token
    axis, delimited by ``cu_seqlens`` prefix sums.

    q/k/v: [total_tokens, h, d]; cu_seqlens_q/k: int [batch+1] with
    cu[0] == 0 and cu[batch] <= total_tokens (trailing padding tokens
    attend only among themselves; their outputs are ignored by
    construction).  Instead of the reference's CUDA varlen layout, the
    TPU mapping is *segment-id masking inside the flash kernel* — one
    fixed-shape kernel launch, no per-sequence dispatch, MXU-friendly.
    ``k``/``v`` may carry fewer heads than ``q`` and ``window`` bounds
    how far back a token sees, as in :func:`flash_attention`.
    """
    if cu_seqlens_k is None:
        cu_seqlens_k = cu_seqlens_q
    total_q, h, d = q.shape
    total_k = k.shape[0]
    # token i belongs to sequence j iff cu[j] <= i < cu[j+1]; tokens past
    # cu[-1] land in segment `batch` (padding bucket)
    seg_q = jnp.searchsorted(cu_seqlens_q, jnp.arange(total_q),
                             side="right") - 1
    seg_k = jnp.searchsorted(cu_seqlens_k, jnp.arange(total_k),
                             side="right") - 1
    qh = jnp.moveaxis(q, 1, 0)  # [h, total_q, d]
    kh = jnp.moveaxis(k, 1, 0)
    vh = jnp.moveaxis(v, 1, 0)
    o = flash_attention(qh, kh, vh, causal=causal,
                        segment_ids=(seg_q, seg_k), scale=scale,
                        block_q=block_q, block_k=block_k, window=window)
    return jnp.moveaxis(o, 0, 1)


# ---------------------------------------------------------------------------
# Flash decode over a paged KV cache (r8, serving path).
#
# Training kernels above see one contiguous [bh, s, d] KV per call; the
# serving engine instead keeps every request's KV in fixed-size PAGES
# of a shared preallocated pool (apex_tpu.serving.kv_cache), so a
# request's cache is a *page list*, not a slab.  The decode kernel
# consumes that layout directly: the page table rides in as a
# scalar-prefetch operand and DRIVES THE KERNEL'S OWN DMAs — the pools
# stay in HBM and a page ``page_table[b, p]`` is copied into VMEM when
# row b's walk comes to it, so the gather that the generic XLA baseline
# materialises in HBM never happens.  The kernel's K/V operand is the
# engine's WHOLE pool, ``[L, n_pages, page_size, h, d]``, every layer
# of it: the layer to read is a third scalar-prefetch operand and the
# first index of every copy's source (as an operand and not a constant
# it leaves the model's L calls one Mosaic kernel, not L of them to
# compile).  A Mosaic custom call cannot take a view into a larger
# array, so handing it ``pool[layer]`` makes XLA copy that layer's
# pages into a fresh buffer before every call (48 copies of 157 MB a
# decode step at the 1.3B geometry: 23 ms; PERF.md, PR 28); addressed
# by the copies the bytes are read where they lie.
#
# The grid is ``(b, tiles)``: a step is one row of the batch (one tile
# of its query positions where a chunk is tiled), and a loop inside the
# step walks that row's LIVE pages, from the first a query row of the
# step can see to the last, ``P`` of them (a block) a turn; ``P``
# follows from the shapes (_decode_pages_per_step).  A page is one
# contiguous DMA, every head of it; the block after (or the next
# step's first) is in flight while this one is scored.  Per-request
# raggedness is the walk's bounds: pages past ``kv_len``, before a
# window or past a tile's last row are neither fetched nor scored, and
# a row with nothing to see costs a grid step and no loop turn (until
# PR 30 the grid was ``(b, p_max)``, a 64-token page a step: 896 steps
# a call at the 1.3B geometry whatever the batch held, and sixteen
# strided head slices with M = 1 contractions a page; PERF.md).  The
# online-softmax carry lives in VMEM scratch and is read and written
# once a block.
# ---------------------------------------------------------------------------


def _decode_q_tile(q_len, group):
    """Query positions a grid step of the decode kernel takes.  All of
    them while a K/V head's ``group * q_len`` rows are at most 512 (plain
    decode, a verify window, the multi-head chunks of old): one step a
    row of the batch.  Beyond that (a grouped-query chunk: 6 x 2,048
    rows a K/V head, whose blocks and accumulators would not fit in
    VMEM) the largest halving of ``q_len`` that fits, kept a multiple
    of 8."""
    tq = q_len
    while group * tq > 512 and tq % 16 == 0:
        tq //= 2
    return tq


# VMEM for the K and V pages in flight: two slots (the block being
# scored and the one the DMAs are filling) of K and of V.
_DECODE_BLOCK_BYTES = 8 * 2 ** 20
# ... and the most columns a block's score tile may have.  The per-head
# body keeps [rows, columns] float32 scores and their exponentials
# live, hundreds of rows of them; the all-heads body's columns are
# (token, head) pairs.
_DECODE_BLOCK_COLS = 1024
_DECODE_ALL_HEADS_COLS = 4096
# Few query rows a K/V head: at most this many and the kernel scores
# all heads of a page at once (see _make_decode_kernel).
_DECODE_FEW_ROWS = 8
# Scoped VMEM the kernel may use (the chip has 128 MiB; Mosaic's default
# of 16 would not hold the two slots beside a chunk's accumulators).
_DECODE_VMEM_LIMIT = 64 * 2 ** 20


def lane_dense_pages(num_heads: int, dtype) -> bool:
    """Whether a page pool of ``num_heads`` K/V heads (the heads ONE
    device holds) in ``dtype`` keeps a page lane-dense, ``[page_size,
    num_heads * head_dim]``, rather than ``[page_size, num_heads,
    head_dim]``.

    Where the heads fill whole sublane tiles (8 or more, a multiple of
    8) a head-split page lies in whole tiles and every body of the
    decode kernel reads it as it lies.  Where they do not, XLA tiles the
    minor ``(h, d)`` pair by ``h``: at 2 heads of 128 in bf16 a 64-token
    page is 64 tiles of 512 bytes, fetched as 64 pieces, and a head is a
    one-sublane-in-eight gather out of the slot.  Stored lane-dense the
    same page is whole ``(8, 128)`` tiles, one contiguous copy, and head
    ``hh`` is lanes ``[hh * d, (hh + 1) * d)``: a tile-aligned view.
    The element order is the same.  A quantized pool (one-byte codes,
    scaled per (slot, head)) keeps its heads split; so does a pool of
    one head, whose lane-dense page would have the shape of one layer
    of a head-split pool, which :func:`flash_decode` also takes."""
    return (jnp.dtype(dtype).itemsize > 1 and num_heads > 1
            and num_heads % 8 != 0)


def _pool_layout(k_pages, d):
    """``(lane_dense, page_size, h)`` of a pool :func:`flash_decode`
    is handed: head-split ``[L, n_pages, page_size, h, d]`` (one layer:
    ``[n_pages, page_size, h, d]``) or lane-dense ``[L, n_pages,
    page_size, h * d]``.  A four-dimensional pool whose last axis is
    ``d`` is one layer of a head-split pool; any other is lane-dense."""
    shape = k_pages.shape
    lane_dense = len(shape) == 4 and shape[-1] != d
    if lane_dense:
        return True, shape[-2], shape[-1] // d
    return False, shape[-3], shape[-2]


def _decode_body(rows_n, h, quantized, lane_dense=False):
    """How the kernel scores a block, from what it can see of the call.

    ``"all_heads"``: few query rows a K/V head (plain decode, a verify
    window): every head of the block in one contraction, the block
    read as ``[pages * page_size * h, d]`` rows, which is the block as
    it lies only where ``h`` fills whole sublane tiles of a head-split
    page.
    ``"per_head"``: many rows (a chunk's tile, or a decode row of a
    large query group): head by head over the whole block, ``[rows, d]
    x [d, pages * page_size]`` on the MXU.
    ``"per_page"``: the rest (a quantized pool, whose codes are scaled
    per (slot, head) first; few rows over heads that fill no sublane
    tile): head by head, a page at a time, as the kernel always did.
    A lane-dense pool's head is a view of the slot either way."""
    if rows_n > _DECODE_FEW_ROWS:
        return "per_page" if quantized else "per_head"
    return ("all_heads" if h % 8 == 0 and not quantized and not lane_dense
            else "per_page")


def _decode_pages_per_step(page_size, h, d, itemsize, p_max, body):
    """``P``, the pages of one row that a turn of the decode kernel's
    page loop brings and scores together: as many as the VMEM set aside
    for them holds twice over (K and V, two slots), no more columns
    than the score tile may have, no more than a row's table has."""
    page_bytes = page_size * h * d * itemsize
    pages = _DECODE_BLOCK_BYTES // (4 * page_bytes)
    if body == "all_heads":
        pages = min(pages, _DECODE_ALL_HEADS_COLS // (page_size * h))
    else:
        pages = min(pages, _DECODE_BLOCK_COLS // page_size)
    return max(1, min(pages, p_max))


def _make_decode_kernel(*, scale, page_size, q_len, h, d, pages, p_max,
                        quantized=False, group=1, tq=None, window=None,
                        has_start=False, body="per_page", lane_dense=False):
    """Decode forward: grid (b, tiles); scalar-prefetch operands
    (page_table [b, p_max], kv_len [b], layer [1]).  Queries are the
    LAST ``q_len`` positions of the request's ``kv_len``-token cache
    (their own k/v already appended), so row i's causal limit is column
    ``kv_len - q_len + i``.

    The pools stay in HBM (``pl.ANY``): a step walks ITS OWN live pages,
    from the first a query row of it can see to the last, ``pages`` of
    them (a block) a turn of a loop inside the kernel.  Each page of
    the block is one DMA, found through the page table, into one of two
    VMEM slots ``[pages, page_size, h, d]`` (``lane_dense``: ``[pages,
    page_size, h * d]``, the pool's own page); the block after (or the
    first block of the next grid step, another row of the batch) is in
    flight while this one is scored.  A page past the row's last, or
    before its window, inside a live block is not fetched and its
    columns are masked; a row with nothing to see costs a grid step and
    no loop turn.  ``quantized`` adds the two per-(page, slot, head)
    fp32 scale planes of ONE layer, fetched beside the pages, and
    dequantizes K/V in VMEM: the narrow bytes are what crosses HBM.

    Three bodies score a block, chosen by :func:`_decode_body`.  Few
    rows: every head of the block at once (see ``all_heads``).  Many
    (a chunk's tile): head by head, ``[rows, d] x [d, columns]`` on the
    MXU.  Either way the softmax state (``m``, ``l``, ``acc``) is read
    and written once a block.

    Grouped-query heads (``group`` > 1): ``h`` counts K/V heads and the
    q block's rows are ``(position, head of the group)``, position
    major, so row ``r`` is query position ``r // group``.  ``tq`` <
    ``q_len`` makes the second grid dimension run over tiles of ``tq``
    query positions.  ``window``: row i also loses the columns at or
    before ``kv_len - q_len + i - window``.  ``has_start``: a fourth
    prefetch operand ``start [b]``, the absolute position of the
    table's first column (a window pool's table holds only the pages
    still in the window)."""
    tq = q_len if tq is None else tq
    rows_n = group * tq
    P = pages
    chunk_pages = P if body == "per_head" else 1

    def kernel(pt_ref, kl_ref, layer_ref, *rest):
        if has_start:
            st_ref, *rest = rest
        q_ref, k_hbm, v_hbm, *rest = rest
        if quantized:
            ks_hbm, vs_hbm, *rest = rest
        o_ref, k_buf, v_buf, *rest = rest
        if quantized:
            ks_buf, vs_buf, *rest = rest
        sem, flight_ref, m_ref, l_ref, acc_ref = rest
        b_idx, t_idx = pl.program_id(0), pl.program_id(1)
        n_b, n_t = pl.num_programs(0), pl.num_programs(1)
        layer = layer_ref[0]

        def first_col(bi):
            """The table's column c is absolute position first_col + c."""
            return st_ref[bi] if has_start else 0

        def first_row(bi, ti):
            """The first query position of step (bi, ti)'s rows."""
            return kl_ref[bi] - q_len + ti * tq

        def span(bi, ti):
            """Step (bi, ti)'s first live page, its last, and how many
            blocks they make (0: nothing to see)."""
            col0, row0 = first_col(bi), first_row(bi, ti)
            # the last row sees up to its own column (which is under
            # kv_len, so garbage past the ragged end is never fetched)
            last = jax.lax.min(row0 + tq - 1 - col0,
                               p_max * page_size - 1)
            lo = 0
            if window is not None:
                # pages wholly before the first row's window score nothing
                lo = jax.lax.div(
                    jax.lax.max(row0 - window + 1 - col0, 0), page_size)
            hi = jax.lax.div(jax.lax.max(last, 0), page_size)
            n = jnp.where(last >= 0, jax.lax.div(hi - lo, P) + 1, 0)
            return lo, hi, n

        def live_pages(lo, hi, i):
            """Block ``i``'s first page and how many of its pages are
            live (the last block of a walk may end early)."""
            first = lo + i * P
            return first, jax.lax.min(P, hi - first + 1)

        def block_dma(bi, lo, hi, i, slot, wait=False):
            """Start (or wait for) the copies of block ``i``'s live
            pages into ``slot``."""
            first, live = live_pages(lo, hi, i)

            def page_dma(j, _):
                # a wait needs the copy's shape and semaphore only
                page = 0 if wait else pt_ref[bi, first + j]
                copies = [(k_hbm.at[layer, page], k_buf.at[slot, j], 0),
                          (v_hbm.at[layer, page], v_buf.at[slot, j], 1)]
                if quantized:
                    copies += [(ks_hbm.at[page], ks_buf.at[slot, j], 2),
                               (vs_hbm.at[page], vs_buf.at[slot, j], 3)]
                for src, dst, s in copies:
                    copy = pltpu.make_async_copy(src, dst, sem.at[slot, s])
                    copy.wait() if wait else copy.start()
                return 0

            jax.lax.fori_loop(0, live, page_dma, 0)

        lo, hi, n_blocks = span(b_idx, t_idx)
        row0, col0 = first_row(b_idx, t_idx), first_col(b_idx)

        @pl.when((b_idx == 0) & (t_idx == 0))
        def _():
            # flight_ref: the slot the next block lands in, and whether
            # the step before has already sent for this step's first
            flight_ref[0] = 0
            flight_ref[1] = 0
            if body != "per_page":
                # a page of a live block that is never fetched is masked
                # (its scores may be anything, its probabilities are 0),
                # but 0 x NaN is NaN: the values the slots hold have to
                # be finite from the start
                def zero(j, _):
                    for slot in range(2):
                        v_buf[slot, j] = jnp.zeros(v_buf.shape[2:],
                                                   v_buf.dtype)
                    return 0

                jax.lax.fori_loop(0, P, zero, 0)

        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        slot0 = flight_ref[0]

        @pl.when((n_blocks > 0) & (flight_ref[1] == 0))
        def _():
            block_dma(b_idx, lo, hi, 0, slot0)

        def attend(hh, q, k, v, seen):
            """One online-softmax update of state row ``hh``: q [rows,
            d] against k/v [columns, d], ``seen`` [rows, columns]."""
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, _NEG_INF)
            m_prev = m_ref[hh]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            pexp = _masked_exp(s, m_new)
            # a block whose every column is masked for some row leaves
            # that row's m at -inf: guard the rescale like _merge_parts
            alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0,
                              jnp.exp(m_prev - m_new))
            l_ref[hh] = alpha * l_ref[hh] + jnp.sum(
                pexp, axis=-1, keepdims=True)
            acc_ref[hh] = acc_ref[hh] * alpha + jax.lax.dot_general(
                pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[hh] = m_new

        def visible(pos, cols):
            """One mask does both jobs: the causal limit for the q_len
            tail AND the kv_len cutoff (query position i's limit
            kv - q_len + i is < kv, so garbage past the ragged end, in
            a page that was fetched or one that was not, never
            scores)."""
            limit = row0 + pos
            seen = cols <= limit
            if window is not None:
                seen &= cols > limit - window
            return seen

        def all_heads(slot, col_base):
            """The block as it lies, ``[pages * page_size * h, d]``:
            a column is a (token, head) pair, a row a (head, query
            row) pair, and a row sees the columns of its own head.
            One contraction scores every head against the whole block
            and one more sums the values: the MXU multiplies h times
            what it must, and nothing is sliced, looped or relaid."""
            n_cols = P * page_size * h
            row = jax.lax.broadcasted_iota(jnp.int32, (h * rows_n, 1), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (1, n_cols), 1)
            # lax.div and lax.rem: nothing here is negative, and jnp's
            # floor fix-up costs six sign() helpers a call to lower
            div, rem = jax.lax.div, jax.lax.rem
            seen = (div(row, rows_n) == rem(col, h)) & visible(
                div(rem(row, rows_n), group), col_base + div(col, h))
            attend(0, q_ref[0, 0], k_buf[slot].reshape(n_cols, d),
                   v_buf[slot].reshape(n_cols, d), seen)

        def head(buf, slot, pages, hh):
            """Head ``hh`` of the slot's ``pages``: lanes ``[hh * d,
            (hh + 1) * d)`` of a lane-dense page, a tile-aligned view;
            an index of the head axis of a head-split one."""
            if lane_dense:
                return buf[slot, pages, :, pl.ds(hh * d, d)]
            return buf[slot, pages, :, hh, :]

        def per_head(slot, j0, col_base):
            """Pages ``[j0, j0 + chunk_pages)`` of the slot, a head at
            a time: ``[rows_n, d] x [d, columns]`` is an MXU shape
            where the rows are a chunk's."""
            n_cols = chunk_pages * page_size
            pos = jax.lax.div(jax.lax.broadcasted_iota(
                jnp.int32, (rows_n, n_cols), 0), group)
            cols = col_base + jax.lax.broadcasted_iota(
                jnp.int32, (rows_n, n_cols), 1)
            seen = visible(pos, cols)
            for hh in range(h):
                q = q_ref[0, hh]          # [rows_n, d]
                if body == "per_head":
                    k = head(k_buf, slot, slice(None), hh).reshape(n_cols, d)
                    v = head(v_buf, slot, slice(None), hh).reshape(n_cols, d)
                else:
                    k = head(k_buf, slot, j0, hh)
                    v = head(v_buf, slot, j0, hh)
                if quantized:   # always page by page (_decode_body)
                    q = q.astype(jnp.float32)
                    k = (k.astype(jnp.float32)
                         * ks_buf[slot, j0, :, hh][:, None])
                    v = (v.astype(jnp.float32)
                         * vs_buf[slot, j0, :, hh][:, None])
                attend(hh, q, k, v, seen)

        def turn(i, _):
            slot = jax.lax.rem(slot0 + i, 2)

            @pl.when(i + 1 < n_blocks)
            def _():
                block_dma(b_idx, lo, hi, i + 1, 1 - slot)

            @pl.when(i + 1 == n_blocks)
            def _():
                # the last block of this step: send for the first of
                # the next step's, another tile or another row
                wrap = t_idx + 1 == n_t
                nb_ = jnp.where(wrap, b_idx + 1, b_idx)
                nt_ = jnp.where(wrap, 0, t_idx + 1)
                there = nb_ < n_b
                nb_ = jax.lax.min(nb_, n_b - 1)
                nlo, nhi, nn = span(nb_, nt_)
                sent = there & (nn > 0)

                @pl.when(sent)
                def _():
                    block_dma(nb_, nlo, nhi, 0, 1 - slot)

                flight_ref[0] = 1 - slot
                flight_ref[1] = sent.astype(jnp.int32)

            block_dma(b_idx, lo, hi, i, slot, wait=True)
            first_pg, live = live_pages(lo, hi, i)
            if body == "all_heads":
                all_heads(slot, col0 + first_pg * page_size)
            elif body == "per_head":
                per_head(slot, 0, col0 + first_pg * page_size)
            else:
                def page(j, _):
                    per_head(slot, j, col0 + (first_pg + j) * page_size)
                    return 0

                jax.lax.fori_loop(0, live, page, 0)
            return 0

        jax.lax.fori_loop(0, n_blocks, turn, 0)

        l = l_ref[...]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)

    return kernel


def _group_rows(q, group):
    """q [b, h_kv * group, q_len, d] -> [b, h_kv, q_len * group, d]:
    the rows of one K/V head, position major."""
    b, hq, q_len, d = q.shape
    q = q.reshape(b, hq // group, group, q_len, d)
    return q.transpose(0, 1, 3, 2, 4).reshape(b, hq // group,
                                              q_len * group, d)


def _ungroup_rows(o, group):
    b, h, rows, d = o.shape
    o = o.reshape(b, h, rows // group, group, d)
    return o.transpose(0, 1, 3, 2, 4).reshape(b, h * group,
                                              rows // group, d)


def _flash_decode_pallas(q, k_pages, v_pages, page_table, kv_len, scale,
                         layer, k_scale=None, v_scale=None, window=None,
                         kv_start=None):
    """q [b, h_q, q_len, d]; k_pages/v_pages [L, n_pages, page_size, h,
    d] or lane-dense [L, n_pages, page_size, h * d], of which the
    static int ``layer`` is read; page_table [b, p_max] int32 (rows
    padded with page 0); kv_len [b]; optional k_scale/v_scale [L,
    n_pages, page_size, h] fp32 (quantized pool — dequantized
    in-kernel); ``window``/``kv_start`` as in :func:`flash_decode`.
    Returns o [b, h_q, q_len, d]."""
    b, hq, q_len, d = q.shape
    lane_dense, page_size, h = _pool_layout(k_pages, d)
    group = hq // h
    p_max = page_table.shape[1]
    quantized = k_scale is not None
    has_start = kv_start is not None
    tq = _decode_q_tile(q_len, group)
    body = _decode_body(group * q_len, h, quantized, lane_dense)
    pages = _decode_pages_per_step(page_size, h, d, k_pages.dtype.itemsize,
                                   p_max, body)
    if group > 1:
        q = _group_rows(q, group)
    # the softmax state's rows: a head's, or every head's in one where
    # the kernel scores all heads at once (a free view of q)
    state = (h, group * tq)
    if body == "all_heads":
        state = (1, h * group * tq)
    grouped_shape, q = q.shape, q.reshape((b,) + state[:1] + (-1, d))
    # index maps take the grid indices, then the prefetch operands
    q_spec = pl.BlockSpec((1,) + state + (d,),
                          lambda bi, t, *_: (bi, 0, t, 0))
    pool_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [q_spec, pool_spec, pool_spec]
    operands = [q, k_pages, v_pages]
    slots = (2, pages, page_size)
    page = (h * d,) if lane_dense else (h, d)
    scratch = [pltpu.VMEM(slots + page, k_pages.dtype),
               pltpu.VMEM(slots + page, v_pages.dtype)]
    if quantized:
        # The scale planes, unlike the pages, go in one layer at a time.
        # XLA keeps an fp32 [L, n_pages, page_size, h] plane in a tiled
        # layout of its own choosing (h = 16 is a poor lane dimension:
        # row-major it pads eightfold) and Mosaic asks for the row-major
        # one, so the operand is laid out anew before every call
        # whatever is passed; of one layer that is 1/L of the work and
        # of the temporary (PERF.md, PR 28).  The padding is spelled
        # out here: a page's [page_size, h] plane cut out of HBM by a
        # DMA has to be whole lane tiles.
        lanes = -(-h // LANE) * LANE
        plane = lambda s: jnp.pad(s[layer].astype(jnp.float32),
                                  ((0, 0), (0, 0), (0, lanes - h)))
        in_specs += [pool_spec, pool_spec]
        operands += [plane(k_scale), plane(v_scale)]
        scratch += [pltpu.VMEM(slots + (lanes,), jnp.float32),
                    pltpu.VMEM(slots + (lanes,), jnp.float32)]
    prefetch = [page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
                jnp.full((1,), layer, jnp.int32)]
    if has_start:
        prefetch.append(kv_start.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, q_len // tq),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch + [
            pltpu.SemaphoreType.DMA((2, 4 if quantized else 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM(state + (1,), jnp.float32),
            pltpu.VMEM(state + (1,), jnp.float32),
            pltpu.VMEM(state + (d,), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        _make_decode_kernel(scale=scale, page_size=page_size,
                            q_len=q_len, h=h, d=d, pages=pages,
                            p_max=p_max, quantized=quantized,
                            group=group, tq=tq, window=window,
                            has_start=has_start, body=body,
                            lane_dense=lane_dense),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="flash_decode_window" if window is not None
        else "flash_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_DECODE_VMEM_LIMIT),
        interpret=use_interpret(),
    )(*prefetch, *operands).reshape(grouped_shape)
    return _ungroup_rows(o, group) if group > 1 else o


def _paged_attention_xla(q, k_pages, v_pages, page_table, kv_len, scale,
                         layer, k_scale=None, v_scale=None, window=None,
                         kv_start=None):
    """Generic baseline: gather the page list out of layer ``layer`` of
    the pool ``[L, n_pages, page_size, h, d]`` into a contiguous
    [b, p_max*page_size, h, d] KV view in HBM (XLA fuses the layer's
    slice into the gather), then plain masked attention in fp32 —
    identical math to the kernel, with the materialised gather the
    kernel exists to avoid.  The decode route's ``routing_override``
    escape hatch and the parity sweep's reference.  With
    ``k_scale``/``v_scale`` [L, n_pages, page_size, h] the pool is
    quantized: the gathered bytes are dequantized (``value * scale``,
    fp32) before scoring — same contraction the Pallas kernel runs in
    VMEM.  Grouped-query heads repeat the gathered K/V heads;
    ``window``/``kv_start`` as in :func:`flash_decode`.  A lane-dense
    pool's gathered pages are viewed ``[..., h, d]``: the same order."""
    b, hq, q_len, d = q.shape
    _, page_size, h = _pool_layout(k_pages, d)
    p_max = page_table.shape[1]
    kc = k_pages[layer][page_table]  # [b, p_max, page_size, h(, d)]
    vc = v_pages[layer][page_table]
    if k_scale is not None:
        kc = kc.astype(jnp.float32) * k_scale[layer][page_table][..., None]
        vc = vc.astype(jnp.float32) * v_scale[layer][page_table][..., None]
    kc = kc.reshape(b, p_max * page_size, h, d)
    vc = vc.reshape(b, p_max * page_size, h, d)
    if hq != h:
        kc = jnp.repeat(kc, hq // h, axis=2)
        vc = jnp.repeat(vc, hq // h, axis=2)
    s = jnp.einsum("bhqd,bkhd->bhqk", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) * scale
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    if kv_start is not None:
        cols = cols + kv_start.astype(jnp.int32)[:, None, None, None]
    limit = (kv_len.astype(jnp.int32) - q_len)[:, None, None, None] + rows
    s = jnp.where(cols <= limit, s, _NEG_INF)
    if window is not None:
        s = jnp.where(cols > limit - window, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = _masked_exp(s, m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bhqd", p, vc.astype(jnp.float32))
    return (o / jnp.where(l == 0, 1.0, l)).astype(q.dtype)


def _decode_shape_ok(q, k_pages):
    """Shape-only gate for the decode kernel (backend-independent —
    interpret mode runs it anywhere): the page's sublane extent must be
    a whole number of native tiles for the POOL dtype (the same Mosaic
    grain rule ``_pallas_ok`` applies to block_q/block_k: 8 rows at
    fp32, 16 at bf16, 32 at one-byte dtypes), and pool/head dims must
    agree.  ``k_pages`` is the whole pool or one layer of it, head-split
    or lane-dense (:func:`_pool_layout`)."""
    b, h, q_len, d = q.shape
    lane_dense, page_size, hp = _pool_layout(k_pages, d)
    grain = 32 // max(1, jnp.dtype(k_pages.dtype).itemsize)
    return (hp >= 1 and h % hp == 0 and k_pages.shape[-1] == (
        hp * d if lane_dense else d) and page_size % grain == 0
        and q_len >= 1)


def _decode_tpu_ok(q):
    """The EXTRA constraint auto-routing applies before picking the
    kernel on a real TPU: the head dim is the block's lane extent and
    must be a whole number of 128-lane tiles for Mosaic to lower the
    (page_size, h, d) K/V blocks.  Conservative by design — the
    flagship geometry (d=128) passes; a forced "decode" skips this
    (interpret mode has no lane constraint, and on-TPU forcing is the
    caller's explicit opt-in, same contract as the fwd/bwd tables)."""
    return q.shape[-1] % LANE == 0


def flash_decode_route(q, k_pages=None) -> str:
    """The route :func:`flash_decode` takes for these operands (arrays
    or ShapeDtypeStructs): "decode" (the paged Pallas kernel) or "xla"
    (the gather-based generic baseline).  The PR 5 routing-table rules
    extended to the serving path: auto routing picks the kernel only on
    TPU with an aligned page shape; ``routing_override(decode=...)``
    forces either side — a forced "decode" skips the backend check (it
    runs in interpret mode off-TPU), a forced "xla" A/Bs the generic
    baseline on identical pages."""
    forced = _ROUTE_OVERRIDE["decode"]
    if forced is not None:
        if forced == "xla":
            return "xla"
        if k_pages is not None and not _decode_shape_ok(q, k_pages):
            return "xla"
        return "decode"
    if jax.default_backend() != "tpu":
        return "xla"
    if k_pages is not None and not _decode_shape_ok(q, k_pages):
        return "xla"
    if not _decode_tpu_ok(q):
        return "xla"
    return "decode"


def flash_decode(
    q: jnp.ndarray,
    k_pages: jnp.ndarray, v_pages: jnp.ndarray,
    page_table: jnp.ndarray, kv_len: jnp.ndarray,
    *,
    scale: Optional[float] = None,
    layer: int = 0,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
    kv_start: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Decode-mode attention against a paged KV cache.

    ``q`` [b, h, q_len, d]: the last ``q_len`` positions of each
    request (q_len is 1 for plain autoregressive decode, >1 for
    speculative/chunked decode).  ``k_pages``/``v_pages``
    [L, n_pages, page_size, h, d]: the shared page pool, WHOLE, every
    layer of it, and ``layer`` (a static int) the one to read.  Pass
    the pool itself and not ``pool[layer]``: the kernel addresses the
    layer through its block index map and reads the pages where they
    lie, whereas a slice handed to a Mosaic call is first copied out,
    layer by layer (157 MB twice a layer at the 1.3B geometry).  A
    single layer's pool ``[n_pages, page_size, h, d]`` is the same
    thing at ``L = 1``.  A pool whose heads fill no sublane tile is
    kept lane-dense (:func:`lane_dense_pages`): ``[L, n_pages,
    page_size, h * d]``, the same numbers in the same order, ``h``
    read as its last axis over ``d``.  ``page_table`` [b, p_max] int32: each
    request's page list in cache order, rows padded with page 0 (the
    pool's reserved scratch page — see ``apex_tpu.serving.kv_cache``).
    ``kv_len`` [b]: valid tokens per request, INCLUDING however many
    of the ``q_len`` query rows are real; their k/v must already be
    appended to the cache.  Decode is causal by construction: query
    row i sees columns ``[0, kv_len - q_len + i]``.

    ``kv_len < q_len`` is ALLOWED and part of the contract (both
    routes guard the empty-window normalizer): rows whose causal
    window is empty (``kv_len - q_len + i < 0``) return exact zeros.
    The serving verify/chunk paths rely on this — they front-pad
    short drafts/chunks into a fixed ``q_len`` window and discard the
    pad rows' outputs (``PagedDecoder.extend``), so a row whose whole
    sequence is shorter than the window must stay finite.  Pinned by
    ``test_kv_len_shorter_than_window_is_exact_zeros``.

    Quantized pool (r17): when ``k_scale``/``v_scale``
    [L, n_pages, page_size, h] fp32 (the pool's shape less ``d``) are
    given, ``k_pages``/``v_pages`` hold quantized codes (int8 or fp8)
    and BOTH routes dequantize on read — ``code * scale`` per (page,
    slot, head), fp32 — so the narrow bytes are what crosses HBM.  Note the shape gate's grain
    rule is dtype-aware: a one-byte pool needs ``page_size % 32 == 0``
    for the Pallas route; smaller pages fall back to the XLA route,
    which runs the identical dequant math.  Scales must come in pairs
    (both or neither).  A quantized pool is head-split, never
    lane-dense.

    Grouped-query heads: the pool may hold fewer heads than ``q`` has
    (``h_q = g * h``); query head ``n`` reads pool head ``n // g``.
    ``window``: query row i sees only the ``window`` columns ending at
    its own, ``(kv_len - q_len + i - window, kv_len - q_len + i]``.
    ``kv_start`` [b] int32: the absolute position of the first column
    of each row of ``page_table`` (a multiple of the page size; 0 when
    not given) — a pool that gives pages back as a window slides hands
    over a table of the pages still held, and ``kv_len`` stays the
    request's whole length.  Pages no query row can see are skipped.

    Inference-only (no VJP — the serving path never differentiates);
    routing per :func:`flash_decode_route`, forceable via
    ``routing_override(decode=...)``.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if window is not None and window < 1:
        raise ValueError(f"window {window} must be >= 1")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    lane_dense = _pool_layout(k_pages, q.shape[-1])[0]
    if lane_dense and k_scale is not None:
        raise ValueError("a quantized pool is [..., h, d], not lane-dense")
    if k_pages.ndim == 4 and not lane_dense:
        # one layer's pool: the whole-pool form at L = 1
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    if not 0 <= layer < k_pages.shape[0]:
        # a block index is not bounds-checked on the chip
        raise ValueError(f"layer {layer} is not one of the pool's "
                         f"{k_pages.shape[0]}")
    kv_len = jnp.asarray(kv_len, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    attend = (_flash_decode_pallas
              if flash_decode_route(q, k_pages) == "decode"
              else _paged_attention_xla)
    return attend(q, k_pages, v_pages, page_table, kv_len, float(scale),
                  layer, k_scale=k_scale, v_scale=v_scale, window=window,
                  kv_start=kv_start)


# ---------------------------------------------------------------------------
# Paged decode over a LATENT page (ISSUE 33): one vector a token, shared
# by every query head, whose first ``v_dim`` numbers are also the value
# (multi-head latent attention in its absorbed form).  K and V are one
# operand: a page crosses HBM once and is scored and summed from the
# same VMEM slot.  ``h`` query heads over one key is grouped-query
# attention with one K/V head, so a row of the batch brings ``h * q_len``
# query rows to every column: at 128 heads the call sits at the ridge
# of the roofline at one query a row and is compute-bound beyond it, and
# every contraction is ``[rows, width] x [width, columns]`` on the MXU.
# The page walk, the two slots and the hand-over of the first block to
# the step before are ``_make_decode_kernel``'s.
#
# A grid step takes a TILE (ISSUE 36): at one query a row, up to
# ``_LATENT_GROUP`` rows of the batch whose page tables start with the
# same blocks.  The blocks they share are fetched once and scored by
# one product over all the tile's query rows (the shared walk); then
# each row walks what is left of its own table with its own rows of the
# same running max, sum and accumulator (the tail walk).  A row that
# shares with nobody is a tile of one and a shared walk of no block: the
# tail walk alone, which is also how a chunk (``q_len > 1``) runs, one
# tile of ``_latent_q_tile`` positions a step.  What decides a tile is
# the page tables (``latent_walk_tiles``); nothing is configured.
# ---------------------------------------------------------------------------

# Query rows (position, head) a grid step of the latent kernel takes at
# most, and the most columns of a block's score tile (on the v5e, 64
# rows at 17k of context: 3.6 / 3.0 / 3.1 ms a call at 512 / 1,024 /
# 2,048 columns; a 512-token chunk 19.1 / 16.1 / 16.9 ms at 256 / 512 /
# 1,024 rows; skipping the mask on blocks wholly under the causal edge
# made both slower; PERF.md, PR 33).  Why a tile (PERF.md, PR 36; 128
# heads, 64 rows over 8 documents of 16,384 tokens): the same 0.30 TFLOP
# took 2.83 ms as 64 steps of 128 query rows and 2.12 ms as 16 steps of
# 512, each against one fetched block; walked in tiles a call takes
# 2.17 ms (documents of 8 rows), 2.27 (of 3), 2.43 (of 2), 2.92 where
# no page is shared (2.83 before the tiles: a turn's cost not found),
# a chunk 16.5 (16.3).  A shared product as wide as the tile has rows:
# tiles of 2 and 3 scored 512 rows wide took 4.11 and 2.83 ms.
_LATENT_ROWS = 512
_LATENT_BLOCK_COLS = 1024
# Rows of the batch a tile takes at most (and no more than fit
# ``_LATENT_ROWS``).
_LATENT_GROUP = 4


def _latent_q_tile(q_len, heads):
    """Query positions a grid step takes: the largest halving of
    ``q_len`` whose ``heads * tq`` rows fit ``_LATENT_ROWS``."""
    tq = q_len
    while heads * tq > _LATENT_ROWS and tq % 2 == 0:
        tq //= 2
    return tq


def _latent_geometry(q_len, heads, page_size, p_max):
    """(tq, group, pages): query positions a unit of work takes, units a
    tile takes at most, pages a block."""
    tq = _latent_q_tile(q_len, heads)
    group = (max(1, min(_LATENT_GROUP, _LATENT_ROWS // heads))
             if q_len == 1 else 1)
    pages = max(1, min(_LATENT_BLOCK_COLS // page_size, p_max))
    return tq, group, pages


class LatentTiles(NamedTuple):
    """What a call of the latent kernel walks, tile by tile.  A UNIT is
    a row of the batch at ``tq`` of its query positions (``n_units = b *
    q_len // tq``, row major).  ``members [n_units * group]``: tile
    ``t``'s units at ``[t * group, t * group + count[t])`` (a slot
    after them: some unit); ``count [n_units]``: 0 from the first
    tile that holds nothing; ``shared [n_units]``: the leading blocks
    the tile's units walk together.  ``walked`` / ``fetched``: the
    blocks the units must see, summed, and the block DMAs this walk
    issues for them."""
    members: jnp.ndarray
    count: jnp.ndarray
    shared: jnp.ndarray
    walked: jnp.ndarray
    fetched: jnp.ndarray


def latent_walk_tiles(page_table, kv_len, *, q_len: int, heads: int,
                      page_size: int, q_start=None) -> LatentTiles:
    """The tiles of one :func:`flash_decode_latent` call, from what the
    call receives.  At one query a row: rows whose tables start with
    the same page become neighbours (rows with nothing to see last),
    cut into tiles of at most ``group`` that never hold two first
    pages; a tile's shared run is the leading blocks on which every row
    holds its first row's page ids and which lie wholly under every
    row's ``kv_len``.  Otherwise every unit is its own tile."""
    page_table = jnp.asarray(page_table, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    b, p_max = page_table.shape
    tq, group, pages = _latent_geometry(q_len, heads, page_size, p_max)
    n_cols = pages * page_size
    n_t = q_len // tq
    # the blocks a unit must see: ``span`` of the kernel
    ti = jnp.arange(n_t, dtype=jnp.int32)[None]
    last = jnp.minimum(kv_len[:, None] - q_len + (ti + 1) * tq - 1,
                       p_max * page_size - 1)
    live = last >= 0
    if q_start is not None:
        live &= (ti + 1) * tq > jnp.asarray(q_start, jnp.int32)[:, None]
    blocks = jnp.where(live, jnp.maximum(last, 0) // n_cols + 1,
                       0).reshape(-1)
    n_units = b * n_t
    unit = jnp.arange(n_units, dtype=jnp.int32)
    walked = jnp.sum(blocks)
    if group == 1:
        return LatentTiles(unit, jnp.ones_like(unit), jnp.zeros_like(unit),
                           walked, walked)
    # compared all against all (``b`` is a batch's rows: no sort, no
    # scatter): ``[i, j]`` is about row ``i`` and row ``j``
    key = jnp.where(kv_len > 0, page_table[:, 0],
                    jnp.iinfo(jnp.int32).max)
    alike = key[:, None] == key[None, :]
    earlier = alike & (unit[None, :] < unit[:, None])
    place = jnp.sum(earlier, axis=1) % group    # its slot in its tile
    opens = place == 0
    before = (key[None, :] < key[:, None]) | earlier
    tile_of = jnp.sum(before & opens[None, :], axis=1) + opens - 1
    inside = tile_of[None, :] == unit[:, None]          # [tile, row]
    count = jnp.sum(inside, axis=1, dtype=jnp.int32)
    slot = jnp.arange(group, dtype=jnp.int32)
    holds = inside[:, None, :] & (place[None, None, :] == slot[None, :, None])
    members = jnp.sum(jnp.where(holds, unit[None, None, :], 0), axis=2)
    # a slot past a tile's count keeps the unit of the tile before: a
    # query block whose index does not move is not copied again
    held = jnp.max(jnp.where(
        (slot[None, None, :] < count[None, :, None])
        & (unit[None, :, None] <= unit[:, None, None]),
        unit[None, :, None], 0), axis=1)
    members = jnp.take_along_axis(members, held, axis=0)
    whole = p_max // pages
    first = page_table[members[tile_of, 0]]
    same = (page_table[:, :whole * pages]
            == first[:, :whole * pages]).reshape(b, whole, pages).all(-1)
    run = jnp.minimum(
        jnp.min(jnp.where(same, whole, jnp.arange(whole)[None]), axis=1),
        kv_len // n_cols)
    shared = jnp.min(jnp.where(inside, run[None, :], whole), axis=1)
    shared = jnp.where(count > 1, shared, 0)
    fetched = walked - jnp.sum(shared * jnp.maximum(count - 1, 0))
    return LatentTiles(members.reshape(-1), count, shared, walked, fetched)


def _make_latent_decode_kernel(*, scale, page_size, q_len, heads, width,
                               v_dim, pages, p_max, tq, group):
    """grid (n_units,): a step is one tile of :class:`LatentTiles`.
    Scalar prefetch (page_table [b, p_max], kv_len [b], layer [1],
    q_start [b], members, count, shared).  ``q`` comes as ``group``
    blocks ``[rows, width]``, one a member, ``rows = tq * heads``
    position major (row ``r`` is query position ``r // heads``); the
    pool and the output stay in HBM.  A step walks its shared blocks
    once for all its members, then each member's own, ``pages`` pages a
    turn, each one DMA ``[page_size, width]`` into one of two slots;
    the turns of a step are one sequence of fetches, and its last
    sends the next step's first.  A unit whose query positions all lie
    before ``q_start`` (the front padding of a chunk) walks nothing."""
    R = heads * tq
    G, P = group, pages
    n_cols = P * page_size
    n_t = q_len // tq

    def kernel(pt_ref, kl_ref, layer_ref, qs_ref, mem_ref, cnt_ref, sh_ref,
               *refs):
        q_refs, (kv_hbm, o_hbm, kv_buf, sem, flight_ref, q_all, m_ref,
                 l_ref, acc_ref, o_buf) = refs[:G], refs[G:]
        t_idx, n_tiles = pl.program_id(0), pl.num_programs(0)
        layer = layer_ref[0]

        def pick(g, xs):
            """``xs[g]`` of a short list of scalars."""
            out = xs[0]
            for j in range(1, len(xs)):
                out = jnp.where(g == j, xs[j], out)
            return out

        def plan(t):
            """Tile ``t``'s walk: the shared run, where in the step's
            sequence of fetches each member's own blocks start (the
            last entry: how many fetches in all), and each member's
            row of the batch, first query row and last live page."""
            shared, count = sh_ref[t], cnt_ref[t]
            at, bis, row0s, his = [shared], [], [], []
            for g in range(G):
                u = mem_ref[t * G + g]
                bi, ti = (u, 0) if n_t == 1 else (jax.lax.div(u, n_t),
                                                  jax.lax.rem(u, n_t))
                row0 = kl_ref[bi] - q_len + ti * tq
                last = jax.lax.min(row0 + tq - 1, p_max * page_size - 1)
                hi = jax.lax.div(jax.lax.max(last, 0), page_size)
                live = ((g < count) & (last >= 0)
                        & ((ti + 1) * tq > qs_ref[bi]))
                own = jnp.where(
                    live, jax.lax.max(jax.lax.div(hi, P) + 1 - shared, 0), 0)
                at.append(at[-1] + own)
                bis.append(bi)
                row0s.append(row0)
                his.append(hi)
            return at, bis, row0s, his

        def fetch(walk, k):
            """The ``k``-th fetch of a step: (row of the batch, block,
            live pages of it)."""
            at, bis, _, his = walk
            g = sum(((k >= at[j]).astype(jnp.int32) for j in range(1, G)),
                    jnp.int32(0))
            block = at[0] + k - pick(g, at[:G])
            return (pick(g, bis), block,
                    jax.lax.min(P, pick(g, his) - block * P + 1))

        def block_dma(bi, block, live, slot, wait=False):
            first = block * P

            def page_dma(j, _):
                page = 0 if wait else pt_ref[bi, first + j]
                copy = pltpu.make_async_copy(
                    kv_hbm.at[layer, page], kv_buf.at[slot, j],
                    sem.at[slot])
                copy.wait() if wait else copy.start()
                return 0

            jax.lax.fori_loop(0, live, page_dma, 0)

        def out_dma(g, wait=False):
            home = 0 if wait else mem_ref[t_idx * G + g]
            copy = pltpu.make_async_copy(o_buf.at[g], o_hbm.at[home],
                                         sem.at[2])
            copy.wait() if wait else copy.start()
            return 0

        def land():
            """Wait for the rows the step before sent home."""
            jax.lax.fori_loop(0, flight_ref[2],
                              lambda g, _: out_dma(g, wait=True), 0)
            flight_ref[2] = 0

        @pl.when(t_idx == 0)
        def _():
            flight_ref[0] = 0
            flight_ref[1] = 0
            flight_ref[2] = 0

            # a page of a live block that is never fetched is masked,
            # but 0 x NaN is NaN: the slots start finite
            def zero(j, _):
                for slot in range(2):
                    kv_buf[slot, j] = jnp.zeros(kv_buf.shape[2:],
                                                kv_buf.dtype)
                return 0

            jax.lax.fori_loop(0, P, zero, 0)

        @pl.when(cnt_ref[t_idx] > 0)
        def _():
            walk = plan(t_idx)
            shared, total = walk[0][0], walk[0][G]
            count = cnt_ref[t_idx]

            # what a step does once a member it does for the members it
            # has: the rows of the others hold what they held, and a
            # product's output row depends on its own query row only
            def members(do, where=True):
                for g in range(G):
                    pl.when((g < count) & where)(functools.partial(do, g))

            def start(g):
                q_all[g] = q_refs[g][0]
                m_ref[g] = jnp.full((R, 1), _NEG_INF, jnp.float32)
                l_ref[g] = jnp.zeros((R, 1), jnp.float32)
                acc_ref[g] = jnp.zeros((R, v_dim), jnp.float32)

            # a tile with nothing to see (a chunk's front padding, rows
            # without a token) only sends zeros home
            members(start, total > 0)

            slot0 = flight_ref[0]

            @pl.when((total > 0) & (flight_ref[1] == 0))
            def _():
                block_dma(*fetch(walk, 0), slot0)

            # the step after this one: its first block is sent for by
            # this step's last turn
            after = plan(jax.lax.min(t_idx + 1, n_tiles - 1))
            sent = (t_idx + 1 < n_tiles) & (after[0][G] > 0)
            ahead = fetch(after, 0)

            pos = jax.lax.div(jax.lax.broadcasted_iota(
                jnp.int32, (R, n_cols), 0), heads)
            col = jax.lax.broadcasted_iota(jnp.int32, (R, n_cols), 1)

            def run(k0, n, bi, hi, block0, who, row0=None):
                """``n`` turns from the step's ``k0``-th fetch on: the
                blocks from ``block0`` of row ``bi``'s table, scored by
                the query rows of ``who``: one member, or a slice of
                them, as one product, unmasked (no first query row is
                given: a shared block lies wholly under every member's
                limit)."""
                then = fetch(walk, k0 + n)

                def get(ref):
                    x = ref[who]
                    return x.reshape(-1, x.shape[-1])

                def put(ref, x):
                    ref[who] = (x.reshape(-1, R, x.shape[-1])
                                if isinstance(who, slice) else x)

                def turn(i, _):
                    k, block = k0 + i, block0 + i
                    slot = jax.lax.rem(slot0 + k, 2)

                    @pl.when(i + 1 < n)
                    def _():
                        block_dma(bi, block + 1, jax.lax.min(
                            P, hi - (block + 1) * P + 1), 1 - slot)

                    @pl.when(i + 1 == n)
                    def _():
                        @pl.when(k + 1 < total)
                        def _():
                            block_dma(*then, 1 - slot)

                        @pl.when(k + 1 == total)
                        def _():
                            @pl.when(sent)
                            def _():
                                block_dma(*ahead, 1 - slot)

                            flight_ref[0] = 1 - slot
                            flight_ref[1] = sent.astype(jnp.int32)

                    block_dma(0, 0, jax.lax.min(P, hi - block * P + 1),
                              slot, wait=True)
                    # the block serves as K, whole, and its first v_dim
                    # lanes as V
                    kv = kv_buf[slot].reshape(n_cols, width)
                    s = jax.lax.dot_general(
                        get(q_all), kv, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    if row0 is not None:
                        # the causal limit of the q_len tail is also the
                        # kv_len cutoff: a column past it, fetched or
                        # not, never scores
                        s = jnp.where(block * n_cols + col <= row0 + pos,
                                      s, _NEG_INF)
                    # the running max is of the scores before ``scale``:
                    # a difference times the scale is the same
                    # arithmetic with the mask between or without it (a
                    # product and a difference may be fused into one
                    # rounding, or not)
                    m_prev = get(m_ref)
                    m_new = jnp.maximum(
                        m_prev, jnp.max(s, axis=-1, keepdims=True))
                    pexp = jnp.where(m_new <= _NEG_INF / 2, 0.0,
                                     jnp.exp((s - m_new) * scale))
                    alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0,
                                      jnp.exp((m_prev - m_new) * scale))
                    put(l_ref, alpha * get(l_ref) + jnp.sum(
                        pexp, axis=-1, keepdims=True))
                    put(acc_ref, get(acc_ref) * alpha + jax.lax.dot_general(
                        pexp.astype(kv.dtype), kv[:, :v_dim],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                    put(m_ref, m_new)
                    return 0

                jax.lax.fori_loop(0, n, turn, 0)

            at, bis, row0s, his = walk

            def tail(g, _):
                k0 = pick(g, at[:G])
                run(k0, pick(g, at[1:]) - k0, pick(g, bis), pick(g, his),
                    shared, g, pick(g, row0s))
                return 0

            if G == 1:
                tail(0, 0)
            else:
                # the shared product is as many rows as the tile has
                for c in range(2, G + 1):
                    pl.when(count == c)(functools.partial(
                        run, 0, shared, bis[0], his[0], 0, slice(0, c)))
                jax.lax.fori_loop(0, count, tail, 0)

            land()

            def finish(g):
                l = l_ref[g]
                o_buf[g] = (acc_ref[g] / jnp.where(l == 0, 1.0, l)).astype(
                    o_buf.dtype)
                out_dma(g)

            def blank(g):
                o_buf[g] = jnp.zeros((R, v_dim), o_buf.dtype)
                out_dma(g)

            members(finish, total > 0)
            members(blank, total == 0)
            flight_ref[2] = count

        @pl.when(t_idx + 1 == n_tiles)
        def _():
            land()

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "scale", "v_dim", "tq", "group", "pages", "interpret"))
def _flash_decode_latent_pallas(q, kv_pages, page_table, kv_len, layer,
                                q_start, tiles, *, scale, v_dim, tq, group,
                                pages, interpret):
    """A jit of its own, ``layer [1]`` an operand: the calls of all of
    a model's layers share one tracing and one lowering of the kernel
    (six of them cost a decode executable 9.9 s of tracing on the
    chip's host where this costs it one; XLA inlines the calls).  What
    a trace depends on besides its operands' shapes is static."""
    b, q_len, heads, width = q.shape
    page_size = kv_pages.shape[2]
    p_max = page_table.shape[1]
    rows = heads * tq
    n_units = b * q_len // tq
    q_specs = [pl.BlockSpec(
        (1, rows, width),
        lambda t, pt, kl, la, qs, members, *_, g=g: (
            members[t * group + g], 0, 0)) for g in range(group)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n_units,),
        in_specs=q_specs + [pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        scratch_shapes=[
            pltpu.VMEM((2, pages, page_size, width), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SMEM((3,), jnp.int32),
            pltpu.VMEM((group, rows, width), q.dtype),
            pltpu.VMEM((group, rows, 1), jnp.float32),
            pltpu.VMEM((group, rows, 1), jnp.float32),
            pltpu.VMEM((group, rows, v_dim), jnp.float32),
            pltpu.VMEM((group, rows, v_dim), q.dtype),
        ],
    )
    q = q.reshape(n_units, rows, width)
    o = pl.pallas_call(
        _make_latent_decode_kernel(
            scale=scale, page_size=page_size, q_len=q_len, heads=heads,
            width=width, v_dim=v_dim, pages=pages, p_max=p_max, tq=tq,
            group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_units, rows, v_dim), q.dtype),
        name="flash_decode_latent",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_VMEM_LIMIT),
        interpret=interpret,
    )(page_table, kv_len, layer, q_start, tiles.members, tiles.count,
      tiles.shared, *[q] * group, kv_pages)
    return o.reshape(b, q_len, heads, v_dim)


def _paged_latent_attention_xla(q, kv_pages, page_table, kv_len, scale,
                                layer, v_dim):
    """The generic baseline: the row's pages gathered into one
    ``[b, p_max * page_size, width]`` view, plain masked attention in
    float32, the same mathematics as the kernel (it computes a chunk's
    front padding like the other rows, and every row against its own
    view, shared pages or not)."""
    b, q_len, heads, width = q.shape
    kc = kv_pages[layer][page_table].reshape(b, -1, width).astype(
        jnp.float32)
    s = jnp.einsum("bqhd,bkd->bhqk", q.astype(jnp.float32), kc) * scale
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    limit = (kv_len - q_len)[:, None, None, None] + rows
    s = jnp.where(cols <= limit, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = _masked_exp(s, m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkd->bqhd", p / jnp.where(l == 0, 1.0, l),
                   kc[..., :v_dim])
    return o.astype(q.dtype)


def flash_decode_latent_route(q, kv_pages) -> str:
    """``"decode"`` (the Pallas kernel) or ``"xla"``: as
    :func:`flash_decode_route`, and ``routing_override(decode=...)``
    forces either.  The kernel wants pages of whole sublane tiles and a
    value that is whole lane tiles of the vector."""
    forced = _ROUTE_OVERRIDE["decode"]
    if forced == "xla":
        return "xla"
    grain = 32 // max(1, jnp.dtype(kv_pages.dtype).itemsize)
    if kv_pages.shape[2] % grain:
        return "xla"
    if forced is None and jax.default_backend() != "tpu":
        return "xla"
    return "decode"


def flash_decode_latent(q, kv_pages, page_table, kv_len, *, v_dim: int,
                        scale: float, layer: int = 0, q_start=None,
                        tiles: Optional[LatentTiles] = None):
    """Decode-mode attention against a paged LATENT cache: every query
    head scores the same key, and the key's first ``v_dim`` numbers are
    the value.

    ``q`` ``[b, q_len, heads, width]``: the last ``q_len`` positions of
    each request, position major (as a projection leaves them; no
    transpose on either side).  ``kv_pages`` ``[L, n_pages, page_size,
    width]``: the one pool, whole, of which the static ``layer`` is
    read.  ``page_table`` ``[b, p_max]`` and ``kv_len`` ``[b]`` as in
    :func:`flash_decode`: the tokens' vectors are already appended, row
    ``i`` sees columns ``[0, kv_len - q_len + i]``, a row with nothing
    to see returns zeros.  ``q_start`` ``[b]`` (0 where not given): the
    query positions before it are a chunk's front padding, and what is
    returned for them is unspecified (the kernel skips the steps that
    hold nothing else and returns zeros there).  ``tiles``: what
    :func:`latent_walk_tiles` makes of these tables, for a caller that
    makes it once for the calls of all its layers; a row's output does
    not depend on its tile.  Returns ``[b, q_len, heads, v_dim]``: the
    probabilities' sum over the values, not yet projected up."""
    if not 0 <= layer < kv_pages.shape[0]:
        raise ValueError(f"layer {layer} is not one of the pool's "
                         f"{kv_pages.shape[0]}")
    if q.shape[-1] != kv_pages.shape[-1] or v_dim > q.shape[-1]:
        raise ValueError(
            f"query width {q.shape[-1]}, page width {kv_pages.shape[-1]}, "
            f"value width {v_dim}")
    kv_len = jnp.asarray(kv_len, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    q_start = (jnp.zeros_like(kv_len) if q_start is None
               else jnp.asarray(q_start, jnp.int32))
    if flash_decode_latent_route(q, kv_pages) != "decode":
        return _paged_latent_attention_xla(
            q, kv_pages, page_table, kv_len, float(scale), layer, v_dim)
    q_len, heads = q.shape[1:3]
    page_size = kv_pages.shape[2]
    if tiles is None:
        tiles = latent_walk_tiles(page_table, kv_len, q_len=q_len,
                                  heads=heads, page_size=page_size,
                                  q_start=q_start)
    tq, group, pages = _latent_geometry(q_len, heads, page_size,
                                        page_table.shape[1])
    return _flash_decode_latent_pallas(
        q, kv_pages, page_table, kv_len, jnp.full((1,), layer, jnp.int32),
        q_start, tiles, scale=float(scale), v_dim=v_dim, tq=tq, group=group,
        pages=pages, interpret=use_interpret())


# ---------------------------------------------------------------------------
# Ring attention — sequence/context parallelism over a mesh axis
# ---------------------------------------------------------------------------


def _ring_fwd_pass(q, k, v, axis_name, causal, scale):
    world = jax.lax.psum(1, axis_name)  # folds to a constant at trace time
    rank = jax.lax.axis_index(axis_name)
    bh, s_local, d = q.shape
    q32 = q.astype(jnp.float32) * scale
    q_start = rank * s_local
    perm = [(i, (i + 1) % world) for i in range(world)]

    def step(carry, _):
        m, l, acc, kc, vc, src = carry
        s = jnp.einsum("bqd,bkd->bqk", q32, kc.astype(jnp.float32))
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (s_local, s_local), 0)
            cols = src * s_local + jax.lax.broadcasted_iota(
                jnp.int32, (s_local, s_local), 1)
            s = jnp.where((rows >= cols)[None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = _masked_exp(s, m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqk,bkd->bqd", p, vc.astype(jnp.float32))
        # rotate K/V to the next device; track the owner of the new chunk
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        src = jax.lax.rem(src - 1 + world, world)
        return (m_new, l, acc, kc, vc, src), None

    m0 = jnp.full((bh, s_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, s_local), jnp.float32)
    acc0 = jnp.zeros((bh, s_local, d), jnp.float32)
    (m, l, acc, _, _, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v, rank), jnp.arange(world))
    l_safe = jnp.where(l == 0, 1.0, l)
    o = (acc / l_safe[..., None]).astype(q.dtype)
    lse = jnp.where(l == 0, _NEG_INF, m + jnp.log(l_safe))
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_attention(q, k, v, axis_name, causal, scale):
    o, _ = _ring_fwd_pass(q, k, v, axis_name, causal, scale)
    return o


def _ring_fwd_rule(q, k, v, axis_name, causal, scale):
    o, lse = _ring_fwd_pass(q, k, v, axis_name, causal, scale)
    return o, (q, k, v, o, lse)


def _ring_bwd_rule(axis_name, causal, scale, res, do):
    """Second ring pass: each (k, v) chunk travels the ring again together
    with its (dk, dv) accumulators; every device adds its queries'
    contribution to the visiting chunk's gradients while accumulating its
    own dq.  After ``world`` hops the chunk — gradients complete — is
    home.  Nothing is saved per hop, so live memory is O(s_local),
    independent of world size (VERDICT r1 weak #4)."""
    q, k, v, o, lse = res
    world = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    bh, s_local, d = q.shape
    q32 = q.astype(jnp.float32)
    do32 = do.astype(jnp.float32)
    delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)  # [bh, s_local]
    q_start = rank * s_local
    perm = [(i, (i + 1) % world) for i in range(world)]

    def step(carry, _):
        dq, kc, vc, dkc, dvc, src = carry
        kc32 = kc.astype(jnp.float32)
        s = jnp.einsum("bqd,bkd->bqk", q32, kc32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (s_local, s_local), 0)
            cols = src * s_local + jax.lax.broadcasted_iota(
                jnp.int32, (s_local, s_local), 1)
            s = jnp.where((rows >= cols)[None], s, _NEG_INF)
        p = _masked_exp(s, lse[..., None])
        dvc = dvc + jnp.einsum("bqk,bqd->bkd", p, do32)
        dp = jnp.einsum("bqd,bkd->bqk", do32, vc.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dkc = dkc + jnp.einsum("bqk,bqd->bkd", ds, q32)
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kc32)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        dkc = jax.lax.ppermute(dkc, axis_name, perm)
        dvc = jax.lax.ppermute(dvc, axis_name, perm)
        src = jax.lax.rem(src - 1 + world, world)
        return (dq, kc, vc, dkc, dvc, src), None

    dq0 = jnp.zeros((bh, s_local, d), jnp.float32)
    acc0 = jnp.zeros((bh, s_local, d), jnp.float32)
    (dq, _, _, dk, dv, _), _ = jax.lax.scan(
        step, (dq0, k, v, acc0, acc0, rank), jnp.arange(world))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_attention.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def ring_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Attention with the sequence axis sharded over ``axis_name``.

    Each device holds its local q/k/v chunk [bh, s_local, d]; K/V chunks
    rotate around the ring with ``lax.ppermute`` while every device
    accumulates its queries' attention over each arriving block with the
    same online-softmax combination the flash kernel uses.  After
    ``world`` steps every query has attended to the full sequence.

    Causal masking uses *global* positions: device r's queries own rows
    ``[r·s_local, (r+1)·s_local)``.

    Must run inside a region binding ``axis_name``.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _ring_attention(q, k, v, axis_name, bool(causal), float(scale))

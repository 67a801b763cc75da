"""GPT-style decoder with a paged KV cache — the serving engine's
model half.

Two entry points mirror the two phases of continuous batching:

* :meth:`PagedDecoder.prefill` — the ADMISSION path.  A fixed-width
  packed token row with segment ids (the PR 5 varlen packed path:
  cross-segment tiles are masked in-kernel and skipped by the
  block-skip index on TPU) — one fixed-shape forward per call, no
  recompiles.  The row format carries ANY number of segments, but the
  engine feeds ONE request per row: a multi-segment row is not
  offset-invariant at the last ulp (the attention contraction's
  reduction grouping depends on where a segment starts), which would
  break the engine's bitwise batched-vs-sequential contract — see
  ``engine.py`` "The isolation contract".  It returns per-layer K/V
  for every packed position; the engine scatters them into the page
  pool.
* :meth:`PagedDecoder.decode` — the STEADY-STATE path.  One token per
  running request: append the token's K/V into its current page, then
  attend over the request's page list via
  :func:`~apex_tpu.ops.flash_decode` (the r8 decode route).  Batch
  width is fixed at the engine's ``max_batch`` with idle rows masked,
  so this too is one compiled step for the whole serving lifetime.
* :meth:`PagedDecoder.extend` — the MULTI-TOKEN cache-extension path
  (ISSUE 12): ``q`` tokens per request through ONE
  :func:`~apex_tpu.ops.flash_decode` call at ``q_len = q``.  Both
  halves of the draft–verify subsystem are this method under two
  fixed shapes: speculative VERIFY (``[max_batch, k + 1]`` — the last
  committed token plus the draft, all scored in one launch) and
  CHUNKED PREFILL (``[1, chunk_size]`` — one chunk of a long context
  against the pages already filled by earlier chunks).  Rows are
  front-padded so the valid tokens are always the LAST rows of the
  window — that is what keeps ``flash_decode``'s causal alignment
  (query row i sees columns ``[0, kv_len - q_len + i]``) exact for
  partial drafts/chunks without a second mask operand.  K/V write
  targets are HOST-computed ``(page, offset)`` arrays (the same idiom
  as ``PagedKVCache.write_tokens``), so padding rows scatter into the
  scratch page instead of a live slot.

Per-row independence is a hard contract: every op in ``decode`` is
row-wise (embedding lookup, layer norm, per-row matmuls, paged
attention over the row's own page list), which is what makes batched
continuous decoding produce bit-identical tokens to one-request-at-a-
time decoding — the scheduler composes batches freely without
perturbing anyone's output.

r17 adds two orthogonal execution modes, both threaded through the
same three methods:

* **Tensor parallelism** (``tp_axis=...``): the methods are written to
  run INSIDE ``shard_map`` over a mesh axis, Megatron-style — wqkv/w1
  column-sharded (each shard owns a head slice; see
  :func:`shard_params_tp` for the wqkv column reorder that keeps the
  in-method ``jnp.split`` correct), wo/w2 row-sharded, embeddings and
  layer norms replicated.  The head count is derived from the LOCAL
  shard shapes, the paged pool shards on its head axis, and each
  block contributes its partial residual via ONE ``lax.psum`` — the
  only collectives on the decode hot path (pinned by the HLO
  contract registry).  Note batched==sequential stays bitwise WITHIN
  a tp config (same executable, same reduction grouping); tp=1 vs
  tp=2 outputs differ at the last ulp like any re-grouped reduction.
* **Quantized pool** (``k_scale``/``v_scale`` given): appends
  quantize-on-write (:func:`~apex_tpu.serving.kv_cache.
  quantize_tokens` — per-(token, head) scales, order-independent) and
  reads dequantize-in-kernel via ``flash_decode``'s scale operands.
  Scales shard on their head axis exactly like the pool, so the two
  modes compose with no extra collectives.

The parameter layout is a plain pytree (:func:`init_params`) with tied
embeddings; fp32 by default (the serving tests pin bitwise claims),
bf16 for TPU throughput via ``ServingModelConfig(dtype=...)``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops import flash_attention, flash_decode
from apex_tpu.serving.kv_cache import quantize_tokens


def quant_qmax(dtype) -> float:
    """qmax for a quantized pool's code dtype (int8 -> 127, fp8 e4m3
    -> 448) — lets the model derive the grid from the pool it is
    handed instead of carrying a second config knob."""
    if np.dtype(dtype) == np.dtype(np.int8):
        return 127.0
    return 448.0


def shard_params_tp(params, tp: int):
    """Reorder each layer's fused ``wqkv`` [h, 3h] into SHARD-MAJOR
    column blocks ``[q_0|k_0|v_0 | q_1|k_1|v_1 | ...]`` so that
    column-sharding it over ``tp`` devices hands shard j exactly its
    head slice of all three projections — the in-method
    ``jnp.split(qkv, 3, -1)`` then works unchanged on the local block.
    Plain column sharding of the unreordered fusion would give shard 0
    a slab of pure-q columns instead.  Returns a NEW pytree (host-side
    numpy reorder, done once at engine init); ``tp=1`` returns the
    params untouched."""
    if tp == 1:
        return params
    out = dict(params)
    out["layers"] = []
    for layer in params["layers"]:
        w = np.asarray(layer["wqkv"])
        h = w.shape[0]
        if h % tp:
            raise ValueError(f"hidden_size {h} not divisible by tp={tp}")
        wq, wk, wv = np.split(w, 3, axis=1)
        blocks = []
        for j in range(tp):
            sl = slice(j * h // tp, (j + 1) * h // tp)
            blocks += [wq[:, sl], wk[:, sl], wv[:, sl]]
        new = dict(layer)
        new["wqkv"] = jnp.asarray(np.concatenate(blocks, axis=1),
                                  w.dtype)
        out["layers"].append(new)
    return out


@dataclasses.dataclass(frozen=True)
class ServingModelConfig:
    """Decoder geometry.  ``max_position`` bounds the learned position
    table — admission must reject requests that could outgrow it."""

    vocab_size: int = 256
    hidden_size: int = 64
    num_heads: int = 4
    num_layers: int = 2
    max_position: int = 512
    mlp_ratio: int = 4
    dtype: object = jnp.float32

    @property
    def head_dim(self) -> int:
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must divide by num_heads")
        return self.hidden_size // self.num_heads


def init_params(cfg: ServingModelConfig, seed: int = 0):
    """Deterministic parameter pytree (scaled-normal init, tied LM
    head = embedding transpose)."""
    keys = jax.random.split(jax.random.PRNGKey(seed),
                            2 + 4 * cfg.num_layers)
    h, r = cfg.hidden_size, cfg.mlp_ratio
    dt = cfg.dtype

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    params = {
        "embed": norm(keys[0], (cfg.vocab_size, h), h),
        "pos": norm(keys[1], (cfg.max_position, h), h),
        "ln_f": {"g": jnp.ones((h,), dt), "b": jnp.zeros((h,), dt)},
        "layers": [],
    }
    for i in range(cfg.num_layers):
        k = keys[2 + 4 * i: 6 + 4 * i]
        params["layers"].append({
            "ln1": {"g": jnp.ones((h,), dt), "b": jnp.zeros((h,), dt)},
            "wqkv": norm(k[0], (h, 3 * h), h),
            "wo": norm(k[1], (h, h), h),
            "ln2": {"g": jnp.ones((h,), dt), "b": jnp.zeros((h,), dt)},
            "w1": norm(k[2], (h, r * h), h),
            "w2": norm(k[3], (r * h, h), r * h),
        })
    return params


def _ln(x, p):
    m = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(var + 1e-5) * p["g"] + p["b"]


def _mlp(x, layer):
    return jax.nn.gelu(x @ layer["w1"]) @ layer["w2"]


class PagedDecoder:
    """The decoder model over the cache layouts the engine owns (the
    engine holds params/pool; this class is pure functions of them)."""

    def __init__(self, cfg: ServingModelConfig):
        self.cfg = cfg

    # -- admission: packed varlen prefill --------------------------------

    def prefill(self, params, tokens: jnp.ndarray, seg: jnp.ndarray,
                positions: jnp.ndarray,
                last_index: Optional[jnp.ndarray] = None,
                *, tp_axis: Optional[str] = None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """tokens/seg/positions ``[1, S]`` (one packed row; seg 0 =
        padding, real segments 1..n; positions restart per segment).
        Returns (logits, k, v ``[L, 1, S, H, D]``) — K/V for every
        packed position, for the engine to scatter into pages.

        ``last_index`` (traced int scalar, so the compiled shape never
        changes): compute logits ``[1, 1, vocab]`` for that single
        position only.  Admission needs exactly one next-token
        distribution (the last context position) — projecting all S
        rows through the LM head would put an S×hidden×vocab matmul on
        the TTFT-critical path for one useful row.  ``None`` returns
        the full ``[1, S, vocab]`` logits (teacher-forcing/scoring
        use).

        ``tp_axis``: run as the per-shard body under ``shard_map`` —
        the local wqkv block carries this shard's heads (the returned
        k/v are the LOCAL head slice) and each block's residual is
        one ``psum``."""
        cfg = self.cfg
        hd = cfg.head_dim
        with jax.named_scope("embedding"):
            x = params["embed"][tokens] + params["pos"][positions]
        ks, vs = [], []
        for layer in params["layers"]:
            with jax.named_scope("layer"):
                hdn = _ln(x, layer["ln1"])
                qkv = hdn @ layer["wqkv"]
                q, k, v = jnp.split(qkv, 3, axis=-1)
                b, s = q.shape[:2]
                nh = k.shape[-1] // hd  # LOCAL heads (H/tp under shard_map)
                q4 = q.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
                k4 = k.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
                v4 = v.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
                ctx = flash_attention(q4, k4, v4, causal=True,
                                      segment_ids=seg)
                ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)
                attn = ctx @ layer["wo"]
                if tp_axis is not None:
                    attn = jax.lax.psum(attn, tp_axis)
                x = x + attn
                mlp = _mlp(_ln(x, layer["ln2"]), layer)
                if tp_axis is not None:
                    mlp = jax.lax.psum(mlp, tp_axis)
                x = x + mlp
                ks.append(k.reshape(b, s, nh, hd))
                vs.append(v.reshape(b, s, nh, hd))
        with jax.named_scope("head"):
            x = _ln(x, params["ln_f"])
            if last_index is not None:
                x = jax.lax.dynamic_slice_in_dim(
                    x, jnp.asarray(last_index, jnp.int32), 1, axis=1)
            logits = x @ params["embed"].T
        return logits, jnp.stack(ks), jnp.stack(vs)

    # -- steady state: paged decode --------------------------------------

    def decode(self, params, k_pool, v_pool, tokens: jnp.ndarray,
               positions: jnp.ndarray, page_table: jnp.ndarray,
               kv_len: jnp.ndarray, *,
               k_scale: Optional[jnp.ndarray] = None,
               v_scale: Optional[jnp.ndarray] = None,
               tp_axis: Optional[str] = None):
        """One decode step for a fixed-width batch.

        ``tokens``/``positions`` ``[b]``: each row's newest token and
        its 0-based sequence position; ``kv_len = positions + 1`` (the
        flash_decode contract: the count includes the query token,
        whose K/V this step appends).  ``page_table`` ``[b, p_max]``.
        Idle rows carry position 0 / kv_len 1 / an all-scratch page
        row; their writes land in scratch page 0 and their outputs are
        discarded by the engine.  Returns (logits ``[b, vocab]``,
        k_pool', v_pool') — or, with ``k_scale``/``v_scale`` (the
        quantized pool's [L, n_pages, ps, H] fp32 scale planes), a
        5-tuple appending the updated scale planes: the append
        quantizes on write and ``flash_decode`` dequantizes on read.
        ``tp_axis``: per-shard body under ``shard_map`` (local head
        slice of pool and scales, one ``psum`` per block).

        Every layer hands ``flash_decode`` the WHOLE pool ``[L,
        n_pages, ps, H, hd]`` and ``layer=li``, never ``k_pool[li]``:
        the kernel reads layer ``li``'s pages where they lie, and a
        slice would be copied out first (all of a layer's pages, twice,
        before each of the L calls).  The append before it stays in
        place: the call reads the buffer the scatter wrote.
        :meth:`extend` does the same."""
        cfg = self.cfg
        hd = cfg.head_dim
        page_size = k_pool.shape[2]
        quantized = k_scale is not None
        qmax = quant_qmax(k_pool.dtype) if quantized else None
        with jax.named_scope("embedding"):
            x = params["embed"][tokens] + params["pos"][positions]  # [b, h]
        page_slot = positions // page_size
        page_idx = jnp.take_along_axis(
            page_table, page_slot[:, None], axis=1)[:, 0]
        offset = positions % page_size
        for li, layer in enumerate(params["layers"]):
            with jax.named_scope("layer"):
                hdn = _ln(x, layer["ln1"])
                qkv = hdn @ layer["wqkv"]
                q, k, v = jnp.split(qkv, 3, axis=-1)
                b = q.shape[0]
                nh = k.shape[-1] // hd  # LOCAL heads (H/tp under shard_map)
                k_new, v_new = k.reshape(b, nh, hd), v.reshape(b, nh, hd)
                if quantized:
                    k_new, k_s = quantize_tokens(k_new, k_pool.dtype, qmax)
                    v_new, v_s = quantize_tokens(v_new, v_pool.dtype, qmax)
                    k_scale = k_scale.at[li, page_idx, offset].set(k_s)
                    v_scale = v_scale.at[li, page_idx, offset].set(v_s)
                k_pool = k_pool.at[li, page_idx, offset].set(k_new)
                v_pool = v_pool.at[li, page_idx, offset].set(v_new)
                q4 = q.reshape(b, 1, nh, hd).transpose(0, 2, 1, 3)
                ctx = flash_decode(
                    q4, k_pool, v_pool, page_table, kv_len, layer=li,
                    k_scale=k_scale, v_scale=v_scale)
                ctx = ctx.transpose(0, 2, 1, 3).reshape(b, -1)
                attn = ctx @ layer["wo"]
                if tp_axis is not None:
                    attn = jax.lax.psum(attn, tp_axis)
                x = x + attn
                mlp = _mlp(_ln(x, layer["ln2"]), layer)
                if tp_axis is not None:
                    mlp = jax.lax.psum(mlp, tp_axis)
                x = x + mlp
        with jax.named_scope("head"):
            logits = _ln(x, params["ln_f"]) @ params["embed"].T
        if quantized:
            return logits, k_pool, v_pool, k_scale, v_scale
        return logits, k_pool, v_pool

    # -- draft–verify / chunked prefill: multi-token extension -----------

    def extend(self, params, k_pool, v_pool, tokens: jnp.ndarray,
               positions: jnp.ndarray, write_pages: jnp.ndarray,
               write_offsets: jnp.ndarray, page_table: jnp.ndarray,
               kv_len: jnp.ndarray, *, last_only: bool = False,
               k_scale: Optional[jnp.ndarray] = None,
               v_scale: Optional[jnp.ndarray] = None,
               tp_axis: Optional[str] = None):
        """Append ``q`` tokens per row to the paged cache and score
        them in one :func:`~apex_tpu.ops.flash_decode` launch.

        ``tokens``/``positions`` ``[b, q]``: each row's newest tokens,
        FRONT-padded — the valid tokens must be the LAST rows, because
        flash_decode's causal rule (row i sees columns
        ``[0, kv_len - q_len + i]``) anchors the query window to the
        END of the ``kv_len``-token cache.  ``write_pages``/
        ``write_offsets`` ``[b, q]``: host-computed scatter targets for
        each row's K/V (padding rows point at scratch page 0, so a
        partial draft/chunk never dirties a live slot).  ``kv_len``
        ``[b]``: valid tokens INCLUDING the q-window's real rows — it
        may be SMALLER than ``q`` (a whole sequence shorter than the
        fixed window): flash_decode's empty-window rule returns exact
        zeros for rows whose causal window is empty, and the caller
        discards pad-row outputs either way (idle rows pass
        ``kv_len = q``).  ``page_table`` ``[b, p_max]``.

        ``last_only`` (static): project only the final row through the
        LM head — the chunked-prefill shape, where one next-token
        distribution is wanted and front-padding pins the chunk's last
        valid token to row ``q - 1``.  ``k_scale``/``v_scale`` and
        ``tp_axis``: as in :meth:`decode` (quantize-on-write appends /
        per-shard ``shard_map`` body).  Returns (logits
        ``[b, q, vocab]`` or ``[b, 1, vocab]``, k_pool', v_pool'[,
        k_scale', v_scale']).
        """
        cfg = self.cfg
        hd = cfg.head_dim
        b, q = tokens.shape
        quantized = k_scale is not None
        qmax = quant_qmax(k_pool.dtype) if quantized else None
        with jax.named_scope("embedding"):   # x [b, q, h]
            x = params["embed"][tokens] + params["pos"][positions]
        for li, layer in enumerate(params["layers"]):
            with jax.named_scope("layer"):
                hdn = _ln(x, layer["ln1"])
                qkv = hdn @ layer["wqkv"]
                qh, kh, vh = jnp.split(qkv, 3, axis=-1)
                nh = kh.shape[-1] // hd  # LOCAL heads (H/tp under shard_map)
                k_new = kh.reshape(b, q, nh, hd)
                v_new = vh.reshape(b, q, nh, hd)
                if quantized:
                    k_new, k_s = quantize_tokens(k_new, k_pool.dtype, qmax)
                    v_new, v_s = quantize_tokens(v_new, v_pool.dtype, qmax)
                    k_scale = k_scale.at[li, write_pages,
                                         write_offsets].set(k_s)
                    v_scale = v_scale.at[li, write_pages,
                                         write_offsets].set(v_s)
                k_pool = k_pool.at[li, write_pages, write_offsets].set(k_new)
                v_pool = v_pool.at[li, write_pages, write_offsets].set(v_new)
                q4 = qh.reshape(b, q, nh, hd).transpose(0, 2, 1, 3)
                ctx = flash_decode(
                    q4, k_pool, v_pool, page_table, kv_len, layer=li,
                    k_scale=k_scale, v_scale=v_scale)
                ctx = ctx.transpose(0, 2, 1, 3).reshape(b, q, -1)
                attn = ctx @ layer["wo"]
                if tp_axis is not None:
                    attn = jax.lax.psum(attn, tp_axis)
                x = x + attn
                mlp = _mlp(_ln(x, layer["ln2"]), layer)
                if tp_axis is not None:
                    mlp = jax.lax.psum(mlp, tp_axis)
                x = x + mlp
        with jax.named_scope("head"):
            x = _ln(x, params["ln_f"])
            if last_only:
                x = x[:, -1:, :]
            logits = x @ params["embed"].T
        if quantized:
            return logits, k_pool, v_pool, k_scale, v_scale
        return logits, k_pool, v_pool

"""Decoders over a paged KV cache — the serving engine's model half.

**The block seam (ISSUE 29).**  :class:`PagedDecoder` is one driver
for every architecture: its three entry points below own the cache
(the packed row, the appends, the page tables, which pool a layer
lives in) and call the architecture's BLOCK for everything between the
embedding and the logits.  A block is written once — ``embed``, one
``layer`` and the head — and is handed an ``attend(q, k, v)`` that is
the varlen prefill kernel in one entry point and append-then-
``flash_decode`` in the other two.  :class:`GPTBlock` (learned
positions, LayerNorm, GELU MLP, multi-head attention, tied head) is
one definition; :class:`AfmoeBlock` (RoPE on sliding-window layers and
none on full ones, RMSNorm sandwiches, QK-norm, a sigmoid output gate,
grouped-query heads, SwiGLU, a dropless top-k expert layer that holds
a share of the experts, muP-scaled embeddings, an untied head) the
second, :class:`DeepseekV2Block` (latent attention over one vector a
token) the third, :class:`GraniteHybridBlock` the fourth: most of its
layers are Mamba-2 STATE-SPACE mixers, which are handed no ``attend``
but a ``scan`` that takes the request's recurrent state (a slot of the
cache's state pool, ISSUE 35) to its next; :class:`Lfm2MoeBlock` the
fifth: gated SHORT-convolution layers, whose slot holds a convolution
tail alone, beside QK-normed grouped-query layers, and a top-4 sigmoid
expert layer without a shared expert (ISSUE 39).  The engine asks a
configuration for its block and never asks which it got.

Two entry points mirror the two phases of continuous batching:

* :meth:`PagedDecoder.prefill` — the ADMISSION path.  A packed token
  row with segment ids (the PR 5 varlen packed path: cross-segment
  tiles are masked in-kernel and skipped by the block-skip index on
  TPU) — one fixed-shape forward per width it is called at, and the
  engine calls it at a short fixed ladder of widths, all warmed, so no
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
import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops import (flash_attention, flash_decode, flash_decode_latent,
                          latent_walk_tiles)
from apex_tpu.ops.grouped import rows_tiled
from apex_tpu.ops.ssm import (causal_conv, short_conv_update, ssd_chunk_scan,
                              ssm_decode_update, tail_from_pool, tail_to_pool)
from apex_tpu.serving.experts import expert_layer, expert_tile, swiglu
from apex_tpu.serving.kv_cache import (as_stored, latent_width, pad_latent,
                                       quantize_tokens)


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
    """GPT decoder geometry.  ``max_position`` bounds the learned
    position table and nothing else — admission must reject requests
    that could outgrow it.  (A model without such a table is bounded by
    its pages: ``ServingEngine.max_context``.)"""

    vocab_size: int = 256
    hidden_size: int = 64
    num_heads: int = 4
    num_layers: int = 2
    max_position: int = 512
    mlp_ratio: int = 4
    dtype: object = jnp.float32

    name = "gpt"

    @property
    def head_dim(self) -> int:
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must divide by num_heads")
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        """Heads a token's K and V have in the pool."""
        return self.num_heads

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        """Per layer, how many tokens back it sees (None: all)."""
        return (None,) * self.num_layers

    #: numbers a token keeps as ONE key-and-value vector (None: K and
    #: V heads, two operands)
    latent_dim = None

    def block(self) -> "GPTBlock":
        return GPTBlock(self)


def init_params(cfg, seed: int = 0):
    """Deterministic parameter pytree (scaled-normal init; for a GPT,
    tied LM head = embedding transpose)."""
    if not isinstance(cfg, ServingModelConfig):
        return cfg.init_params(seed)
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


class GPTBlock:
    """The pre-LN GPT block: learned positions, LayerNorm, fused QKV,
    multi-head attention, GELU MLP, head tied to the embedding.  Under
    ``tp_axis`` it is the per-shard body of ``shard_map``: the local
    ``wqkv`` block carries this shard's heads and each half of the
    block contributes its residual through one ``psum``."""

    #: engine options this architecture cannot serve (none)
    refuses: Tuple[str, ...] = ()
    #: per-launch counters its executables return after the pools
    stat_names: Tuple[str, ...] = ()
    #: the section of docs/serving.md that says why each is refused
    refuses_doc = "The block seam"

    def __init__(self, cfg: ServingModelConfig):
        self.cfg = cfg

    def embed(self, params, tokens, positions):
        return params["embed"][tokens] + params["pos"][positions]

    def layer(self, layer, li, x, positions, attend, *, tp_axis=None,
              valid=None, stats=None):
        hd = self.cfg.head_dim
        hdn = _ln(x, layer["ln1"])
        qkv = hdn @ layer["wqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        lead = q.shape[:-1]
        nh = k.shape[-1] // hd  # LOCAL heads (H/tp under shard_map)
        ctx = attend(q.reshape(*lead, nh, hd), k.reshape(*lead, nh, hd),
                     v.reshape(*lead, nh, hd))
        attn = ctx @ layer["wo"]
        if tp_axis is not None:
            attn = jax.lax.psum(attn, tp_axis)
        x = x + attn
        mlp = _mlp(_ln(x, layer["ln2"]), layer)
        if tp_axis is not None:
            mlp = jax.lax.psum(mlp, tp_axis)
        return x + mlp

    def final_norm(self, params, x):
        return _ln(x, params["ln_f"])

    def logits(self, params, x):
        return x @ params["embed"].T


# -- afmoe (Trinity): the second block ---------------------------------------


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """Geometry of an ``afmoe`` decoder (arcee-ai Trinity) as one chip
    of an expert-parallel deployment holds it: ``num_experts`` is the
    router's width, ``experts_held`` the ``[lo, hi)`` of them whose
    weights are here, ``vocab_size`` this chip's slice.  There is no
    position table (RoPE on sliding layers, nothing on full ones): a
    request is bounded by its pages."""

    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]       # "sliding_attention" | "full_attention"
    num_dense_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    experts_held: Tuple[int, int]
    top_k: int
    route_scale: float
    sliding_window: int
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: object = jnp.float32

    name = "afmoe"
    #: no learned position table
    max_position = None
    latent_dim = None

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        return tuple(self.sliding_window if t == "sliding_attention"
                     else None for t in self.layer_types)

    def block(self) -> "AfmoeBlock":
        return AfmoeBlock(self)

    def init_params(self, seed: int = 0):
        """Seeded parameters in the block's layout: matrices normal
        with std 1/sqrt(fan_in), gains 1 + 0.02 normal, the router's
        selection bias 0.02 normal (so that tests exercise it)."""
        d, dt = self.hidden_size, self.dtype
        hq, hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
        n_held = self.experts_held[1] - self.experts_held[0]
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

        def mat(*shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    / math.sqrt(shape[-2])).astype(dt)

        def gain(n):
            return (1.0 + 0.02 * jax.random.normal(
                next(keys), (n,), jnp.float32)).astype(dt)

        def mlp(*lead, f):
            return {"wg": mat(*lead, d, f), "wu": mat(*lead, d, f),
                    "wd": mat(*lead, f, d)}

        layers = []
        for i in range(self.num_layers):
            layer = {"g1": gain(d), "g2": gain(d), "g3": gain(d),
                     "g4": gain(d), "gq": gain(hd), "gk": gain(hd),
                     "wq": mat(d, hq * hd), "wk": mat(d, hk * hd),
                     "wv": mat(d, hk * hd), "wgate": mat(d, hq * hd),
                     "wo": mat(hq * hd, d)}
            if i < self.num_dense_layers:
                layer["mlp"] = mlp(f=self.intermediate_size)
            else:
                f = self.moe_intermediate_size
                layer["moe"] = {
                    "router": mat(d, self.num_experts),
                    "expert_bias": (0.02 * jax.random.normal(
                        next(keys), (self.num_experts,),
                        jnp.float32)).astype(dt),
                    "experts": mlp(n_held, f=f), "shared": mlp(f=f)}
            layers.append(layer)
        embed = (jax.random.normal(next(keys), (self.vocab_size, d),
                                   jnp.float32) / math.sqrt(d)).astype(dt)
        return {"embed": embed, "head": mat(d, self.vocab_size),
                "norm_f": gain(d), "layers": layers}


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Rotate-half RoPE over all of the head dimension, absolute
    0-based ``positions`` (the lead dimensions of ``x [..., h, d]``)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class AfmoeBlock:
    """The ``afmoe`` block (docs/serving.md, "The block seam"):

    ``h = x + RMS(Attn(RMS(x)))``, ``x' = h + RMS(MLP(RMS(h)))`` — four
    norms a layer.  Attention: grouped-query heads with RMS norm on
    each head of q and k, RoPE on SLIDING layers only (full layers
    carry no positional signal), a sigmoid gate from the normed input
    on the context before ``wo``, no biases.  The MLP is SwiGLU in the
    first ``num_dense_layers`` layers and the dropless expert layer of
    :mod:`apex_tpu.serving.experts` after them.  Embeddings are scaled
    by ``sqrt(d)`` (muP); the head is its own matrix."""

    refuses = ("tp", "kv_quant", "prefix_sharing", "speculation",
               "prefill_only", "kv_import")
    stat_names = ("moe_pairs_held", "moe_load_max", "moe_rows_tiled")
    #: where the engine's refusal sends the reader
    refuses_doc = "What afmoe refuses"

    def __init__(self, cfg: AfmoeConfig):
        self.cfg = cfg
        self.windows = cfg.layer_windows

    def embed(self, params, tokens, positions):
        x = params["embed"][tokens]
        return x * jnp.asarray(math.sqrt(self.cfg.hidden_size), x.dtype)

    def layer(self, layer, li, x, positions, attend, *, tp_axis=None,
              valid=None, stats=None):
        cfg = self.cfg
        eps, hd = cfg.rms_norm_eps, cfg.head_dim
        lead = x.shape[:-1]
        u = _rms(x, layer["g1"], eps)
        q = (u @ layer["wq"]).reshape(*lead, cfg.num_heads, hd)
        k = (u @ layer["wk"]).reshape(*lead, cfg.num_kv_heads, hd)
        v = (u @ layer["wv"]).reshape(*lead, cfg.num_kv_heads, hd)
        gate = u @ layer["wgate"]
        q, k = _rms(q, layer["gq"], eps), _rms(k, layer["gk"], eps)
        sliding = self.windows[li] is not None
        if sliding:
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        with jax.named_scope("attn_window" if sliding else "attn_full"):
            ctx = attend(q, k, v)
        a = (ctx.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
        h = x + _rms(a @ layer["wo"], layer["g2"], eps)
        u = _rms(h, layer["g3"], eps)
        if "mlp" in layer:
            m = swiglu(u, layer["mlp"])
        else:
            m, load = expert_layer(
                u, layer["moe"], held=cfg.experts_held, top_k=cfg.top_k,
                route_scale=cfg.route_scale, valid=valid)
            if stats is not None:
                stats.append(
                    (load, expert_tile(u, layer["moe"], cfg.top_k)))
        return h + _rms(m, layer["g4"], eps)

    def final_norm(self, params, x):
        return _rms(x, params["norm_f"], self.cfg.rms_norm_eps)

    def logits(self, params, x):
        return x @ params["head"]


# -- deepseek_v2: the third block, latent attention ---------------------------


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    """Geometry of a ``deepseek_v2`` decoder as one chip of its
    expert-parallel deployment holds it: ``n_routed_experts`` is the
    router's width, ``experts_held`` the ``[lo, hi)`` of them whose
    weights are here, ``vocab_size`` this chip's slice.  Attention is
    multi-head LATENT attention: a token keeps ``kv_lora_rank +
    qk_rope_head_dim`` numbers a layer (:attr:`latent_dim`), one vector
    for all heads.  No position table: a request is bounded by its
    pages."""

    vocab_size: int
    hidden_size: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    num_layers: int
    first_k_dense_replace: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    top_k: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    n_shared_experts: int
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    rms_norm_eps: float = 1e-6
    dtype: object = jnp.float32

    name = "deepseek_v2"
    max_position = None

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def head_dim(self) -> int:
        """The width a whole-row prefill's heads have in the attention
        forward: Q/K (``qk_head_dim``) and V (``v_head_dim``) both
        zero-padded to whole lane tiles of one width."""
        return latent_width(max(self.qk_head_dim, self.v_head_dim))

    @property
    def kv_heads(self) -> int:
        return self.num_heads

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        return (None,) * self.num_layers

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5 * m * m``, ``m`` YaRN's
        ``0.1 * mscale_all_dim * ln(factor) + 1``."""
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    def block(self) -> "DeepseekV2Block":
        return DeepseekV2Block(self)

    def init_params(self, seed: int = 0):
        """Seeded parameters in the block's layout (the reference's):
        matrices normal with std 1/sqrt(fan_in), gains 1 + 0.02 normal.
        The up-projection of the latent is held as its two column
        blocks, ``wuk`` (the heads' keys) and ``wuv`` (their values)."""
        d, dt, nh = self.hidden_size, self.dtype, self.num_heads
        n_held = self.experts_held[1] - self.experts_held[0]
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

        def mat(*shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    / math.sqrt(shape[-2])).astype(dt)

        def gain(n):
            return (1.0 + 0.02 * jax.random.normal(
                next(keys), (n,), jnp.float32)).astype(dt)

        def mlp(*lead, f):
            return {"wg": mat(*lead, d, f), "wu": mat(*lead, d, f),
                    "wd": mat(*lead, f, d)}

        layers = []
        for i in range(self.num_layers):
            layer = {"g1": gain(d), "g2": gain(d),
                     "gq": gain(self.q_lora_rank),
                     "gkv": gain(self.kv_lora_rank),
                     "wdq": mat(d, self.q_lora_rank),
                     "wuq": mat(self.q_lora_rank, nh * self.qk_head_dim),
                     "wdkv": mat(d, self.latent_dim),
                     "wuk": mat(self.kv_lora_rank,
                                nh * self.qk_nope_head_dim),
                     "wuv": mat(self.kv_lora_rank, nh * self.v_head_dim),
                     "wo": mat(nh * self.v_head_dim, d)}
            if i < self.first_k_dense_replace:
                layer["mlp"] = mlp(f=self.intermediate_size)
            else:
                f = self.moe_intermediate_size
                layer["moe"] = {
                    "router": mat(d, self.n_routed_experts),
                    "experts": mlp(n_held, f=f),
                    "shared": mlp(f=f * self.n_shared_experts)}
            layers.append(layer)
        embed = (jax.random.normal(next(keys), (self.vocab_size, d),
                                   jnp.float32) / math.sqrt(d)).astype(dt)
        return {"embed": embed, "head": mat(d, self.vocab_size),
                "norm_f": gain(d), "layers": layers}


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, *, theta: float, factor: float,
                  original_max: int, beta_fast: float, beta_slow: float):
    """The ``dim // 2`` rotary frequencies under YaRN, float64 numpy:
    ``f_i = theta ** (-2i / dim)``, divided by ``factor`` for the pairs
    past ``high`` (``ramp`` 1), untouched before ``low``, blended
    between.  Returns (inv_freq, low, high)."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    corr = lambda r: (dim * math.log(original_max / (2 * math.pi * r))
                      / (2 * math.log(theta)))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp), low, high


def _rope_pairs(x, positions, inv_freq):
    """RoPE over ADJACENT pairs ``(2i, 2i + 1)`` of the last dimension,
    in place; ``positions`` is the lead shape of ``x`` less any head
    axis between (``x [..., d]`` or ``x [..., h, d]``)."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    if x.ndim == ang.ndim + 1:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class DeepseekV2Block:
    """The ``deepseek_v2`` block (docs/serving.md, "The latent page"):
    ``h = x + Attn(RMS(x))``, ``x' = h + MLP(RMS(h))``.

    Attention is latent: the query goes through a low-rank bottleneck
    with an RMSNorm (``cq``), and a token's keys and values are ONE
    vector ``[c (kv_lora_rank, after its RMSNorm), k_pe (the rotary key,
    after RoPE, shared by all heads)]``, which is what the cache holds.
    The block hands ``attend`` the query's two parts, that vector and
    the up-projections ``wuk`` / ``wuv``; the DECODER chooses the form:
    expanded (per-head K and V from ``c``; a whole-row prefill) or
    absorbed (``wuk`` folded into the query, ``wuv`` applied to the
    output; over the latent pages).  The MLP is SwiGLU in the first
    ``first_k_dense_replace`` layers and after them the dropless expert
    layer under the group-limited softmax router, with the shared
    experts as one SwiGLU."""

    refuses = ("tp", "kv_quant", "speculation", "prefill_only",
               "kv_import")
    stat_names = ("moe_pairs_held", "moe_load_max", "moe_rows_tiled",
                  "latent_blocks_walked", "latent_blocks_fetched")
    refuses_doc = "What DeepSeek-V2 refuses"

    def __init__(self, cfg: DeepseekV2Config):
        self.cfg = cfg
        inv_freq, _, _ = yarn_inv_freq(
            cfg.qk_rope_head_dim, theta=cfg.rope_theta,
            factor=cfg.rope_factor, original_max=cfg.rope_original_max,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow)
        self.inv_freq = np.asarray(inv_freq, np.float32)
        # cos and sin carry mscale / mscale_all_dim, 1 where they agree
        self.rope_gain = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                          / yarn_mscale(cfg.rope_factor,
                                        cfg.rope_mscale_all_dim))

    def embed(self, params, tokens, positions):
        return params["embed"][tokens]

    def layer(self, layer, li, x, positions, attend, *, tp_axis=None,
              valid=None, stats=None):
        cfg = self.cfg
        eps, nh = cfg.rms_norm_eps, cfg.num_heads
        lead = x.shape[:-1]
        u = _rms(x, layer["g1"], eps)
        with jax.named_scope("mla_q"):
            cq = _rms(u @ layer["wdq"], layer["gq"], eps)
            q = (cq @ layer["wuq"]).reshape(*lead, nh, cfg.qk_head_dim)
            q_nope = q[..., :cfg.qk_nope_head_dim]
            q_pe = _rope_pairs(q[..., cfg.qk_nope_head_dim:], positions,
                               self.inv_freq) * self.rope_gain
        with jax.named_scope("mla_kv_down"):
            ckv = u @ layer["wdkv"]
            c = _rms(ckv[..., :cfg.kv_lora_rank], layer["gkv"], eps)
            k_pe = _rope_pairs(ckv[..., cfg.kv_lora_rank:], positions,
                               self.inv_freq) * self.rope_gain
            latent = jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)
        with jax.named_scope("attn_latent"):
            ctx = attend(q_nope, q_pe.astype(q.dtype), latent,
                         layer["wuk"], layer["wuv"],
                         scale=cfg.softmax_scale)
        with jax.named_scope("mla_out"):
            h = x + ctx @ layer["wo"]
        u = _rms(h, layer["g2"], eps)
        if "mlp" in layer:
            with jax.named_scope("mlp"):
                m = swiglu(u, layer["mlp"])
        else:
            m, load = expert_layer(
                u, layer["moe"], held=cfg.experts_held, top_k=cfg.top_k,
                route_scale=cfg.routed_scaling_factor, valid=valid,
                groups=(cfg.n_group, cfg.topk_group))
            if stats is not None:
                stats.append(
                    (load, expert_tile(u, layer["moe"], cfg.top_k)))
        return h + m

    def final_norm(self, params, x):
        return _rms(x, params["norm_f"], self.cfg.rms_norm_eps)

    def logits(self, params, x):
        return x @ params["head"]


# -- granite-hybrid: the fourth block, state-space layers ---------------------


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Geometry of a ``granitemoehybrid`` decoder without experts
    (ibm-granite Granite 4.0-H): ``layer_types`` names each layer's
    mixer, ``"mamba"`` (a Mamba-2 state-space mixer) or ``"attention"``
    (grouped-query attention with NO positional signal), each followed
    by a SwiGLU MLP; the four Granite multipliers scale the embedding,
    both residual branches, the attention scores and the logits; the
    head is the embedding.  No position enters anywhere, so no table
    bounds a request: its pages do.

    A request keeps, a state-space layer, a state of ``mamba_d_state x
    (mamba_n_heads * mamba_d_head)`` numbers and the last ``mamba_d_conv
    - 1`` rows of the convolution's input (:attr:`state_shape`,
    :attr:`tail_shape`: a SLOT of the cache's ``state_pool``), and an
    attention layer, K and V of ``num_kv_heads`` heads a token."""

    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    layer_types: Tuple[str, ...]       # "mamba" | "attention"
    intermediate_size: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    attention_multiplier: float
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    dtype: object = jnp.float32
    #: the recurrent state's type in the pool
    state_dtype: object = jnp.float32

    name = "granite_hybrid"
    max_position = None
    latent_dim = None
    #: one B/C group: every head reads the same ``B`` and ``C``
    mamba_n_groups = 1

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must divide by num_heads")
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        return (None,) * self.num_layers

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """The layers that keep a recurrent state and no K/V."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "mamba")

    @property
    def page_head_dim(self) -> int:
        """The width a K/V head is stored at in the page pool: whole
        lane tiles, the padding zero.  The device lays a head of 64 out
        in a 128-lane tile anyway, and the paged kernels want whole
        tiles (:meth:`PagedDecoder._stored`)."""
        return latent_width(self.head_dim)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``x`` and one group's B and C."""
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def state_shape(self) -> Tuple[int, int]:
        """A slot's state of one layer (``apex_tpu.ops.ssm``'s layout)."""
        return (self.mamba_d_state, self.d_inner)

    @property
    def tail_shape(self) -> Tuple[int, int]:
        """A slot's tail of one layer as the pool holds it: the last
        ``mamba_d_conv - 1`` rows of ``xBC`` end to end, one vector."""
        return (1, (self.mamba_d_conv - 1) * self.conv_dim)

    def block(self) -> "GraniteHybridBlock":
        return GraniteHybridBlock(self)

    def init_params(self, seed: int = 0):
        """Seeded parameters in the block's layout (the reference's):
        matrices normal with std 1/sqrt(fan_in), gains 1 + 0.02 normal,
        the convolution's taps normal with std 1/sqrt(taps).  The
        recurrence's scalars as Mamba-2 initialises them, so that heads
        remember over a few tokens and over thousands: ``A`` spread over
        [1, 16], the step ``softplus(dt_bias)`` log-spread over [0.001,
        0.1], ``D`` 1."""
        d, dt = self.hidden_size, self.dtype
        hq, hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
        nh, di, ch = self.mamba_n_heads, self.d_inner, self.conv_dim
        f = self.intermediate_size
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

        def mat(*shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    / math.sqrt(shape[-2])).astype(dt)

        def gain(n):
            return (1.0 + 0.02 * jax.random.normal(
                next(keys), (n,), jnp.float32)).astype(dt)

        step = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), nh))
        layers = []
        for kind in self.layer_types:
            layer = {"g1": gain(d), "g2": gain(d),
                     "mlp": {"wg": mat(d, f), "wu": mat(d, f),
                             "wd": mat(f, d)}}
            if kind == "mamba":
                layer.update(
                    win=mat(d, di + ch + nh), conv_w=mat(self.mamba_d_conv,
                                                         ch),
                    conv_b=(0.02 * jax.random.normal(
                        next(keys), (ch,), jnp.float32)).astype(dt),
                    dt_bias=jnp.log(jnp.expm1(step)).astype(dt),
                    a_log=jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(dt),
                    d_skip=gain(nh), gn=gain(di), wout=mat(di, d))
            else:
                layer.update(wq=mat(d, hq * hd), wk=mat(d, hk * hd),
                             wv=mat(d, hk * hd), wo=mat(hq * hd, d))
            layers.append(layer)
        # a row of norm 1 AFTER the multiplier: the head is the same
        # matrix, and rows the multiplier made 12 times the usual would
        # make every token predict itself
        embed = (jax.random.normal(next(keys), (self.vocab_size, d),
                                   jnp.float32)
                 / (self.embedding_multiplier * math.sqrt(d))).astype(dt)
        return {"embed": embed, "norm_f": gain(d), "layers": layers}


class GraniteHybridBlock:
    """The Granite 4.0-H block (docs/serving.md, "Slots beside pages"):
    ``h = x + r Mixer(RMS(x))``, ``x' = h + r MLP(RMS(h))`` with ``r``
    the residual multiplier; embeddings times ``embedding_multiplier``,
    logits over ``logits_scaling`` through the embedding transposed.

    The mixer is the layer's own, and so is what the decoder hands it:
    an ATTENTION layer (a few of the layers) gets ``attend(q, k, v,
    scale=)`` as every attention block does, with 32 query heads over 8
    K/V heads of 64, scores times ``attention_multiplier``, no rotary
    and no bias; a STATE-SPACE layer gets ``scan(xbc, dt, layer)``, the
    Mamba-2 recurrence from the request's state and tail to its new
    ones (the chunked form over a prefill row or chunk, the in-place
    update at decode: ``apex_tpu.ops.ssm``), between the block's input
    projection and its gated RMSNorm and output projection."""

    #: a shared prefix needs a snapshot of the state at the page it
    #: ends on, a rejected draft a rolled-back state, shipped pages a
    #: shipped slot: none exists yet
    refuses = ("tp", "kv_quant", "prefix_sharing", "speculation",
               "prefill_only", "kv_import")
    stat_names: Tuple[str, ...] = ()
    #: where the engine's refusal sends the reader
    refuses_doc = "What granite-hybrid refuses"

    def __init__(self, cfg: GraniteHybridConfig):
        self.cfg = cfg

    def embed(self, params, tokens, positions):
        x = params["embed"][tokens]
        return x * jnp.asarray(self.cfg.embedding_multiplier, x.dtype)

    def _residual(self, x, branch):
        r = self.cfg.residual_multiplier
        return (x.astype(jnp.float32)
                + r * branch.astype(jnp.float32)).astype(x.dtype)

    def layer(self, layer, li, x, positions, mixer, *, tp_axis=None,
              valid=None, stats=None):
        cfg = self.cfg
        eps, hd = cfg.rms_norm_eps, cfg.head_dim
        lead = x.shape[:-1]
        u = _rms(x, layer["g1"], eps)
        if "win" in layer:
            di, ch = cfg.d_inner, cfg.conv_dim
            with jax.named_scope("ssm_in_proj"):
                zxbcdt = u @ layer["win"]
                z = zxbcdt[..., :di]
                xbc = zxbcdt[..., di:di + ch]
                dt = zxbcdt[..., di + ch:]
            y = mixer(xbc, dt, layer)           # float32
            with jax.named_scope("ssm_gate_norm"):
                g = y * jax.nn.silu(z.astype(jnp.float32))
                g = _rms(g, layer["gn"], eps).astype(x.dtype)
            with jax.named_scope("ssm_out_proj"):
                m = g @ layer["wout"]
        else:
            q = (u @ layer["wq"]).reshape(*lead, cfg.num_heads, hd)
            k = (u @ layer["wk"]).reshape(*lead, cfg.num_kv_heads, hd)
            v = (u @ layer["wv"]).reshape(*lead, cfg.num_kv_heads, hd)
            with jax.named_scope("attn_nope"):
                ctx = mixer(q, k, v, scale=cfg.attention_multiplier)
            m = ctx @ layer["wo"]
        h = self._residual(x, m)
        with jax.named_scope("mlp"):
            m = swiglu(_rms(h, layer["g2"], eps), layer["mlp"])
        return self._residual(h, m)

    def final_norm(self, params, x):
        return _rms(x, params["norm_f"], self.cfg.rms_norm_eps)

    def logits(self, params, x):
        return (x @ params["embed"].T) / jnp.asarray(
            self.cfg.logits_scaling, x.dtype)


# -- lfm2_moe (LFM2): the fifth block, short convolutions --------------------


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """Geometry of an ``lfm2_moe`` decoder (LiquidAI LFM2-MoE):
    ``layer_types`` names each layer's mixer, ``"conv"`` (a gated short
    convolution) or ``"full_attention"`` (grouped-query attention with
    RMS-normed heads and RoPE); the first ``num_dense_layers`` layers
    have a SwiGLU MLP and the rest a top-k sigmoid expert layer of
    ``num_experts`` experts, of which ``experts_held`` ``[lo, hi)`` are
    here (all of them where one chip holds a layer), and no shared
    expert.  The head is the embedding.  No position table: a request is
    bounded by its pages.

    A request keeps, a convolution layer, the last ``conv_L_cache - 1``
    gated inputs of the convolution (:attr:`tail_shape`: a SLOT of the
    cache's ``state_pool``, which holds no recurrent state for this
    model, :attr:`state_shape` None), and an attention layer, K and V of
    ``num_kv_heads`` heads a token."""

    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    layer_types: Tuple[str, ...]       # "conv" | "full_attention"
    num_dense_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    experts_held: Tuple[int, int]
    top_k: int
    route_scale: float = 1.0
    conv_L_cache: int = 3
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    dtype: object = jnp.float32

    name = "lfm2_moe"
    max_position = None
    latent_dim = None
    #: a slot holds a tail and no recurrent state
    state_shape = None
    #: the renormalisation's epsilon in the published router
    route_eps = 1e-6

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must divide by num_heads")
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        return (None,) * self.num_layers

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """The layers that keep a tail in a slot and no K/V."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "conv")

    @property
    def page_head_dim(self) -> int:
        """A K/V head's width in the page pool: whole lane tiles, as
        :attr:`GraniteHybridConfig.page_head_dim`."""
        return latent_width(self.head_dim)

    @property
    def tail_shape(self) -> Tuple[int, int]:
        """A slot's tail of one layer: the gated inputs ``B * x`` of the
        ``conv_L_cache - 1`` tokens before the next."""
        return (self.conv_L_cache - 1, self.hidden_size)

    def block(self) -> "Lfm2MoeBlock":
        return Lfm2MoeBlock(self)

    def init_params(self, seed: int = 0):
        """Seeded parameters in the block's layout (the reference's):
        matrices normal with std 1/sqrt(fan_in) (the convolution's taps
        1/sqrt(taps)), gains 1 + 0.02 normal, the router's selection
        bias 0.02 normal (so that tests exercise it)."""
        d, dt = self.hidden_size, self.dtype
        hq, hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
        n_held = self.experts_held[1] - self.experts_held[0]
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

        def mat(*shape):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    / math.sqrt(shape[-2])).astype(dt)

        def gain(n):
            return (1.0 + 0.02 * jax.random.normal(
                next(keys), (n,), jnp.float32)).astype(dt)

        def mlp(*lead, f):
            return {"wg": mat(*lead, d, f), "wu": mat(*lead, d, f),
                    "wd": mat(*lead, f, d)}

        layers = []
        for i, kind in enumerate(self.layer_types):
            layer = {"g_op": gain(d), "g_ffn": gain(d)}
            if kind == "conv":
                layer.update(w_in=mat(d, 3 * d),
                             conv_w=mat(self.conv_L_cache, d),
                             w_out=mat(d, d))
            else:
                layer.update(wq=mat(d, hq * hd), wk=mat(d, hk * hd),
                             wv=mat(d, hk * hd), wo=mat(hq * hd, d),
                             gq=gain(hd), gk=gain(hd))
            if i < self.num_dense_layers:
                layer["mlp"] = mlp(f=self.intermediate_size)
            else:
                layer["moe"] = {
                    "router": mat(d, self.num_experts),
                    "expert_bias": (0.02 * jax.random.normal(
                        next(keys), (self.num_experts,),
                        jnp.float32)).astype(dt),
                    "experts": mlp(n_held, f=self.moe_intermediate_size)}
            layers.append(layer)
        embed = (jax.random.normal(next(keys), (self.vocab_size, d),
                                   jnp.float32) / math.sqrt(d)).astype(dt)
        return {"embed": embed, "norm_f": gain(d), "layers": layers}


class Lfm2MoeBlock:
    """The LFM2-MoE block (docs/serving.md, "The fifth block"): ``h = x
    + Mixer(RMS(x; g_op))``, ``x' = h + FFN(RMS(h; g_ffn))``; the head
    is the embedding transposed, after a final RMSNorm.

    A CONVOLUTION layer's mixer: ``[B | C | x] = u w_in``, ``v = B * x``,
    a depthwise causal convolution of ``conv_L_cache`` taps over ``v``
    with no bias and no activation, gated by ``C``, then ``w_out``; the
    decoder hands it ``mixer(v, layer)``, the convolution from the
    request's tail (a slot: the whole row's from zero, a chunk's from
    the slot, a decode token's against the slot in place:
    :func:`~apex_tpu.ops.ssm.short_conv_update`).  An ATTENTION layer:
    grouped-query heads, each of q and k RMS-normed over its head
    before RoPE (rotate-half over the whole head), scores over
    ``sqrt(head_dim)``, no bias; it gets ``attend(q, k, v, scale=)``.
    The FFN is SwiGLU in the first ``num_dense_layers`` layers and the
    dropless sigmoid expert layer after them, with no shared expert and
    the published renormalisation ``w / (sum w + 1e-6)``."""

    #: as Granite's: a shared prefix needs a snapshot of the tail at
    #: the page it ends on, a rejected draft a rolled-back tail, shipped
    #: pages a shipped slot: none exists yet
    refuses = ("tp", "kv_quant", "prefix_sharing", "speculation",
               "prefill_only", "kv_import")
    stat_names = ("moe_pairs_held", "moe_load_max", "moe_rows_tiled",
                  "moe_experts_touched")
    #: where the engine's refusal sends the reader
    refuses_doc = "What LFM2 refuses"

    def __init__(self, cfg: Lfm2MoeConfig):
        self.cfg = cfg

    def embed(self, params, tokens, positions):
        return params["embed"][tokens]

    def layer(self, layer, li, x, positions, mixer, *, tp_axis=None,
              valid=None, stats=None):
        cfg = self.cfg
        eps, hd = cfg.norm_eps, cfg.head_dim
        lead = x.shape[:-1]
        u = _rms(x, layer["g_op"], eps)
        if "w_in" in layer:
            with jax.named_scope("conv_in_proj"):
                b, c, xt = jnp.split(u @ layer["w_in"], 3, axis=-1)
                v = b * xt
            with jax.named_scope("short_conv"):
                conv = mixer(v, layer)            # float32
            with jax.named_scope("conv_out_proj"):
                m = ((c.astype(jnp.float32) * conv).astype(x.dtype)
                     @ layer["w_out"])
        else:
            with jax.named_scope("attn_full"):
                q = (u @ layer["wq"]).reshape(*lead, cfg.num_heads, hd)
                k = (u @ layer["wk"]).reshape(*lead, cfg.num_kv_heads, hd)
                v = (u @ layer["wv"]).reshape(*lead, cfg.num_kv_heads, hd)
                q = _rope(_rms(q, layer["gq"], eps), positions,
                          cfg.rope_theta)
                k = _rope(_rms(k, layer["gk"], eps), positions,
                          cfg.rope_theta)
                m = mixer(q, k, v, scale=hd ** -0.5) @ layer["wo"]
        h = x + m
        u = _rms(h, layer["g_ffn"], eps)
        if "mlp" in layer:
            with jax.named_scope("mlp"):
                m = swiglu(u, layer["mlp"])
        else:
            m, load = expert_layer(
                u, layer["moe"], held=cfg.experts_held, top_k=cfg.top_k,
                route_scale=cfg.route_scale, valid=valid,
                route_eps=cfg.route_eps)
            if stats is not None:
                stats.append(
                    (load, expert_tile(u, layer["moe"], cfg.top_k)))
        return h + m

    def final_norm(self, params, x):
        return _rms(x, params["norm_f"], self.cfg.norm_eps)

    def logits(self, params, x):
        return x @ params["embed"].T


# -- nemotron_h (Nemotron-H): the sixth block, one mixer a layer --------------

#: ``hybrid_override_pattern``'s letters -> the layer's one mixer
NEMOTRON_H_MIXERS = {"M": "mamba", "E": "moe", "*": "attention"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Geometry of a ``nemotron_h`` decoder with a latent expert layer
    (NVIDIA Nemotron 3 Super): ``layer_types`` names each layer's ONE
    mixer, ``"mamba"`` (a Mamba-2 state-space mixer whose heads read
    ``mamba_n_groups`` groups of B and C), ``"moe"`` (a top-k sigmoid
    expert layer of squared-ReLU experts, run in a latent of
    ``moe_latent_size``, beside a squared-ReLU shared expert) or
    ``"attention"`` (grouped-query attention with NO positional signal);
    every layer is ``x + Mixer(RMS(x))``, with no second branch.
    ``num_experts`` is the router's width, of which ``experts_held``
    ``[lo, hi)`` are here.  The head is untied.  No position table: a
    request is bounded by its pages.

    A request keeps, a state-space layer, a state and a tail in a SLOT
    of the cache's ``state_pool`` (:attr:`state_shape`,
    :attr:`tail_shape`), an attention layer K and V of ``num_kv_heads``
    heads a token, and an expert layer nothing (:attr:`bare_layers`).
    :meth:`from_published` reads the published ``config.json`` keys."""

    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]       # "mamba" | "moe" | "attention"
    mamba_n_heads: int
    mamba_d_head: int
    mamba_n_groups: int
    mamba_d_state: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    num_experts: int
    experts_held: Tuple[int, int]
    top_k: int
    moe_latent_size: int
    route_scale: float = 1.0
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    dtype: object = jnp.float32
    #: the recurrent state's type in the pool
    state_dtype: object = jnp.float32

    name = "nemotron_h"
    max_position = None
    latent_dim = None
    #: the renormalisation's epsilon in the published router
    route_eps = 1e-20

    @classmethod
    def from_published(cls, model: dict, **kw) -> "NemotronHConfig":
        """The configuration from a ``nemotron_h`` ``config.json``'s
        keys (``hybrid_override_pattern``, ``mamba_num_heads``,
        ``mamba_head_dim``, ``n_groups``, ``ssm_state_size``,
        ``conv_kernel``, ``chunk_size``, ``moe_latent_size``, the expert
        and attention sizes), and where a configuration file gives them
        beside those, ``router_width`` (the router's outputs, where
        ``n_routed_experts`` counts the experts held) and
        ``experts_held`` (all of them where not given); ``kw`` sets any
        field (``dtype``, ``state_dtype``, ``experts_held``).  Refuses what
        the block does not implement: dense MLP layers (``-``), an
        activation other than ``relu2``, routing that is not
        renormalised or is group limited."""
        pattern = model["hybrid_override_pattern"]
        if set(pattern) - set(NEMOTRON_H_MIXERS):
            raise ValueError(f"layers {sorted(set(pattern))}: only M, E "
                             "and * are implemented")
        if model["mlp_hidden_act"] != "relu2" or \
                not model["norm_topk_prob"] or \
                int(model.get("n_group", 1)) != 1:
            raise ValueError("relu2 experts under a renormalised router "
                             "with no group limit are implemented")
        experts = int(model.get("router_width", model["n_routed_experts"]))
        held = kw.pop("experts_held", model.get("experts_held",
                                                (0, experts)))
        return cls(
            vocab_size=int(model["vocab_size"]),
            hidden_size=int(model["hidden_size"]),
            num_heads=int(model["num_attention_heads"]),
            num_kv_heads=int(model["num_key_value_heads"]),
            head_dim=int(model["head_dim"]),
            layer_types=tuple(NEMOTRON_H_MIXERS[c] for c in pattern),
            mamba_n_heads=int(model["mamba_num_heads"]),
            mamba_d_head=int(model["mamba_head_dim"]),
            mamba_n_groups=int(model["n_groups"]),
            mamba_d_state=int(model["ssm_state_size"]),
            mamba_d_conv=int(model["conv_kernel"]),
            mamba_chunk_size=int(model["chunk_size"]),
            moe_latent_size=int(model["moe_latent_size"]),
            moe_intermediate_size=int(model["moe_intermediate_size"]),
            moe_shared_expert_intermediate_size=int(
                model["moe_shared_expert_intermediate_size"]),
            num_experts=experts,
            experts_held=tuple(int(e) for e in held),
            top_k=int(model["num_experts_per_tok"]),
            route_scale=float(model["routed_scaling_factor"]),
            rms_norm_eps=float(model["layer_norm_epsilon"]), **kw)

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        return (None,) * self.num_layers

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """The layers that keep a recurrent state and no K/V."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "mamba")

    @property
    def bare_layers(self) -> Tuple[int, ...]:
        """The layers that keep nothing of a request: the expert
        layers."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "moe")

    @property
    def page_head_dim(self) -> int:
        """A K/V head's width in the page pool: whole lane tiles."""
        return latent_width(self.head_dim)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``x`` and every group's B and
        C."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def state_shape(self) -> Tuple[int, int]:
        """A slot's state of one layer (``apex_tpu.ops.ssm``'s layout)."""
        return (self.mamba_d_state, self.d_inner)

    @property
    def tail_shape(self) -> Tuple[int, int]:
        """A slot's tail of one layer as the pool holds it: the last
        ``mamba_d_conv - 1`` rows of ``xBC``, group by group, each
        group's rows end to end (``apex_tpu.ops.ssm.tail_to_pool``)."""
        return (1, (self.mamba_d_conv - 1) * self.conv_dim)

    def block(self) -> "NemotronHBlock":
        return NemotronHBlock(self)

    def init_params(self, seed: int = 0):
        """Seeded parameters in the block's layout (the reference's):
        matrices normal with std 1/sqrt(fan_in), the mixers' output
        projections (``wout``, ``wo``, ``latent_up``, the shared
        expert's ``wd``) that over sqrt(num_layers) more, so that the
        residual stream stays O(1) through the layers; gains 1 + 0.02
        normal; the router's selection bias 0.001 normal; the
        recurrence's scalars as :meth:`GraniteHybridConfig.init_params`
        sets them."""
        d, dt = self.hidden_size, self.dtype
        hq, hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
        nh, di, ch = self.mamba_n_heads, self.d_inner, self.conv_dim
        n_held = self.experts_held[1] - self.experts_held[0]
        lat = self.moe_latent_size
        f, fs = (self.moe_intermediate_size,
                 self.moe_shared_expert_intermediate_size)
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))
        out = 1.0 / math.sqrt(self.num_layers)

        def mat(*shape, scale=1.0):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * scale / math.sqrt(shape[-2])).astype(dt)

        def gain(n):
            return (1.0 + 0.02 * jax.random.normal(
                next(keys), (n,), jnp.float32)).astype(dt)

        step = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), nh))
        layers = []
        for kind in self.layer_types:
            layer = {"norm": gain(d)}
            if kind == "mamba":
                layer.update(
                    win=mat(d, di + ch + nh),
                    conv_w=mat(self.mamba_d_conv, ch),
                    conv_b=(0.02 * jax.random.normal(
                        next(keys), (ch,), jnp.float32)).astype(dt),
                    dt_bias=jnp.log(jnp.expm1(step)).astype(dt),
                    a_log=jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(dt),
                    d_skip=gain(nh), gn=gain(di), wout=mat(di, d, scale=out))
            elif kind == "attention":
                layer.update(wq=mat(d, hq * hd), wk=mat(d, hk * hd),
                             wv=mat(d, hk * hd),
                             wo=mat(hq * hd, d, scale=out))
            else:
                layer["moe"] = {
                    "router": mat(d, self.num_experts),
                    "expert_bias": (0.001 * jax.random.normal(
                        next(keys), (self.num_experts,),
                        jnp.float32)).astype(dt),
                    "latent_down": mat(d, lat),
                    "latent_up": mat(lat, d, scale=out),
                    "experts": {"wu": mat(n_held, lat, f),
                                "wd": mat(n_held, f, lat)},
                    "shared": {"wu": mat(d, fs), "wd": mat(fs, d, scale=out)}}
            layers.append(layer)
        embed = jax.random.normal(next(keys), (self.vocab_size, d),
                                  jnp.float32).astype(dt)
        return {"embed": embed, "head": mat(d, self.vocab_size),
                "norm_f": gain(d), "layers": layers}


class NemotronHBlock:
    """The Nemotron-H block (docs/serving.md, "The sixth block"): every
    layer is ``x' = x + Mixer(RMS(x; norm))``, ONE mixer a layer; a
    final RMSNorm and an untied head.

    A STATE-SPACE layer gets ``scan(xbc, dt, layer)``, Granite's Mamba-2
    recurrence with ``mamba_n_groups`` groups of B and C (head ``h``
    reads group ``h // (H / G)``), and its gated RMSNorm ``RMS(y *
    silu(z))`` is taken within each group's ``d_inner / G`` channels.
    An ATTENTION layer gets ``attend(q, k, v, scale=)``: grouped-query
    heads of ``head_dim``, scores over ``sqrt(head_dim)``, no rotary,
    no bias.  An EXPERT layer gets nothing (``None``: it keeps no pages
    and no slot): the sigmoid router over all ``num_experts`` at full
    width, top-k on ``score + bias``, renormalised and scaled; the held
    experts' part in the latent (``latent_down``, squared-ReLU experts,
    the weighted sum, ``latent_up``), plus the squared-ReLU shared
    expert at full width (:func:`~apex_tpu.serving.experts.
    expert_layer`, ``act="relu2"``)."""

    #: as Granite's: a shared prefix needs a snapshot of the state at
    #: the page it ends on, a rejected draft a rolled-back state (the
    #: model's own draft module among them), shipped pages a shipped
    #: slot: none exists yet
    refuses = ("tp", "kv_quant", "prefix_sharing", "speculation",
               "prefill_only", "kv_import")
    stat_names = ("moe_pairs_held", "moe_load_max", "moe_rows_tiled",
                  "moe_experts_touched")
    #: where the engine's refusal sends the reader
    refuses_doc = "What Nemotron-H refuses"

    def __init__(self, cfg: NemotronHConfig):
        self.cfg = cfg

    def embed(self, params, tokens, positions):
        return params["embed"][tokens]

    def layer(self, layer, li, x, positions, mixer, *, tp_axis=None,
              valid=None, stats=None):
        cfg = self.cfg
        eps, hd = cfg.rms_norm_eps, cfg.head_dim
        lead = x.shape[:-1]
        u = _rms(x, layer["norm"], eps)
        kind = cfg.layer_types[li]
        if kind == "mamba":
            di, ch, g = cfg.d_inner, cfg.conv_dim, cfg.mamba_n_groups
            with jax.named_scope("ssm_in_proj"):
                zxbcdt = u @ layer["win"]
                z = zxbcdt[..., :di]
                xbc = zxbcdt[..., di:di + ch]
                dt = zxbcdt[..., di + ch:]
            y = mixer(xbc, dt, layer)           # float32
            with jax.named_scope("ssm_gate_norm"):
                gated = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(
                    *lead, g, di // g)
                gated = _rms(gated, layer["gn"].reshape(g, di // g), eps)
                gated = gated.reshape(*lead, di).astype(x.dtype)
            with jax.named_scope("ssm_out_proj"):
                m = gated @ layer["wout"]
        elif kind == "attention":
            q = (u @ layer["wq"]).reshape(*lead, cfg.num_heads, hd)
            k = (u @ layer["wk"]).reshape(*lead, cfg.num_kv_heads, hd)
            v = (u @ layer["wv"]).reshape(*lead, cfg.num_kv_heads, hd)
            with jax.named_scope("attn_nope"):
                ctx = mixer(q, k, v, scale=hd ** -0.5)
            m = ctx @ layer["wo"]
        else:
            m, load = expert_layer(
                u, layer["moe"], held=cfg.experts_held, top_k=cfg.top_k,
                route_scale=cfg.route_scale, valid=valid,
                route_eps=cfg.route_eps, act="relu2")
            if stats is not None:
                stats.append(
                    (load, expert_tile(u, layer["moe"], cfg.top_k)))
        return x + m

    def final_norm(self, params, x):
        return _rms(x, params["norm_f"], self.cfg.rms_norm_eps)

    def logits(self, params, x):
        return x @ params["head"]


class StateIO(NamedTuple):
    """The slot half of a hybrid cache as a paged step sees it: the
    pools of the state layers (``ssm`` ``[L_s, n_slots, N, H * P]``, None
    where a slot holds a tail alone; ``conv`` ``[L_s, n_slots, taps -
    1, ...]``), each row's slot (``slots [b]``; 0, the scratch slot, for
    an idle row) and, for a multi-token step, whether the row starts
    from zero instead of from what its slot holds (``fresh [b]``: a
    request's first chunk)."""

    ssm: jnp.ndarray
    conv: jnp.ndarray
    slots: jnp.ndarray
    fresh: jnp.ndarray


class WindowKV(NamedTuple):
    """The window-lifetime half of a two-lifetime cache as a paged step
    sees it: the pool of the sliding layers (``k``/``v`` ``[L_w,
    n_pages, page, H, D]``), each row's COMPACT table of the pages it
    still holds (``pages [b, wp_max]``) and the absolute position of
    the table's first column (``start [b]``, ``flash_decode``'s
    ``kv_start``)."""

    k: jnp.ndarray
    v: jnp.ndarray
    pages: jnp.ndarray
    start: jnp.ndarray


class PagedDecoder:
    """The decoder over the cache layouts the engine owns (the engine
    holds params/pools; this class is pure functions of them), for
    whatever block its configuration names.

    Layers with a window (``cfg.layer_windows``) live in a pool of
    their own, in layer order, and so do the others: ``pool_index[li]``
    is layer ``li``'s index in its pool."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.block = cfg.block()
        self.windows = tuple(cfg.layer_windows)
        #: the layers that keep a recurrent state in a slot and no K/V
        self.state_layers = frozenset(getattr(cfg, "state_layers", ()))
        #: the layers that keep nothing of a request, neither pages nor
        #: a slot (Nemotron-H's expert layers): their mixer is None
        self.bare_layers = frozenset(getattr(cfg, "bare_layers", ()))
        counts = {"full": 0, "window": 0, "state": 0, "bare": 0}
        index = []
        for li, w in enumerate(self.windows):
            family = ("state" if li in self.state_layers
                      else "bare" if li in self.bare_layers
                      else "full" if w is None else "window")
            index.append(counts[family])
            counts[family] += 1
        self.pool_index = tuple(index)
        self.full_layers, self.window_layers = (counts["full"],
                                                counts["window"])
        self.n_state_layers = counts["state"]
        #: what a slot holds of a state layer: a Mamba-2 state and a
        #: tail, or (a short convolution) a tail alone
        self.state_planes = (
            () if not self.n_state_layers
            else ("conv",) if cfg.state_shape is None else ("ssm", "conv"))
        self.stat_names = self.block.stat_names
        #: a latent model: one vector a token, one pool operand
        self.latent = cfg.latent_dim is not None
        #: the width a K/V head has in the pool (wider than the head
        #: where the configuration stores it in whole lane tiles)
        self.page_head_dim = getattr(cfg, "page_head_dim", cfg.head_dim)
        #: the stacks a prefill returns after the logits: K/V, as many
        #: as the cache's pools have unquantized operands, then the
        #: state layers' final planes (states and tails, or tails)
        self.kv_stacks = ((1 if self.latent
                           else 4 if self.window_layers else 2)
                          + len(self.state_planes))

    # -- heads under a lane tile ------------------------------------------

    def _stored(self, *heads):
        """``[..., h, head_dim]`` -> the pool's ``[..., h,
        page_head_dim]``, zeros after: a query so padded scores a stored
        key as the head itself scores it, and what comes back has the
        head's own numbers first."""
        pad = self.page_head_dim - self.cfg.head_dim
        if not pad:
            return heads
        return tuple(jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
                     for a in heads)

    def _ssm_row(self, p, xbc, dt, valid, state_in, tail_in):
        """One row of a prefill or a chunk through a state-space
        layer's convolution and chunked scan: ``xbc`` ``[S, ch]``,
        ``dt`` ``[S, H]``, from (state, tail) to the new ones.  Returns
        (y ``[S, H * P]`` float32, state, tail); the tails as the pool
        holds them (:attr:`GraniteHybridConfig.tail_shape`)."""
        cfg = self.cfg
        di, n, g = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_n_groups
        with jax.named_scope("ssm_conv"):
            conv, tail = causal_conv(
                xbc, tail_from_pool(tail_in, di, n, g, cfg.mamba_d_conv),
                valid, p["conv_w"], p["conv_b"])
            conv = conv.astype(xbc.dtype)
            tail = tail_to_pool(tail, di, n, g)
        b, c = conv[:, di:di + g * n], conv[:, di + g * n:]
        if g > 1:
            b, c = b.reshape(-1, g, n), c.reshape(-1, g, n)
        y, state = ssd_chunk_scan(
            conv[:, :di].reshape(-1, cfg.mamba_n_heads, cfg.mamba_d_head),
            dt, p["a_log"], b, c, p["d_skip"], state_in, valid,
            dt_bias=p["dt_bias"], chunk=cfg.mamba_chunk_size)
        return y, state, tail

    def _expanded(self, attention, seg, kept):
        """A latent layer's ``attend`` for a whole row, no cache yet:
        the equations as written.  K and V of every head come from the
        row's own ``c`` (``wuk``, ``wuv``), the rotary key is every
        head's; Q/K and V are zero-padded to one width of whole lane
        tiles, which changes no score and no sum.  The row's latent
        vectors are kept for the pool."""
        cfg = self.cfg
        width, nope = cfg.head_dim, cfg.qk_nope_head_dim

        def attend(q_nope, q_pe, latent, wuk, wuv, *, scale):
            b, s, nh = q_nope.shape[:3]
            kept.append(latent)
            with jax.named_scope("mla_expand"):
                c = latent[..., :cfg.kv_lora_rank]
                k_nope = (c @ wuk).reshape(b, s, nh, nope)
                v = (c @ wuv).reshape(b, s, nh, cfg.v_head_dim)
                k_pe = jnp.broadcast_to(
                    latent[..., None, cfg.kv_lora_rank:],
                    (b, s, nh, cfg.qk_rope_head_dim))
                q = pad_latent(jnp.concatenate([q_nope, q_pe], -1), width)
                k = pad_latent(jnp.concatenate([k_nope, k_pe], -1), width)
                v = pad_latent(v, width)
            ctx = attention(q, k, v, seg, window=None, scale=scale)
            ctx = ctx.transpose(0, 2, 1, 3)[..., :cfg.v_head_dim]
            return ctx.reshape(b, s, -1)

        return attend

    def _stats(self, stats, walk=(0, 0)):
        """The block's per-launch counters as one int32 vector, in
        ``stat_names`` order: the (token, expert) pairs its held experts
        took, summed over layers; the most any one took; the rows the
        grouped product multiplied (each layer's active row tiles times
        its tile), summed over layers; lfm2_moe: the held experts that
        took any, summed over layers; deepseek_v2: ``walk``, the blocks
        of latent pages a layer's call had to see and those it fetched,
        0 where no call walked pages.  ``stats``: a ``(load, tm)`` a
        layer."""
        with jax.named_scope("counters"):
            load = jnp.stack([pairs for pairs, _ in stats])
            named = {"moe_pairs_held": jnp.sum(load),
                     "moe_load_max": jnp.max(load),
                     "moe_rows_tiled": sum(rows_tiled(pairs, tm)
                                           for pairs, tm in stats),
                     "moe_experts_touched": jnp.sum(load > 0),
                     "latent_blocks_walked": walk[0],
                     "latent_blocks_fetched": walk[1]}
            return jnp.stack([named[n] for n in self.stat_names]).astype(
                jnp.int32)

    # -- admission: packed varlen prefill --------------------------------

    def prefill(self, params, tokens: jnp.ndarray, seg: jnp.ndarray,
                positions: jnp.ndarray,
                last_index: Optional[jnp.ndarray] = None,
                *, tp_axis: Optional[str] = None):
        """tokens/seg/positions ``[1, S]`` (one packed row; seg 0 =
        padding, real segments 1..n; positions restart per segment).
        Returns (logits, k, v ``[L, 1, S, H, D]``) — K/V for every
        packed position, for the engine to scatter into pages.  A
        model with window layers returns two more, ``(logits, k, v,
        wk, wv)``: the full layers' K/V and then the window layers',
        each for its own pool; a model with state-space layers the
        row's final states ``[L_s, 1, N, H * P]`` and tails ``[L_s, 1,
        taps - 1, ch]`` for its slot (the K/V then of its attention
        layers alone); a block with counters appends them last.

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
        block = self.block
        with jax.named_scope("embedding"):
            x = block.embed(params, tokens, positions)
        kept = {False: ([], []), True: ([], [])}
        stats = []

        # a jit of this trace's own: the layers of one geometry (a
        # window or none) share one tracing and one lowering of the
        # kernel, where every layer's call used to bring its own
        # (ISSUE 32: of a 24-layer row's 2.5-4.4 s of warm set-up on
        # the chip, at every width the engine warms).  XLA inlines the
        # calls, so the executable computes what it did.  Not a
        # module-level jit: the route is chosen while tracing
        # (``routing_override``), and a cache that outlived this trace
        # would hand the next one a route it did not choose.
        @functools.partial(jax.jit, static_argnames=("window", "scale"))
        def attention(q, k, v, seg, window, scale=None):
            return flash_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True,
                segment_ids=seg, window=window, scale=scale)

        states, tails = [], []

        def scan(xbc, dt, p):
            """A state-space layer's mixer over a whole row: from a
            zero state and tail; the final ones are kept for the slot."""
            cfg = self.cfg
            zero = (jnp.zeros(cfg.state_shape, jnp.float32),
                    jnp.zeros(cfg.tail_shape, xbc.dtype))
            y, state, tail = jax.vmap(
                lambda xbc, dt, valid: self._ssm_row(
                    p, xbc, dt, valid, *zero))(xbc, dt, seg != 0)
            states.append(state)
            tails.append(tail)
            return y

        def short_conv(v, p):
            """A short-convolution layer's mixer over a whole row: from
            a zero tail; the final one is kept for the slot."""
            zero = jnp.zeros(self.cfg.tail_shape, v.dtype)
            c, tail = jax.vmap(lambda v, valid: causal_conv(
                v, zero, valid, p["conv_w"], None, None))(v, seg != 0)
            tails.append(tail)
            return c

        def attend_in(li):
            if li in self.bare_layers:
                return None
            if li in self.state_layers:
                return scan if "ssm" in self.state_planes else short_conv
            if self.latent:
                return self._expanded(attention, seg, kept[False][0])
            window = self.windows[li]
            ks, vs = kept[window is not None]

            def attend(q, k, v, scale=None):
                b, s = q.shape[:2]
                ctx = attention(q, k, v, seg, window=window, scale=scale)
                k, v = self._stored(k, v)
                ks.append(k)
                vs.append(v)
                return ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)

            return attend

        for li, layer in enumerate(params["layers"]):
            with jax.named_scope("layer"):
                x = block.layer(layer, li, x, positions, attend_in(li),
                                tp_axis=tp_axis, valid=seg != 0,
                                stats=stats)
        with jax.named_scope("head"):
            x = block.final_norm(params, x)
            if last_index is not None:
                x = jax.lax.dynamic_slice_in_dim(
                    x, jnp.asarray(last_index, jnp.int32), 1, axis=1)
            logits = block.logits(params, x)
        out = (logits, jnp.stack(kept[False][0]))
        if not self.latent:
            out += (jnp.stack(kept[False][1]),)
        if self.window_layers:
            out += (jnp.stack(kept[True][0]), jnp.stack(kept[True][1]))
        if states:
            out += (jnp.stack(states).astype(self.cfg.state_dtype),)
        if tails:
            out += (jnp.stack(tails),)
        if stats:
            out += (self._stats(stats),)
        return out

    # -- the paged step: append, then attend over the pages --------------

    def _paged(self, params, k_pool, v_pool, tokens, positions,
               write_pages, write_offsets, page_table, kv_len, *,
               k_scale, v_scale, tp_axis, window: Optional[WindowKV],
               state: Optional[StateIO] = None):
        """What :meth:`decode` (lead shape ``[b]``) and :meth:`extend`
        (``[b, q]``) share: every layer appends its tokens' K/V at
        ``(write_pages, write_offsets)`` of its pool and attends over
        the row's pages.  Returns (final-norm hidden states, pools in
        executable order, counters).

        Every layer hands ``flash_decode`` the WHOLE pool ``[L,
        n_pages, ps, H, hd]`` (lane-dense: ``[L, n_pages, ps, H * hd]``,
        and the tokens are appended in that form) and ``layer=``, never
        ``k_pool[li]``:
        the kernel reads that layer's pages where they lie, and a
        slice would be copied out first (all of a layer's pages, twice,
        before each of the L calls).  The append before it stays in
        place: the call reads the buffer the scatter wrote.

        A window layer appends into ``window``'s pool instead.  Its
        targets are worked out here from the row's compact table (slot
        ``position // page - start // page``); a row that writes the
        full pool's scratch page (padding, an idle row) writes the
        window pool's too."""
        block = self.block
        lead = tokens.shape
        b = lead[0]
        quantized = k_scale is not None
        qmax = quant_qmax(k_pool.dtype) if quantized else None
        real = write_pages != 0
        pools = {False: [k_pool, v_pool, k_scale, v_scale]}
        targets = {False: (write_pages, write_offsets)}
        tables = {False: dict(page_table=page_table)}
        if window is not None:
            ps = window.k.shape[2]
            slot = (positions.reshape(b, -1) // ps
                    - (window.start // ps)[:, None])
            held = jnp.take_along_axis(
                window.pages, jnp.clip(slot, 0, window.pages.shape[1] - 1),
                axis=1).reshape(lead)
            pools[True] = [window.k, window.v, None, None]
            targets[True] = (jnp.where(real & (slot.reshape(lead) >= 0),
                                       held, 0), write_offsets)
            tables[True] = dict(page_table=window.pages,
                                kv_start=window.start)
        stats = []
        if self.latent:
            # a chunk's rows before its first real one are padding (they
            # write the scratch page): the kernel walks nothing for them
            q_start = (None if real.ndim == 1 else
                       real.shape[1] - jnp.sum(real, axis=1, dtype=jnp.int32))
            # which rows walk which pages together: the same for every
            # layer, so made once a step (docs/serving.md, "The latent
            # page")
            tiles = latent_walk_tiles(
                page_table, kv_len, q_len=1 if len(lead) == 1 else lead[1],
                heads=self.cfg.num_heads, page_size=k_pool.shape[2],
                q_start=q_start)

        def absorbed(pi):
            """A latent layer's ``attend`` over the latent pages: the
            token's vector is appended, ``wuk`` is folded into the
            query (one key of ``latent_dim`` for every head) and
            ``wuv`` applied to what comes back: the same mathematics as
            the expanded form, with nothing expanded."""
            cfg = self.cfg
            pool = pools[False]
            rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim

            def attend(q_nope, q_pe, latent, wuk, wuv, *, scale):
                nh = q_nope.shape[-2]
                width = pool[0].shape[-1]
                pool[0] = pool[0].at[pi, write_pages, write_offsets].set(
                    pad_latent(latent, width))
                with jax.named_scope("mla_absorb"):
                    q_abs = jnp.einsum(
                        "...hd,chd->...hc", q_nope,
                        wuk.reshape(rank, nh, nope))
                    q = pad_latent(jnp.concatenate([q_abs, q_pe], -1), width)
                o = flash_decode_latent(
                    q.reshape(b, -1, nh, width), pool[0], page_table,
                    kv_len, v_dim=rank, scale=scale, layer=pi,
                    q_start=q_start, tiles=tiles)
                with jax.named_scope("mla_absorb"):
                    ctx = jnp.einsum(
                        "...hc,chd->...hd", o.reshape(*lead, nh, rank),
                        wuv.reshape(rank, nh, cfg.v_head_dim))
                return ctx.reshape(*lead, -1)

            return attend

        slot_pools = [state.ssm, state.conv] if state is not None else None

        def scan_in(pi):
            cfg = self.cfg

            def step(xbc, dt, p):
                # one token a row: the slot advanced where it lies
                y, slot_pools[0], slot_pools[1] = ssm_decode_update(
                    slot_pools[0], slot_pools[1], state.slots, xbc, dt,
                    layer=pi, conv_w=p["conv_w"], conv_b=p["conv_b"],
                    dt_bias=p["dt_bias"], a_log=p["a_log"],
                    d_skip=p["d_skip"], heads=cfg.mamba_n_heads,
                    groups=cfg.mamba_n_groups)
                return y

            def scan(xbc, dt, p):
                # a chunk a row, from what the slot holds (or zero) to
                # what it holds next: a slice in and a slice out, row
                # by row (a scatter into a pool of gigabytes is not
                # reliably in place)
                ys = []
                for i in range(b):
                    slot, keep = state.slots[i], state.fresh[i] == 0
                    at = lambda pool: jax.lax.dynamic_index_in_dim(
                        pool[pi], slot, keepdims=False)
                    y, s_out, t_out = self._ssm_row(
                        p, xbc[i], dt[i], real[i],
                        jnp.where(keep, at(slot_pools[0]), 0).astype(
                            jnp.float32),
                        jnp.where(keep, at(slot_pools[1]), 0))
                    for j, new in enumerate((s_out, t_out)):
                        slot_pools[j] = jax.lax.dynamic_update_slice(
                            slot_pools[j],
                            new[None, None].astype(slot_pools[j].dtype),
                            (pi, slot, 0, 0))
                    ys.append(y)
                return jnp.stack(ys)

            return step if tokens.ndim == 1 else scan

        def conv_in(pi):
            def step(v, p):
                # one token a row against its slot's tail, in place
                c, slot_pools[1] = short_conv_update(
                    slot_pools[1], state.slots, v, layer=pi,
                    conv_w=p["conv_w"])
                return c

            def chunk(v, p):
                # a chunk a row from its slot's tail (or zero), the new
                # tail back into the slot, as ``scan`` does
                cs = []
                for i in range(b):
                    slot, keep = state.slots[i], state.fresh[i] == 0
                    held = jax.lax.dynamic_index_in_dim(
                        slot_pools[1][pi], slot, keepdims=False)
                    c, tail = causal_conv(
                        v[i], jnp.where(keep, held, 0), real[i],
                        p["conv_w"], None, None)
                    slot_pools[1] = jax.lax.dynamic_update_slice(
                        slot_pools[1],
                        tail[None, None].astype(slot_pools[1].dtype),
                        (pi, slot, 0, 0))
                    cs.append(c)
                return jnp.stack(cs)

            return step if tokens.ndim == 1 else chunk

        def attend_in(li):
            pi = self.pool_index[li]
            if li in self.bare_layers:
                return None
            if li in self.state_layers:
                return (scan_in(pi) if "ssm" in self.state_planes
                        else conv_in(pi))
            w = self.windows[li]
            pool = pools[w is not None]
            pages, offsets = targets[w is not None]
            if self.latent:
                return absorbed(pi)

            def attend(q, k, v, scale=None):
                own = q.shape[-1]
                q, k, v = self._stored(q, k, v)
                nh, hd = q.shape[-2:]
                k_new, v_new = k, v
                if quantized:
                    k_new, k_s = quantize_tokens(k_new, pool[0].dtype, qmax)
                    v_new, v_s = quantize_tokens(v_new, pool[1].dtype, qmax)
                    pool[2] = pool[2].at[pi, pages, offsets].set(k_s)
                    pool[3] = pool[3].at[pi, pages, offsets].set(v_s)
                pool[0] = pool[0].at[pi, pages, offsets].set(
                    as_stored(pool[0], k_new))
                pool[1] = pool[1].at[pi, pages, offsets].set(
                    as_stored(pool[1], v_new))
                q4 = q.reshape(b, -1, nh, hd).transpose(0, 2, 1, 3)
                kw = dict(tables[w is not None])
                if w is not None:
                    kw["window"] = w
                ctx = flash_decode(
                    q4, pool[0], pool[1], kw.pop("page_table"), kv_len,
                    scale=scale, layer=pi, k_scale=pool[2],
                    v_scale=pool[3], **kw)
                return ctx.transpose(0, 2, 1, 3)[..., :own].reshape(
                    *lead, -1)

            return attend

        with jax.named_scope("embedding"):
            x = block.embed(params, tokens, positions)
        for li, layer in enumerate(params["layers"]):
            with jax.named_scope("layer"):
                x = block.layer(layer, li, x, positions, attend_in(li),
                                tp_axis=tp_axis, valid=real, stats=stats)
        out = tuple(pools[False][:4 if quantized
                                 else 1 if self.latent else 2])
        if window is not None:
            out += tuple(pools[True][:2])
        if state is not None:
            out += tuple(a for a in slot_pools if a is not None)
        if stats:
            out += (self._stats(stats, (tiles.walked, tiles.fetched)
                                if self.latent else (0, 0)),)
        return x, out

    # -- steady state: paged decode --------------------------------------

    def decode(self, params, k_pool, v_pool, tokens: jnp.ndarray,
               positions: jnp.ndarray, page_table: jnp.ndarray,
               kv_len: jnp.ndarray, *,
               k_scale: Optional[jnp.ndarray] = None,
               v_scale: Optional[jnp.ndarray] = None,
               tp_axis: Optional[str] = None,
               window: Optional[WindowKV] = None,
               state: Optional[StateIO] = None):
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
        With ``window`` (:class:`WindowKV`) the window pool's k and v
        follow the full pool's, with ``state`` (:class:`StateIO`) the
        state pool's planes after those (states and tails, or tails),
        and a block's counters come last.
        ``tp_axis``: per-shard body under ``shard_map`` (local head
        slice of pool and scales, one ``psum`` per block).

        The append and the attention over the pages are
        :meth:`_paged`'s; :meth:`extend` does the same."""
        page_size = k_pool.shape[2]     # a latent pool's too
        with jax.named_scope("pages"):
            page_slot = positions // page_size
            page_idx = jnp.take_along_axis(
                page_table, page_slot[:, None], axis=1)[:, 0]
            offset = positions % page_size
        x, out = self._paged(
            params, k_pool, v_pool, tokens, positions, page_idx, offset,
            page_table, kv_len, k_scale=k_scale, v_scale=v_scale,
            tp_axis=tp_axis, window=window, state=state)
        with jax.named_scope("head"):
            logits = self.block.logits(
                params, self.block.final_norm(params, x))
        return (logits,) + out

    # -- draft–verify / chunked prefill: multi-token extension -----------

    def extend(self, params, k_pool, v_pool, tokens: jnp.ndarray,
               positions: jnp.ndarray, write_pages: jnp.ndarray,
               write_offsets: jnp.ndarray, page_table: jnp.ndarray,
               kv_len: jnp.ndarray, *, last_only: bool = False,
               k_scale: Optional[jnp.ndarray] = None,
               v_scale: Optional[jnp.ndarray] = None,
               tp_axis: Optional[str] = None,
               window: Optional[WindowKV] = None,
               state: Optional[StateIO] = None):
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
        valid token to row ``q - 1``.  ``k_scale``/``v_scale``,
        ``window``, ``state`` and ``tp_axis``: as in :meth:`decode`
        (quantize-on-write appends / the window pool / per-shard
        ``shard_map`` body).  Returns (logits ``[b, q, vocab]`` or
        ``[b, 1, vocab]``, k_pool', v_pool'[, k_scale', v_scale']
        [, wk', wv'][, counters]).
        """
        x, out = self._paged(
            params, k_pool, v_pool, tokens, positions, write_pages,
            write_offsets, page_table, kv_len, k_scale=k_scale,
            v_scale=v_scale, tp_axis=tp_axis, window=window, state=state)
        with jax.named_scope("head"):
            x = self.block.final_norm(params, x)
            if last_only:
                x = x[:, -1:, :]
            logits = self.block.logits(params, x)
        return (logits,) + out

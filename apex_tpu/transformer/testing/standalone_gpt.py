"""Standalone Megatron GPT — the reference testing model, TPU-native.

Re-design of ``apex.transformer.testing.standalone_gpt``
(reference standalone_gpt.py: GPTModel :1426, gpt_model_provider :1502,
ParallelMLP :234, ParallelAttention :283, ParallelTransformerLayer :575,
ParallelTransformer :711).

Structure parity (pre-LN GPT-2 architecture, untied pieces noted):

* vocab-parallel word embedding + learned position embedding,
* N × ParallelTransformerLayer:
    LN → ParallelAttention (ColumnParallel QKV → causal fused softmax →
    RowParallel proj) → residual → LN → ParallelMLP (ColumnParallel h→4h →
    GELU → RowParallel 4h→h) → residual,
* final LN, logits through the (vocab-parallel) word-embedding transpose,
* loss = vocab-parallel cross entropy.

TPU-native choices: layers are stacked and applied with ``lax.scan``
(constant compile time in depth); attention softmax is the fused
:class:`apex_tpu.ops.FusedScaleMaskSoftmax` causal kernel; all TP
communication comes from the plain-collective mappings, so the backward
all-reduces are derived by AD.  ``apply`` must run inside a region binding
the "tensor" axis.  Dropout is deterministic-off by default so pipeline /
TP parity tests are exact (reference tests run in eval-determinism too).

For pipeline parallelism, :func:`gpt_stage_fn` / :func:`gpt_loss_fn` adapt
the model to the compiled schedules: stage 0 embeds, the last stage applies
the head — selected with ``jnp.where`` on the stage index (SPMD-uniform).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops import (
    AttnMaskType,
    FusedScaleMaskSoftmax,
    layer_norm,
)
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.random import (
    dropout as _dropout,
    model_parallel_dropout_key,
)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Network-size args (reference testing/arguments.py network-size group)."""

    num_layers: int = 2
    hidden_size: int = 64
    num_attention_heads: int = 4
    vocab_size: int = 128
    max_position_embeddings: int = 64
    ffn_hidden_size: Optional[int] = None
    layernorm_epsilon: float = 1e-5
    init_method_std: float = 0.02
    fp16: bool = False
    bf16: bool = False
    tp_size: int = 1
    # dropout (reference ParallelAttention :283 / ParallelMLP-consumer
    # bias_dropout_add :575 / Embedding dropout): active only when a
    # ``dropout_key`` is passed to ``apply`` (training mode); parity and
    # eval runs simply pass no key
    attention_dropout: float = 0.0
    hidden_dropout: float = 0.0
    # TPU-first extensions beyond the reference's arguments set:
    # use the Pallas flash kernel for causal self-attention (no S×S
    # probs materialised) and rematerialise each layer in backward
    use_flash_attention: bool = False
    remat: bool = False
    # what the per-layer checkpoint saves: "full" recomputes the whole
    # layer (max memory savings, ~33% extra flops); "dots" saves matmul
    # outputs and recomputes only the cheap pointwise ops
    # (jax.checkpoint_policies.dots_saveable) — near-zero recompute
    # flops at ~4× the activation footprint of "full"
    remat_policy: str = "full"
    # Mixture-of-Experts: num_experts > 0 replaces every layer's MLP
    # with a Switch-routed expert MLP (apex_tpu.transformer.moe) —
    # experts replicated across TP; shard them over an expert mesh axis
    # by using SwitchMLP directly.  aux loss (load balancing) is folded
    # into the returned per-token losses so mean(losses) includes it.
    num_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coeff: float = 1e-2
    # flash kernel tile sizes (512² measured best for fwd+bwd at the
    # GPT-350M shape bh=128 s=1024 d=64; the 512/1024 library defaults
    # favor long sequences)
    flash_block_q: int = 512
    flash_block_k: int = 512

    @property
    def ffn(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def compute_dtype(self):
        if self.bf16:
            return jnp.bfloat16
        if self.fp16:
            return jnp.float16
        return jnp.float32

    @property
    def kv_channels(self) -> int:
        return self.hidden_size // self.num_attention_heads


def _normal_init(std):
    def init(key, shape):
        return jax.random.normal(key, shape) * std

    return init


class ParallelAttention:
    """Causal self-attention (reference standalone_gpt.py:283-546)."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg
        self.qkv = ColumnParallelLinear(
            cfg.hidden_size, 3 * cfg.hidden_size, gather_output=False,
            init_method=_normal_init(cfg.init_method_std), tp_size=cfg.tp_size)
        self.proj = RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size, input_is_parallel=True,
            init_method=_normal_init(cfg.init_method_std), tp_size=cfg.tp_size)
        self.softmax = FusedScaleMaskSoftmax(
            input_in_fp16=cfg.fp16, input_in_bf16=cfg.bf16,
            attn_mask_type=AttnMaskType.causal,
            scaled_masked_softmax_fusion=True, softmax_in_fp32=True,
            scale=None)
        self.np_local = cfg.num_attention_heads // cfg.tp_size

    def init_master(self, key):
        k1, k2 = jax.random.split(key)
        return {"qkv": self.qkv.init_master(k1), "proj": self.proj.init_master(k2)}

    def shard_master(self, master, rank):
        return {"qkv": self.qkv.shard_master(master["qkv"], rank),
                "proj": self.proj.shard_master(master["proj"], rank)}

    def apply(self, params, h, attention_mask=None, dropout_key=None,
              segment_ids=None):
        # h: [b, s, hidden]; segment_ids: int [b, s] varlen-packing ids
        # (pad tokens in their own bucket) — masks cross-segment scores
        cfg = self.cfg
        do_dropout = dropout_key is not None and cfg.attention_dropout > 0.0
        b, s, _ = h.shape
        qkv = self.qkv.apply(params["qkv"], h)  # [b, s, 3*hidden/tp]
        # flash-path dropout runs IN-KERNEL (counter-hash masks, FMHA
        # parity) — the seed derives from the per-TP-rank stream so
        # head-sharded probs drop independently per rank
        flash_drop = {}
        if cfg.use_flash_attention and do_dropout:
            seed = jax.random.bits(
                model_parallel_dropout_key(dropout_key), (),
                jnp.uint32).astype(jnp.int32)
            flash_drop = dict(dropout_rate=cfg.attention_dropout,
                              dropout_seed=seed)
        # the module's mask type, not the mask's presence, decides
        # causality (GPT: causal even WITH an extra padding mask)
        is_causal = self.softmax.attn_mask_type == AttnMaskType.causal
        is_key_padding = (attention_mask is not None
                          and attention_mask.ndim == 4
                          and attention_mask.shape[1] == 1
                          and attention_mask.shape[2] == 1)
        if cfg.use_flash_attention and (
                attention_mask is None or is_key_padding):
            # Packed flash kernel: consumes the QKV projection output
            # directly in its interleaved per-head layout and emits
            # dqkv the same way — no head transposes in forward,
            # recompute, or backward (r5; ~10 ms/step of layout copies
            # at the 350M bench shape).  Varlen shapes STAY on it (r7):
            # explicit packing ids, and KEY-PADDING masks ([b, 1, 1, s],
            # True = masked key — the BERT form) as segment ids with
            # all-ones query ids, reproducing key-side-only masking
            # exactly (pad QUERY rows still attend real keys, like the
            # reference's additive mask; the reference FMHA existed for
            # precisely this BERT varlen case, fmha.py:33-75).  The
            # segment predicate is fused in-kernel and fully-masked
            # k-blocks are skipped via the block-skip index; composes
            # with the causal flag for causal-model + padding callers.
            from apex_tpu.ops.attention import flash_attention_qkv

            seg = None
            if segment_ids is not None:
                seg = segment_ids
                if is_key_padding:
                    # fold padding into the packing ids: pad keys get a
                    # bucket no real segment uses (ids are >= 0), so no
                    # query row — any packing id — attends a pad key
                    pad = attention_mask[:, 0, 0, :].astype(bool)
                    seg = (seg, jnp.where(pad, -1, seg))
            elif is_key_padding:
                keep = (~attention_mask[:, 0, 0, :].astype(bool)).astype(
                    jnp.int32)  # [b, s], 1 = real token
                seg = (jnp.ones_like(keep), keep)
            ctx = flash_attention_qkv(
                qkv, self.np_local, causal=is_causal, segment_ids=seg,
                block=cfg.flash_block_q, block_k=cfg.flash_block_k,
                **flash_drop).astype(h.dtype)
            return self.proj.apply(params["proj"], ctx)
        qkv = qkv.reshape(b, s, self.np_local, 3 * cfg.kv_channels)
        q, k, v = jnp.split(qkv, 3, axis=-1)  # each [b, s, np, hn]
        # scores [b, np, s, s]; scale 1/sqrt(hn) matches norm_factor (:389)
        scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.kv_channels, jnp.float32))
        scores = jnp.einsum("bqnh,bknh->bnqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = (scores * scale).astype(h.dtype)
        if segment_ids is not None:
            # reference path for the packed form: cross-segment scores
            # masked through the same boolean-mask softmax (True =
            # masked) the padding variant uses — the parity anchor for
            # the flash packed path
            seg_mask = (segment_ids[:, None, :, None]
                        != segment_ids[:, None, None, :])
            if attention_mask is not None:
                seg_mask = seg_mask | attention_mask.astype(bool)
            attention_mask = seg_mask
        probs = self.softmax(scores, attention_mask)
        if do_dropout:
            # probs are head-sharded over TP: per-rank stream (reference
            # wraps this dropout in get_cuda_rng_tracker().fork(), :283)
            probs = _dropout(probs, cfg.attention_dropout,
                             model_parallel_dropout_key(dropout_key))
        ctx = jnp.einsum("bnqk,bknh->bqnh", probs, v,
                         preferred_element_type=jnp.float32).astype(h.dtype)
        ctx = ctx.reshape(b, s, self.np_local * cfg.kv_channels)
        return self.proj.apply(params["proj"], ctx)


class ParallelMLP:
    """h → 4h → h with fused GELU (reference standalone_gpt.py:234-281)."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg
        self.dense_h_to_4h = ColumnParallelLinear(
            cfg.hidden_size, cfg.ffn, gather_output=False,
            init_method=_normal_init(cfg.init_method_std), tp_size=cfg.tp_size)
        self.dense_4h_to_h = RowParallelLinear(
            cfg.ffn, cfg.hidden_size, input_is_parallel=True,
            init_method=_normal_init(cfg.init_method_std), tp_size=cfg.tp_size)

    def init_master(self, key):
        k1, k2 = jax.random.split(key)
        return {"dense_h_to_4h": self.dense_h_to_4h.init_master(k1),
                "dense_4h_to_h": self.dense_4h_to_h.init_master(k2)}

    def shard_master(self, master, rank):
        return {
            "dense_h_to_4h": self.dense_h_to_4h.shard_master(
                master["dense_h_to_4h"], rank),
            "dense_4h_to_h": self.dense_4h_to_h.shard_master(
                master["dense_4h_to_h"], rank),
        }

    def apply(self, params, h):
        from jax.ad_checkpoint import checkpoint_name

        inter = self.dense_h_to_4h.apply(params["dense_h_to_4h"], h)
        # named for remat_policy="attn_res_mlp": the PRE-gelu h→4h output
        # is the one tensor whose save removes the layer's biggest GEMM
        # (4h² of the 12h² per-layer GEMM flops) from the remat
        # recompute — gelu's backward needs this value, gelu/4h→h-wgrad
        # inputs rebuild from it elementwise, and the 4h→h forward
        # output is dead in the recompute graph (nothing in the backward
        # reads it)
        inter = checkpoint_name(inter, "mlp_4h")
        inter = jax.nn.gelu(inter, approximate=True)  # bias_gelu fusion (:250)
        return self.dense_4h_to_h.apply(params["dense_4h_to_h"], inter)


def embedding_dropout(h, cfg, dropout_key):
    """Dropout on the embedding output (reference Embedding.forward
    applies hidden_dropout before the first layer).  Replicated stream;
    one shared derivation so GPT and BERT keep identical RNG
    conventions."""
    if dropout_key is None or cfg.hidden_dropout <= 0.0:
        return h
    return _dropout(h, cfg.hidden_dropout,
                    jax.random.fold_in(dropout_key, 0x0E0B))


def _hidden_dropout(x, cfg, key):
    """Post-RowParallel hidden dropout: the activation is TP-replicated, so
    the *base* (replicated) key is correct — every rank must drop the same
    elements or the replicas diverge (reference bias_dropout_add :575 runs
    on the default RNG stream)."""
    if key is None or cfg.hidden_dropout <= 0.0:
        return x
    return _dropout(x, cfg.hidden_dropout, key)


class ParallelTransformerLayer:
    """Pre-LN block (reference standalone_gpt.py:575-709); with
    ``cfg.num_experts > 0`` the MLP is a Switch-routed expert MLP."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg
        self.attention = ParallelAttention(cfg)
        if cfg.num_experts > 0:
            from apex_tpu.transformer.moe import MoEConfig, SwitchMLP

            self.mlp = SwitchMLP(MoEConfig(
                hidden_size=cfg.hidden_size, ffn_hidden_size=cfg.ffn,
                num_experts=cfg.num_experts,
                capacity_factor=cfg.moe_capacity_factor,
                init_method_std=cfg.init_method_std))
        else:
            self.mlp = ParallelMLP(cfg)

    def init_master(self, key):
        k1, k2 = jax.random.split(key)
        h = self.cfg.hidden_size
        return {
            "input_layernorm": {"weight": jnp.ones((h,)), "bias": jnp.zeros((h,))},
            "attention": self.attention.init_master(k1),
            "post_attention_layernorm": {"weight": jnp.ones((h,)),
                                         "bias": jnp.zeros((h,))},
            "mlp": self.mlp.init_master(k2),
        }

    def shard_master(self, master, rank):
        if self.cfg.num_experts > 0:
            # experts are replicated across TP (shard them over an
            # expert axis with SwitchMLP.shard_master directly)
            mlp = master["mlp"]
        else:
            mlp = self.mlp.shard_master(master["mlp"], rank)
        return {
            "input_layernorm": master["input_layernorm"],
            "attention": self.attention.shard_master(master["attention"], rank),
            "post_attention_layernorm": master["post_attention_layernorm"],
            "mlp": mlp,
        }

    def apply(self, params, h, attention_mask=None, dropout_key=None,
              segment_ids=None):
        """Returns ``(h, aux)`` — ``aux`` is the MoE load-balancing loss
        (0.0 for the dense MLP)."""
        cfg = self.cfg
        eps = cfg.layernorm_epsilon
        k_attn = k_h1 = k_h2 = None
        if dropout_key is not None:
            k_attn, k_h1, k_h2 = (jax.random.fold_in(dropout_key, i)
                                  for i in range(3))
        ln1 = layer_norm(h, params["input_layernorm"]["weight"],
                         params["input_layernorm"]["bias"], eps=eps)
        attn = self.attention.apply(params["attention"], ln1, attention_mask,
                                    dropout_key=k_attn,
                                    segment_ids=segment_ids)
        # named for remat_policy="attn_out": saving just this [b,s,h]
        # tensor per layer (16 MB at the 350M bench shape) removes the
        # whole attention region from the remat recompute
        from jax.ad_checkpoint import checkpoint_name

        attn = checkpoint_name(attn, "attn_out")
        h = h + _hidden_dropout(attn, cfg, k_h1)
        ln2 = layer_norm(h, params["post_attention_layernorm"]["weight"],
                         params["post_attention_layernorm"]["bias"], eps=eps)
        if cfg.num_experts > 0:
            b, s, hid = ln2.shape
            out, aux = self.mlp.apply(params["mlp"], ln2.reshape(b * s, hid))
            out = out.reshape(b, s, hid).astype(h.dtype)
        else:
            out, aux = self.mlp.apply(params["mlp"], ln2), jnp.zeros((),
                                                                    jnp.float32)
        return h + _hidden_dropout(out, cfg, k_h2), aux


class ParallelTransformer:
    """Stack of layers applied with lax.scan (reference :711-1040 keeps a
    ModuleList; scanning is the compile-time-friendly TPU equivalent)."""

    def __init__(self, cfg: GPTConfig, num_layers: Optional[int] = None):
        self.cfg = cfg
        self.num_layers = num_layers if num_layers is not None else cfg.num_layers
        self.layer = ParallelTransformerLayer(cfg)

    def init_master(self, key):
        keys = jax.random.split(key, self.num_layers)
        layers = [self.layer.init_master(k) for k in keys]
        return {"layers": jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *layers)}

    def shard_master(self, master, rank):
        # shard each stacked leaf layer-wise
        def shard(stacked):
            return jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[self.layer.shard_master(
                    jax.tree_util.tree_map(lambda a: a[i], stacked), rank)
                  for i in range(self.num_layers)])

        return {"layers": shard(master["layers"])}

    def apply(self, params, h, attention_mask=None, dropout_key=None,
              segment_ids=None):
        """Returns ``(h, aux)``; ``aux`` sums the layers' MoE
        load-balancing losses (0.0 for dense MLPs)."""
        def body(carry, xs):
            hidden, aux_sum = carry
            layer_params, idx = xs
            k = (None if dropout_key is None
                 else jax.random.fold_in(dropout_key, idx))
            hidden, aux = self.layer.apply(layer_params, hidden,
                                           attention_mask, dropout_key=k,
                                           segment_ids=segment_ids)
            return (hidden, aux_sum + aux), None

        if self.cfg.remat:
            # save only layer boundaries; recompute inside each layer on
            # backward (reference activation checkpointing, random.py TPU
            # mapping) — activation memory O(L·B·S·H) → O(B·S·H).  RNG
            # replay on recompute is free: keys are values (fold_in of the
            # same inputs), the property the reference's CheckpointFunction
            # restores CUDA RNG state for.  remat_policy="dots" keeps the
            # memory ceiling but skips recomputing the matmuls (the flops).
            if self.cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.dots_saveable
            elif self.cfg.remat_policy == "attn_res":
                # save the flash kernel's RESIDUALS (o, lse — named in
                # ops/attention._flash_fwd_rule): the backward then
                # consumes them directly instead of re-running the
                # attention forward inside the remat region (saving the
                # module OUTPUT alone cannot do this — the custom_vjp
                # backward needs o and lse, so remat reruns the kernel
                # to rebuild them)
                policy = jax.checkpoint_policies.save_only_these_names(
                    "flash_attn_out", "flash_attn_lse")
            elif self.cfg.remat_policy == "attn_res_mlp":
                # attn_res plus the pre-gelu h→4h output (named in
                # ParallelMLP.apply): removes the h→4h GEMM (the
                # largest single recompute GEMM, 4h² of the 12h² body)
                # and gelu from the recompute.  The qkv and proj GEMMs
                # STILL recompute — the flash custom_vjp saves only
                # (o, lse), and its backward consumes q/k/v, which must
                # be rebuilt (their 4h² stays in the recompute
                # term of any hardware-FLOP count).  Costs
                # +b·s·4h·2B per layer over attn_res (64 MB at the
                # 350M bench shape); measured LOSING to attn_res at
                # B=8/16 (r5 sweep)
                policy = jax.checkpoint_policies.save_only_these_names(
                    "flash_attn_out", "flash_attn_lse", "mlp_4h")
            elif self.cfg.remat_policy == "attn_out":
                # keep the flash-attention output per layer (named above):
                # +16 MB/layer at the 350M shape.  This only removes
                # recompute of ops DOWNSTREAM of the saved output — the
                # flash custom_vjp backward still needs its (o, lse)
                # residuals, so remat re-runs the kernel to rebuild them
                # (only attn_res skips the kernel re-run; a
                # hardware-FLOP count includes it here).
                # Measured ~7% off the step at B=8 (r4 sweep)
                policy = jax.checkpoint_policies.save_only_these_names(
                    "attn_out")
            elif self.cfg.remat_policy == "full":
                policy = None
            else:
                # a misspelled policy must not silently degrade to full
                # recompute (review finding)
                raise ValueError(
                    f"unknown remat_policy {self.cfg.remat_policy!r}; "
                    "expected full|dots|attn_res|attn_res_mlp|attn_out")
            body = jax.checkpoint(body, policy=policy)
        (h, aux), _ = jax.lax.scan(
            body, (h, jnp.zeros((), jnp.float32)),
            (params["layers"], jnp.arange(self.num_layers)))
        return h, aux


class GPTModel:
    """Reference GPTModel (standalone_gpt.py:1426-1500): embeddings +
    transformer + tied LM head."""

    def __init__(self, cfg: GPTConfig, num_layers: Optional[int] = None,
                 pre_process: bool = True, post_process: bool = True):
        self.cfg = cfg
        self.pre_process = pre_process
        self.post_process = post_process
        self.embedding = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            init_method=_normal_init(cfg.init_method_std), tp_size=cfg.tp_size)
        self.transformer = ParallelTransformer(cfg, num_layers)

    def init_master(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        p = {"transformer": self.transformer.init_master(k3)}
        if self.pre_process:
            p["embedding"] = self.embedding.init_master(k1)
            p["position_embeddings"] = {
                "weight": jax.random.normal(
                    k2, (self.cfg.max_position_embeddings,
                         self.cfg.hidden_size)) * self.cfg.init_method_std}
        if self.post_process:
            h = self.cfg.hidden_size
            p["final_layernorm"] = {"weight": jnp.ones((h,)),
                                    "bias": jnp.zeros((h,))}
            if not self.pre_process:
                # untied stage: own copy of the word embedding for the head
                p["embedding"] = self.embedding.init_master(k1)
        return p

    def shard_master(self, master, rank):
        p = {"transformer": self.transformer.shard_master(
            master["transformer"], rank)}
        if "embedding" in master:
            p["embedding"] = self.embedding.shard_master(master["embedding"], rank)
        if "position_embeddings" in master:
            p["position_embeddings"] = master["position_embeddings"]
        if "final_layernorm" in master:
            p["final_layernorm"] = master["final_layernorm"]
        return p

    def embed(self, params, tokens):
        h = self.embedding.apply(params["embedding"], tokens)
        pos = params["position_embeddings"]["weight"][:tokens.shape[1]]
        return (h + pos[None]).astype(self.cfg.compute_dtype)

    def _final_norm(self, params, h):
        return layer_norm(h, params["final_layernorm"]["weight"],
                          params["final_layernorm"]["bias"],
                          eps=self.cfg.layernorm_epsilon)

    def head_logits_local(self, params, h):
        """Sharded logits [b, s, vocab/tp] through the tied embedding
        (reference post_language_model_processing / parallel_lm_logits)."""
        h = self._final_norm(params, h)
        # cast the tied fp32 master weight to the compute dtype (O2
        # semantics, and what the fused tp=1 head does): a mixed
        # bf16xfp32 dot would silently promote to an fp32 matmul
        w = params["embedding"]["weight"].astype(
            self.cfg.compute_dtype)  # [vocab/tp, hidden]
        return jax.lax.dot_general(
            h, w, (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def apply(self, params, tokens, labels=None, attention_mask=None,
              dropout_key=None):
        """Full forward.  With ``labels`` returns per-token losses
        (reference GPTModel.forward returning CE loss); otherwise sharded
        logits.  ``dropout_key`` switches the config's
        attention/hidden-dropout rates on (training mode); the key must be
        TP-replicated — per-rank streams are derived inside (reference RNG
        tracker discipline, random.py:193-221)."""
        h = self.embed(params, tokens)
        h = embedding_dropout(h, self.cfg, dropout_key)
        h, aux = self.transformer.apply(params["transformer"], h,
                                        attention_mask,
                                        dropout_key=dropout_key)
        if labels is None:
            return self.head_logits_local(params, h)
        if self.cfg.tp_size == 1 and self.cfg.compute_dtype != jnp.float32:
            # single-shard half-precision head: fuse projection + CE so
            # only bf16 logits + fp32 lse round-trip HBM
            # (ops/fused_linear_xent.py).  fp32 configs keep the full-
            # precision unfused head (the fused op narrows operands);
            # TP-sharded heads keep the collective vocab-parallel CE.
            from apex_tpu.ops import fused_linear_cross_entropy

            hn = self._final_norm(params, h)
            b, s, hid = hn.shape
            losses = fused_linear_cross_entropy(
                hn.reshape(b * s, hid), params["embedding"]["weight"],
                labels.reshape(b * s)).reshape(b, s)
        else:
            logits_local = self.head_logits_local(params, h)
            losses = vocab_parallel_cross_entropy(logits_local, labels)
        if self.cfg.num_experts > 0:
            # fold the MoE load-balancing term in per-token so that
            # mean(losses) == CE_mean + coeff * aux (the Megatron
            # convention of adding aux to the scalar loss)
            losses = losses + (self.cfg.moe_aux_loss_coeff * aux
                               ).astype(losses.dtype)
        return losses

    __call__ = apply


def gpt_model_provider(cfg: GPTConfig, pre_process: bool = True,
                       post_process: bool = True) -> GPTModel:
    """Reference gpt_model_provider (standalone_gpt.py:1502)."""
    return GPTModel(cfg, pre_process=pre_process, post_process=post_process)


# --- pipeline adaptation ----------------------------------------------------


def make_gpt_stage_fns(cfg: GPTConfig, n_stages: int
                       ) -> Tuple[Any, Any]:
    """Split a GPT into ``n_stages`` pipeline stages for the compiled
    schedules (reference build_model pre/post_process flags per stage,
    schedules/common.py:18-106).

    Every stage holds the same param structure — embedding, L/p layers, and
    head — but only the first uses the embedding and only the last the head
    (where-masked).  Returns ``(stage_fn, loss_fn)`` for
    ``forward_backward_pipelining_without_interleaving``; microbatches are
    dicts with "tokens" and "labels".
    """
    if cfg.num_layers % n_stages != 0:
        raise ValueError("num_layers must divide evenly into stages")
    if getattr(cfg, "num_experts", 0):
        import warnings

        warnings.warn(
            "MoE under pipeline parallelism drops the load-balancing aux "
            "loss (stage outputs are a single hidden tensor) — routing "
            "can silently collapse. Use MoE with TP/DP, or thread a "
            "custom stage contract that carries the aux loss.",
            stacklevel=2)
    model = GPTModel(cfg, num_layers=cfg.num_layers // n_stages)

    def stage_fn(params, h_in, mb):
        s = parallel_state.get_pipeline_model_parallel_rank()
        embedded = model.embed(params, mb["tokens"])
        h = jnp.where(s == 0, embedded, h_in.astype(embedded.dtype))
        # MoE aux is dropped under pipelining (stage outputs are a single
        # hidden tensor); use MoE with TP/DP, not PP, or thread a custom
        # stage contract
        h, _aux = model.transformer.apply(params["transformer"], h)
        return h

    def loss_fn(params, h_out, mb):
        logits_local = model.head_logits_local(params, h_out)
        return jnp.mean(vocab_parallel_cross_entropy(logits_local, mb["labels"]))

    return stage_fn, loss_fn

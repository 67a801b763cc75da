"""Work and least bytes of an ``lfm2_moe`` decoder (LFM2-MoE) as one chip
holds it, from shapes alone (``flops.py`` counts a GPT,
``flops_afmoe.py`` Trinity, ``flops_deepseek_v2.py`` DeepSeek-V2,
``flops_granite_hybrid.py`` Granite 4.0-H).  As there: nothing here
looks at the program, recomputation, padding and idle rows are not
work, a multiply-add counts as two.

A token:

* the GEMMs at 2 FLOP a parameter: a convolution layer's ``w_in`` and
  ``w_out``, an attention layer's four projections, the dense layers'
  MLPs, an expert layer's router and the ``top_k`` experts the token
  chose (all of them held here: every pair is computed on this chip),
  the head (the embedding, tied);
* a convolution layer's mixer beside them: ``B * x`` and ``C * c`` (one
  multiply a channel each) and the taps (``2 K`` a channel);
* an attention layer's (query, key) pairs at ``4 * heads * head_dim``.

Least bytes of a decode step: every weight the step must touch, once
(the embedding once as the head; of an expert layer's experts only
those some row chose: the count comes from the program's
``moe_experts_touched``, the experts that took any pair, summed over
the layers); for every REAL row, a convolution layer, its tail in and
out; an attention layer, K and V of every cached token once (64 numbers
a head, as published: the pool's padding to 128 is the program's).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from flops_afmoe import attention_pairs, model_of  # noqa: F401


@dataclasses.dataclass(frozen=True)
class Lfm2MoeShape:
    layer_types: Tuple[str, ...]
    dense_layers: int
    hidden: int
    heads: int
    kv_heads: int
    ffn: int
    expert_ffn: int
    experts: int
    held: int
    top_k: int
    taps: int
    vocab: int

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def conv_layers(self) -> int:
        return sum(t == "conv" for t in self.layer_types)

    @property
    def attention_layers(self) -> int:
        return self.layers - self.conv_layers

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers


def model_shape(model: dict) -> Lfm2MoeShape:
    """``model_of`` a configuration file -> :class:`Lfm2MoeShape`."""
    lo, hi = model.get("experts_held", (0, model["num_experts"]))
    return Lfm2MoeShape(
        layer_types=tuple(model["layer_types"]),
        dense_layers=int(model["num_dense_layers"]),
        hidden=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        ffn=int(model["intermediate_size"]),
        expert_ffn=int(model["moe_intermediate_size"]),
        experts=int(model["num_experts"]), held=int(hi) - int(lo),
        top_k=int(model["num_experts_per_tok"]),
        taps=int(model["conv_L_cache"]), vocab=int(model["vocab_size"]))


# -- parameters ---------------------------------------------------------------

def conv_mixer_params(m: Lfm2MoeShape) -> int:
    """w_in ``[d, 3d]``, the taps ``[K, d]``, w_out ``[d, d]``."""
    return 3 * m.hidden * m.hidden + m.taps * m.hidden + m.hidden * m.hidden


def attention_mixer_params(m: Lfm2MoeShape) -> int:
    """wq, wo ``[d, H dh]`` and wk, wv ``[d, Hk dh]`` (the heads' norm
    gains left out: a few hundred)."""
    kv = m.kv_heads * m.head_dim
    return 2 * m.hidden * m.heads * m.head_dim + 2 * m.hidden * kv


def mlp_params(m: Lfm2MoeShape, width: int) -> int:
    return 3 * m.hidden * width


def expert_params(m: Lfm2MoeShape) -> int:
    return mlp_params(m, m.expert_ffn)


def router_params(m: Lfm2MoeShape) -> int:
    return m.hidden * m.experts


def mixer_params(m: Lfm2MoeShape) -> int:
    """The mixers of every layer."""
    return (m.conv_layers * conv_mixer_params(m)
            + m.attention_layers * attention_mixer_params(m))


def total_params(m: Lfm2MoeShape) -> int:
    """What the chip holds (norm gains and the selection bias left
    out): mixers, the dense MLPs, the held experts and routers, the
    embedding (the head is the same matrix)."""
    return (mixer_params(m) + m.dense_layers * mlp_params(m, m.ffn)
            + m.expert_layers * (m.held * expert_params(m)
                                 + router_params(m))
            + m.vocab * m.hidden)


# -- FLOPs --------------------------------------------------------------------

def conv_flops_per_token(m: Lfm2MoeShape) -> int:
    """A convolution layer beside its GEMMs: the two gates and the
    taps."""
    return 2 * m.hidden + 2 * m.taps * m.hidden


def routed_pairs_per_token(m: Lfm2MoeShape) -> float:
    """(token, expert) pairs a token sends to the experts held here, in
    expectation (``top_k`` where all are held)."""
    return m.top_k * m.held / m.experts


def layer_flops_per_token(m: Lfm2MoeShape) -> float:
    """Forward FLOPs of one token through every layer, attention's
    pairs left out."""
    expert_layer = (2 * router_params(m)
                    + 2 * expert_params(m) * routed_pairs_per_token(m))
    return (2 * mixer_params(m) + m.conv_layers * conv_flops_per_token(m)
            + 2 * m.dense_layers * mlp_params(m, m.ffn)
            + m.expert_layers * expert_layer)


def head_flops_per_token(m: Lfm2MoeShape) -> int:
    return 2 * m.hidden * m.vocab


def attention_flops(m: Lfm2MoeShape, q_len: int, kv_len: int) -> int:
    return (m.attention_layers * 4 * m.heads * m.head_dim
            * attention_pairs(q_len, kv_len))


def prefill_flops(m: Lfm2MoeShape, prompt_len: int) -> float:
    """Forward FLOPs to prefill ``prompt_len`` real tokens, whole or in
    chunks, and produce one next-token distribution."""
    return (prompt_len * layer_flops_per_token(m)
            + attention_flops(m, prompt_len, prompt_len)
            + head_flops_per_token(m))


def decode_flops(m: Lfm2MoeShape, kv_len: int) -> float:
    """Forward FLOPs of one decoded token whose context, itself
    included, is ``kv_len`` tokens."""
    return (layer_flops_per_token(m) + head_flops_per_token(m)
            + attention_flops(m, 1, kv_len))


# -- least bytes --------------------------------------------------------------

def tail_bytes(m: Lfm2MoeShape, itemsize: int = 2) -> int:
    """One convolution layer's tail of one request."""
    return (m.taps - 1) * m.hidden * itemsize


def slot_bytes(m: Lfm2MoeShape, itemsize: int = 2) -> int:
    return m.conv_layers * tail_bytes(m, itemsize)


def kv_token_bytes(m: Lfm2MoeShape, itemsize: int = 2) -> int:
    """K and V of one cached token over the whole model."""
    return m.attention_layers * 2 * m.kv_heads * m.head_dim * itemsize


def decode_weight_bytes(m: Lfm2MoeShape, experts_touched: float,
                        itemsize: int = 2) -> float:
    """Weights one decode step must read: all of them (the embedding
    once, as the head) but the held experts no row chose;
    ``experts_touched`` counts those some row chose, summed over the
    expert layers."""
    untouched = m.expert_layers * m.held - experts_touched
    return (total_params(m) - untouched * expert_params(m)) * itemsize


def decode_steps_bytes(m: Lfm2MoeShape, steps: int, experts_touched: float,
                       rows: int, cached_tokens: int,
                       itemsize: int = 2) -> float:
    """Least bytes of ``steps`` decode steps that touched
    ``experts_touched`` experts each (a mean over steps), carried
    ``rows`` real rows between them and attended over ``cached_tokens``
    tokens."""
    if not steps:
        return 0.0
    return (steps * decode_weight_bytes(m, experts_touched, itemsize)
            + rows * 2 * slot_bytes(m, itemsize)
            + cached_tokens * kv_token_bytes(m, itemsize))


def decode_ffn_bytes(m: Lfm2MoeShape, experts_touched: float,
                     itemsize: int = 2) -> float:
    """What a decode step's FFNs must read (the scopes ``mlp``,
    ``moe_router``, ``moe_experts``): the touched experts, the routers
    and the dense layers' MLPs."""
    return (experts_touched * expert_params(m)
            + m.expert_layers * router_params(m)
            + m.dense_layers * mlp_params(m, m.ffn)) * itemsize


def experts_touched(m: Lfm2MoeShape, rows: float) -> float:
    """Held experts that at least one of ``rows`` rows chose, summed
    over the expert layers, in expectation under uniform routing (the
    prediction; the metrics read the program's own count)."""
    return (m.expert_layers * m.held
            * (1.0 - (1.0 - m.top_k / m.experts) ** rows))

"""Work and least bytes of a ``granitemoehybrid`` decoder without
experts (Granite 4.0-H) served whole on one chip, from shapes alone
(``flops.py`` counts a GPT, ``flops_afmoe.py`` Trinity,
``flops_deepseek_v2.py`` DeepSeek-V2).  As there: nothing here looks at
the program, recomputation, padding and idle rows are not work, a
multiply-add counts as two.

A token, in either form of the recurrence (token by token or chunked:
the chunked form's quadratic part inside a block is the program's way,
not the algorithm's least):

* the GEMMs at 2 FLOP a parameter: a state-space layer's ``win`` and
  ``wout``, an attention layer's four projections, every layer's MLP,
  the head (the embedding, tied);
* a state-space layer's recurrence at the equations' own count: per
  element of the state ``[H, P, N]`` the decay (1), the outer product's
  two multiplies and the add (3), and ``S C`` (2): ``6 H P N``; plus the
  convolution ``2 K`` a channel;
* an attention layer's (query, key) pairs at ``4 * heads * head_dim``.

Least bytes of a decode step: every weight once (the embedding as the
head), and for every REAL row, a state-space layer, its state in and
out (float32) and its tail in and out; an attention layer, K and V of
every cached token once.  Of one ``ssm_decode_update`` call: the rows'
states in and out, and what the kernel takes and gives beside them
(decay, ``delta x``, ``y``, B and C, float32 as the kernel has them).
The tail is the convolution's, outside that call.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from flops_afmoe import attention_pairs, model_of  # noqa: F401


@dataclasses.dataclass(frozen=True)
class GraniteHybridShape:
    layer_types: Tuple[str, ...]
    hidden: int
    heads: int
    kv_heads: int
    ffn: int
    ssm_heads: int
    ssm_head_dim: int
    state: int
    taps: int
    vocab: int

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def ssm_layers(self) -> int:
        return sum(t == "mamba" for t in self.layer_types)

    @property
    def attention_layers(self) -> int:
        return len(self.layer_types) - self.ssm_layers

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.state


def model_shape(model: dict) -> GraniteHybridShape:
    """``model_of`` a configuration file -> :class:`GraniteHybridShape`."""
    return GraniteHybridShape(
        layer_types=tuple(model["layer_types"]),
        hidden=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        ffn=int(model["shared_intermediate_size"]),
        ssm_heads=int(model["mamba_n_heads"]),
        ssm_head_dim=int(model["mamba_d_head"]),
        state=int(model["mamba_d_state"]), taps=int(model["mamba_d_conv"]),
        vocab=int(model["vocab_size"]))


# -- parameters ---------------------------------------------------------------

def ssm_mixer_params(m: GraniteHybridShape) -> int:
    """win and wout (the GEMMs)."""
    return (m.hidden * (m.d_inner + m.conv_dim + m.ssm_heads)
            + m.d_inner * m.hidden)


def ssm_small_params(m: GraniteHybridShape) -> int:
    """The convolution, the gated norm's gain and the three scalars a
    head."""
    return (m.taps + 1) * m.conv_dim + m.d_inner + 3 * m.ssm_heads


def attention_mixer_params(m: GraniteHybridShape) -> int:
    kv = m.kv_heads * m.head_dim
    return 2 * m.hidden * m.hidden + 2 * m.hidden * kv


def mlp_params(m: GraniteHybridShape) -> int:
    return 3 * m.hidden * m.ffn


def layer_params(m: GraniteHybridShape) -> int:
    """Every GEMM weight of the layers (norm gains left out)."""
    return (m.ssm_layers * ssm_mixer_params(m)
            + m.attention_layers * attention_mixer_params(m)
            + len(m.layer_types) * mlp_params(m))


def total_params(m: GraniteHybridShape) -> int:
    """What the chip holds: the layers with their small leaves and two
    norm gains each, the embedding (the head is the same matrix), the
    final norm."""
    return (layer_params(m) + m.ssm_layers * ssm_small_params(m)
            + 2 * len(m.layer_types) * m.hidden
            + m.vocab * m.hidden + m.hidden)


# -- what a request holds -----------------------------------------------------

def state_bytes(m: GraniteHybridShape, state_itemsize: int = 4) -> int:
    """One layer's recurrent state of one request."""
    return m.d_inner * m.state * state_itemsize


def tail_bytes(m: GraniteHybridShape, itemsize: int = 2) -> int:
    return (m.taps - 1) * m.conv_dim * itemsize


def slot_bytes(m: GraniteHybridShape, itemsize: int = 2) -> int:
    return m.ssm_layers * (state_bytes(m) + tail_bytes(m, itemsize))


def kv_token_bytes(m: GraniteHybridShape, itemsize: int = 2) -> int:
    """K and V of one cached token over the whole model."""
    return m.attention_layers * 2 * m.kv_heads * m.head_dim * itemsize


# -- FLOPs --------------------------------------------------------------------

def recurrence_flops_per_token(m: GraniteHybridShape) -> int:
    """One state-space layer: decay, outer product, add and ``S C`` on
    every element of the state, ``D x`` and the gate on every channel,
    the convolution's taps."""
    return (6 * m.d_inner * m.state + 4 * m.d_inner
            + 2 * m.taps * m.conv_dim)


def layer_flops_per_token(m: GraniteHybridShape) -> int:
    """Forward FLOPs of one token through every layer, attention's
    pairs left out."""
    return (2 * layer_params(m)
            + m.ssm_layers * recurrence_flops_per_token(m))


def head_flops_per_token(m: GraniteHybridShape) -> int:
    return 2 * m.hidden * m.vocab


def attention_flops(m: GraniteHybridShape, q_len: int, kv_len: int) -> int:
    return (m.attention_layers * 4 * m.heads * m.head_dim
            * attention_pairs(q_len, kv_len))


def prefill_flops(m: GraniteHybridShape, prompt_len: int) -> float:
    """Forward FLOPs to prefill ``prompt_len`` real tokens, whole or in
    chunks, and produce one next-token distribution."""
    return (prompt_len * layer_flops_per_token(m)
            + attention_flops(m, prompt_len, prompt_len)
            + head_flops_per_token(m))


def decode_flops(m: GraniteHybridShape, kv_len: int) -> float:
    """Forward FLOPs of one decoded token whose context, itself
    included, is ``kv_len`` tokens."""
    return (layer_flops_per_token(m) + head_flops_per_token(m)
            + attention_flops(m, 1, kv_len))


# -- least bytes --------------------------------------------------------------

def decode_weight_bytes(m: GraniteHybridShape, itemsize: int = 2) -> int:
    """Weights one decode step must read: all of them, the embedding
    once as the head (the rows looked up are among them)."""
    return total_params(m) * itemsize


def decode_row_state_bytes(m: GraniteHybridShape, itemsize: int = 2) -> int:
    """State and tail of one real row, in and out, over the model."""
    return m.ssm_layers * 2 * (state_bytes(m) + tail_bytes(m, itemsize))


def decode_steps_bytes(m: GraniteHybridShape, steps: int, rows: int,
                       cached_tokens: int, itemsize: int = 2) -> float:
    """Least bytes of ``steps`` decode steps that carried ``rows`` real
    rows between them and attended over ``cached_tokens`` tokens."""
    if not steps:
        return 0.0
    return (steps * decode_weight_bytes(m, itemsize)
            + rows * decode_row_state_bytes(m, itemsize)
            + cached_tokens * kv_token_bytes(m, itemsize))


def ssm_update_call_bytes(m: GraniteHybridShape, rows: float) -> float:
    """Least bytes of one ``ssm_decode_update`` kernel call over
    ``rows`` real rows: each row's state in and out, and the float32
    vectors the kernel takes (decay, ``delta x``, B, C) and gives
    (``y``)."""
    return rows * (2 * state_bytes(m)
                   + 4 * (3 * m.d_inner + 2 * m.state))


def ssm_update_call_flops(m: GraniteHybridShape, rows: float) -> float:
    """What the kernel computes of the recurrence: decay, outer
    product, add, ``S C``."""
    return rows * 6 * m.d_inner * m.state

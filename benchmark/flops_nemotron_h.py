"""Work and least bytes of a ``nemotron_h`` decoder with a latent expert
layer (Nemotron 3 Super) as one chip holds it, from shapes alone
(``flops_granite_hybrid.py`` counts Granite 4.0-H, ``flops_lfm2_moe.py``
LFM2-MoE).  As there: nothing here looks at the program, recomputation,
padding and idle rows are not work, a multiply-add counts as two.

A token:

* the GEMMs at 2 FLOP a parameter: a Mamba layer's ``win`` and ``wout``,
  the attention layer's four projections, an expert layer's router, its
  latent pair (``latent_down``, ``latent_up``), its shared expert and
  the routed pairs that land on the experts held here (``top_k * held /
  E`` in expectation), the head;
* a Mamba layer's recurrence at the equations' own count: per element
  of the state ``[H, P, N]`` the decay, the outer product's two
  multiplies and the add, and ``S C`` (6), ``D x`` and the gate on every
  channel (4), the convolution's taps (``2 K`` a channel);
* the attention layer's (query, key) pairs at ``4 * heads * head_dim``.

Least bytes of a decode step: every weight the step must touch, once:
all of them but the held experts no row chose (the count comes from the
program's ``moe_experts_touched``, the experts that took any pair,
summed over the layers) and, of the embedding, the rows looked up (the
head is its own matrix, read whole); for every REAL row, a Mamba layer,
its float32 state and its tail in and out; the attention layer, K and V
of every cached token once.  Of one ``ssm_decode_update`` call: the
rows' states in and out, and the float32 vectors the kernel takes
(decay, ``delta x``, every group's B and C) and gives (``y``).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from flops_afmoe import attention_pairs, model_of  # noqa: F401

MIXERS = {"M": "mamba", "E": "moe", "*": "attention"}


@dataclasses.dataclass(frozen=True)
class NemotronHShape:
    layer_types: Tuple[str, ...]
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    groups: int
    state: int
    taps: int
    latent: int
    expert_ffn: int
    shared_ffn: int
    experts: int
    held: int
    top_k: int
    vocab: int

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state

    @property
    def ssm_layers(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def expert_layers(self) -> int:
        return self.layer_types.count("moe")

    @property
    def attention_layers(self) -> int:
        return self.layer_types.count("attention")


def model_shape(model: dict) -> NemotronHShape:
    """``model_of`` a configuration file -> :class:`NemotronHShape`
    (``router_width`` the router's outputs where ``n_routed_experts``
    counts those held)."""
    experts = int(model.get("router_width", model["n_routed_experts"]))
    lo, hi = model.get("experts_held", (0, experts))
    return NemotronHShape(
        layer_types=tuple(MIXERS[c] for c in model["hybrid_override_pattern"]),
        hidden=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        ssm_heads=int(model["mamba_num_heads"]),
        ssm_head_dim=int(model["mamba_head_dim"]),
        groups=int(model["n_groups"]), state=int(model["ssm_state_size"]),
        taps=int(model["conv_kernel"]), latent=int(model["moe_latent_size"]),
        expert_ffn=int(model["moe_intermediate_size"]),
        shared_ffn=int(model["moe_shared_expert_intermediate_size"]),
        experts=experts, held=int(hi) - int(lo),
        top_k=int(model["num_experts_per_tok"]),
        vocab=int(model["vocab_size"]))


# -- parameters ---------------------------------------------------------------

def ssm_mixer_params(m: NemotronHShape) -> int:
    """win and wout (the GEMMs)."""
    return (m.hidden * (m.d_inner + m.conv_dim + m.ssm_heads)
            + m.d_inner * m.hidden)


def ssm_small_params(m: NemotronHShape) -> int:
    """The convolution's taps and bias, the gated norm's gain and the
    three scalars a head."""
    return (m.taps + 1) * m.conv_dim + m.d_inner + 3 * m.ssm_heads


def attention_mixer_params(m: NemotronHShape) -> int:
    return (2 * m.hidden * m.heads * m.head_dim
            + 2 * m.hidden * m.kv_heads * m.head_dim)


def router_params(m: NemotronHShape) -> int:
    return m.hidden * m.experts


def latent_params(m: NemotronHShape) -> int:
    """latent_down and latent_up."""
    return 2 * m.hidden * m.latent


def shared_params(m: NemotronHShape) -> int:
    return 2 * m.hidden * m.shared_ffn


def expert_params(m: NemotronHShape) -> int:
    """One routed expert: up and down, in the latent."""
    return 2 * m.latent * m.expert_ffn


def expert_layer_fixed_params(m: NemotronHShape) -> int:
    """An expert layer without its routed experts."""
    return router_params(m) + latent_params(m) + shared_params(m)


def total_params(m: NemotronHShape) -> int:
    """What the chip holds: the mixers with their small leaves, the held
    experts, a norm gain a layer, the embedding, the head and the final
    norm (the selection bias left out)."""
    return (m.ssm_layers * (ssm_mixer_params(m) + ssm_small_params(m))
            + m.attention_layers * attention_mixer_params(m)
            + m.expert_layers * (expert_layer_fixed_params(m)
                                 + m.held * expert_params(m))
            + len(m.layer_types) * m.hidden
            + 2 * m.vocab * m.hidden + m.hidden)


# -- what a request holds -----------------------------------------------------

def state_bytes(m: NemotronHShape, state_itemsize: int = 4) -> int:
    """One Mamba layer's recurrent state of one request."""
    return m.d_inner * m.state * state_itemsize


def tail_bytes(m: NemotronHShape, itemsize: int = 2) -> int:
    return (m.taps - 1) * m.conv_dim * itemsize


def slot_bytes(m: NemotronHShape, itemsize: int = 2) -> int:
    return m.ssm_layers * (state_bytes(m) + tail_bytes(m, itemsize))


def kv_token_bytes(m: NemotronHShape, itemsize: int = 2) -> int:
    """K and V of one cached token over the whole model."""
    return m.attention_layers * 2 * m.kv_heads * m.head_dim * itemsize


# -- FLOPs --------------------------------------------------------------------

def recurrence_flops_per_token(m: NemotronHShape) -> int:
    """One Mamba layer beside its GEMMs."""
    return (6 * m.d_inner * m.state + 4 * m.d_inner
            + 2 * m.taps * m.conv_dim)


def routed_pairs_per_token(m: NemotronHShape) -> float:
    """(token, expert) pairs a token sends to the experts held here, in
    expectation."""
    return m.top_k * m.held / m.experts


def layer_flops_per_token(m: NemotronHShape) -> float:
    """Forward FLOPs of one token through every layer, attention's pairs
    left out."""
    expert_layer = (2 * expert_layer_fixed_params(m)
                    + 2 * expert_params(m) * routed_pairs_per_token(m))
    return (m.ssm_layers * (2 * ssm_mixer_params(m)
                            + recurrence_flops_per_token(m))
            + m.attention_layers * 2 * attention_mixer_params(m)
            + m.expert_layers * expert_layer)


def head_flops_per_token(m: NemotronHShape) -> int:
    return 2 * m.hidden * m.vocab


def attention_flops(m: NemotronHShape, q_len: int, kv_len: int) -> int:
    return (m.attention_layers * 4 * m.heads * m.head_dim
            * attention_pairs(q_len, kv_len))


def prefill_flops(m: NemotronHShape, prompt_len: int) -> float:
    """Forward FLOPs to prefill ``prompt_len`` real tokens, whole or in
    chunks, and produce one next-token distribution."""
    return (prompt_len * layer_flops_per_token(m)
            + attention_flops(m, prompt_len, prompt_len)
            + head_flops_per_token(m))


def decode_flops(m: NemotronHShape, kv_len: int) -> float:
    """Forward FLOPs of one decoded token whose context, itself
    included, is ``kv_len`` tokens."""
    return (layer_flops_per_token(m) + head_flops_per_token(m)
            + attention_flops(m, 1, kv_len))


# -- least bytes --------------------------------------------------------------

def decode_weight_bytes(m: NemotronHShape, experts_touched: float,
                        rows: float, itemsize: int = 2) -> float:
    """Weights one decode step of ``rows`` real rows must read: all of
    them but the held experts no row chose (``experts_touched`` counts
    those some row chose, summed over the expert layers) and the
    embedding's rows nobody looked up."""
    untouched = m.expert_layers * m.held - experts_touched
    return (total_params(m) - untouched * expert_params(m)
            - (m.vocab - rows) * m.hidden) * itemsize


def decode_row_state_bytes(m: NemotronHShape, itemsize: int = 2) -> int:
    """State and tail of one real row, in and out, over the model."""
    return 2 * slot_bytes(m, itemsize)


def decode_steps_bytes(m: NemotronHShape, steps: int, experts_touched: float,
                       rows: int, cached_tokens: int,
                       itemsize: int = 2) -> float:
    """Least bytes of ``steps`` decode steps that touched
    ``experts_touched`` experts each (a mean over steps), carried
    ``rows`` real rows between them and attended over ``cached_tokens``
    tokens."""
    if not steps:
        return 0.0
    return (steps * decode_weight_bytes(m, experts_touched, rows / steps,
                                        itemsize)
            + rows * decode_row_state_bytes(m, itemsize)
            + cached_tokens * kv_token_bytes(m, itemsize))


def decode_experts_bytes(m: NemotronHShape, experts_touched: float,
                         itemsize: int = 2) -> float:
    """What a decode step's expert layers must read (the scopes
    ``moe_router``, ``moe_latent_down``, ``moe_experts``,
    ``moe_latent_up``, ``moe_shared``): the touched experts, and each
    layer's router, latent pair and shared expert."""
    return (experts_touched * expert_params(m)
            + m.expert_layers * expert_layer_fixed_params(m)) * itemsize


def experts_touched(m: NemotronHShape, rows: float) -> float:
    """Held experts that at least one of ``rows`` rows chose, summed
    over the expert layers, in expectation under uniform routing (the
    prediction; the metrics read the program's own count)."""
    return (m.expert_layers * m.held
            * (1.0 - (1.0 - m.top_k / m.experts) ** rows))


def ssm_update_call_bytes(m: NemotronHShape, rows: float) -> float:
    """Least bytes of ``ssm_decode_update`` calls over ``rows`` real
    rows (a row of one layer is one row): each row's state in and out,
    and the float32 vectors the kernel takes (decay, ``delta x``, the
    groups' B and C) and gives (``y``)."""
    return rows * (2 * state_bytes(m)
                   + 4 * (3 * m.d_inner + 2 * m.groups * m.state))


def ssm_update_call_flops(m: NemotronHShape, rows: float) -> float:
    """What the kernel computes of the recurrence: decay, outer
    product, add, ``S C``."""
    return rows * 6 * m.d_inner * m.state

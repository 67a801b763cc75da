"""Work and least bytes of a ``deepseek_v2`` decoder as one chip of its
expert-parallel deployment holds it, from shapes alone (``flops.py``
counts a GPT, ``flops_afmoe.py`` Trinity).  As there: nothing here looks
at the program, recomputation and padding are not work, a multiply-add
counts as two.

What differs and is counted here:

* the low-rank projections of latent attention (``wdq``, ``wuq``,
  ``wdkv``, the up-projection of the latent as ``wuk`` and ``wuv``,
  ``wo``): a token multiplies each once in EITHER form of the attention
  (expanded, it makes its own keys and values with ``wuk`` / ``wuv``;
  absorbed, it folds ``wuk`` into its query and applies ``wuv`` to what
  comes back);
* a (query, key) pair.  Expanded (a prefill's tokens, the algorithm's
  least): ``H * (2 (dn + dr) + 2 dv)``, 81,920 at the published sizes.
  Absorbed (one query a row over the latent cache, which is what the
  algorithm does there: expanding a cached token for one query would
  cost 33.5 MFLOP): ``H * (2 (rank + dr) + 2 rank)``, 278,528;
* tokens that rode in on SHARED pages are no work: a prompt counts from
  its shared count on, attending over its whole context;
* the router over its whole width, the shared experts as one MLP, the
  ROUTED pairs at their expectation ``top_k * held / experts`` a token,
  the head over this chip's vocabulary slice.

Least bytes of a decode step: every weight the step must touch, once
(of the held experts those some row chose, in expectation, as
``flops_afmoe.py`` counts them and for its reason), plus the latent
vector (``rank + dr`` numbers: 1,152 B in bfloat16, whatever the pool
pads it to) of every DISTINCT cached token the step's rows read, once a
layer: a page that ten rows share is one page.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable

from flops_afmoe import attention_pairs, model_of  # noqa: F401


@dataclasses.dataclass(frozen=True)
class DeepseekV2Shape:
    layers: int
    dense_layers: int
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    ffn: int
    expert_ffn: int
    router_width: int
    held: int
    top_k: int
    n_shared: int
    vocab: int

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope


def model_shape(model: dict) -> DeepseekV2Shape:
    """``model_of`` a configuration file -> :class:`DeepseekV2Shape`."""
    return DeepseekV2Shape(
        layers=int(model["num_hidden_layers"]),
        dense_layers=int(model["first_k_dense_replace"]),
        hidden=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        q_rank=int(model["q_lora_rank"]), kv_rank=int(model["kv_lora_rank"]),
        nope=int(model["qk_nope_head_dim"]),
        rope=int(model["qk_rope_head_dim"]), v_dim=int(model["v_head_dim"]),
        ffn=int(model["intermediate_size"]),
        expert_ffn=int(model["moe_intermediate_size"]),
        router_width=int(model["router_width"]),
        held=int(model["n_routed_experts"]),
        top_k=int(model["num_experts_per_tok"]),
        n_shared=int(model["n_shared_experts"]),
        vocab=int(model["vocab_size"]))


# -- parameters ---------------------------------------------------------------

def attention_params(m: DeepseekV2Shape) -> int:
    """wdq, wuq, wdkv, wuk, wuv, wo."""
    return (m.hidden * m.q_rank + m.q_rank * m.heads * (m.nope + m.rope)
            + m.hidden * m.latent_dim
            + m.kv_rank * m.heads * (m.nope + m.v_dim)
            + m.heads * m.v_dim * m.hidden)


def mlp_params(m: DeepseekV2Shape, width: int) -> int:
    return 3 * m.hidden * width


def expert_layer_params(m: DeepseekV2Shape, experts: float) -> float:
    """Router, the shared experts (one MLP) and ``experts`` routed ones."""
    return (m.hidden * m.router_width
            + mlp_params(m, m.expert_ffn * m.n_shared)
            + experts * mlp_params(m, m.expert_ffn))


def held_params(m: DeepseekV2Shape) -> int:
    """Every GEMM weight this chip holds (norm gains left out)."""
    return int(m.layers * attention_params(m)
               + m.dense_layers * mlp_params(m, m.ffn)
               + m.expert_layers * expert_layer_params(m, m.held)
               + 2 * m.vocab * m.hidden)


# -- FLOPs --------------------------------------------------------------------

def routed_pairs_per_token(m: DeepseekV2Shape) -> float:
    return m.top_k * m.held / m.router_width


def layer_matmul_flops_per_token(m: DeepseekV2Shape) -> float:
    """Forward FLOPs of one token through the GEMMs of every layer, in
    either form of the attention."""
    return 2 * (m.layers * attention_params(m)
                + m.dense_layers * mlp_params(m, m.ffn)
                + m.expert_layers * expert_layer_params(
                    m, routed_pairs_per_token(m)))


def head_flops_per_token(m: DeepseekV2Shape) -> int:
    return 2 * m.hidden * m.vocab


def expanded_pair_flops(m: DeepseekV2Shape) -> int:
    """One (query, key) pair, every head, per-head K and V."""
    return m.heads * (2 * (m.nope + m.rope) + 2 * m.v_dim)


def absorbed_pair_flops(m: DeepseekV2Shape) -> int:
    """One (query, cached token) pair, every head, over the latent."""
    return m.heads * (2 * m.latent_dim + 2 * m.kv_rank)


def prefill_flops(m: DeepseekV2Shape, context: int, shared: int = 0) -> float:
    """Forward FLOPs to prefill the ``context - shared`` tokens of a
    context that were computed (the first ``shared`` rode in on shared
    pages), attending over the whole context, and produce one
    next-token distribution."""
    new = context - shared
    return (new * layer_matmul_flops_per_token(m)
            + m.layers * expanded_pair_flops(m)
            * attention_pairs(new, context)
            + head_flops_per_token(m))


def decode_attention_flops(m: DeepseekV2Shape, kv_len: int) -> int:
    return m.layers * absorbed_pair_flops(m) * kv_len


def decode_flops(m: DeepseekV2Shape, kv_len: int) -> float:
    """Forward FLOPs of one decoded token whose context, itself
    included, is ``kv_len`` tokens."""
    return (layer_matmul_flops_per_token(m) + head_flops_per_token(m)
            + decode_attention_flops(m, kv_len))


# -- least bytes --------------------------------------------------------------

def latent_token_bytes(m: DeepseekV2Shape, itemsize: int) -> int:
    """What the cache holds of one token in one layer."""
    return m.latent_dim * itemsize


def latent_attention_bytes(m: DeepseekV2Shape, distinct_tokens: int,
                           itemsize: int) -> int:
    """Least bytes the decode attention reads: every distinct cached
    token's vector, once a layer."""
    return m.layers * latent_token_bytes(m, itemsize) * distinct_tokens


def experts_touched(m: DeepseekV2Shape, rows: float) -> float:
    """Held experts that at least one of ``rows`` rows chose, in
    expectation under uniform routing."""
    return m.held * (1.0 - (1.0 - m.top_k / m.router_width) ** rows)


def decode_weight_bytes(m: DeepseekV2Shape, rows: float,
                        itemsize: int) -> float:
    """Weights one decode step of ``rows`` rows must read: all but the
    embedding (``rows`` rows of it) and the held experts nobody chose."""
    params = (m.layers * attention_params(m)
              + m.dense_layers * mlp_params(m, m.ffn)
              + m.expert_layers * expert_layer_params(
                  m, experts_touched(m, rows))
              + m.vocab * m.hidden + rows * m.hidden)
    return params * itemsize


def distinct_tokens(kv_lens: Iterable[int], shared_rows: Iterable[int],
                    shared_len: int) -> int:
    """Cached tokens a set of decode steps reads, a shared page counted
    once a step: the rows' contexts ``kv_lens`` less, for every step
    and document, ``shared_len`` for each row after the first that
    reads it (``shared_rows``: per step and document, how many rows
    shared it)."""
    return sum(kv_lens) - shared_len * sum(n - 1 for n in shared_rows)


def decode_steps_bytes(m: DeepseekV2Shape, steps: int, rows: int,
                       distinct: int, itemsize: int) -> float:
    """Least bytes of ``steps`` decode steps that carried ``rows`` rows
    between them (the steps taken as equally full) and read ``distinct``
    cached tokens."""
    if not steps:
        return 0.0
    return (steps * decode_weight_bytes(m, rows / steps, itemsize)
            + latent_attention_bytes(m, distinct, itemsize))

"""Work and least bytes of an ``afmoe`` decoder as one chip of an
expert-parallel deployment holds it, from shapes alone (``flops.py``
counts a GPT).  As there: nothing here looks at the program,
recomputation and padding are not work, a multiply-add counts as two.

What differs from a GPT and is counted here: grouped-query projections
(``H`` query heads, ``Hkv`` K/V heads) and the output gate's
projection; (query, key) pairs under a sliding window (``min`` of the
causal count and ``W``); the router, over its whole width; the shared
expert; the ROUTED pairs at their expectation, ``top_k * held /
experts`` a token (the router is seeded noise: no expert is favoured);
the head over this chip's vocabulary slice.

Least bytes of a decode step: every weight the step must touch, once,
plus each row's K and V under its layers' windows.  Of the held experts
a step touches only those some row chose: with ``r`` rows and uniform
routing an expert is chosen by none with probability ``(1 - top_k /
experts) ** r``, so at 64 rows and top-4 of 256 a step touches 63% of
its 32 held experts in expectation, not all of them — the count below
uses that expectation (ISSUE 29 asked for every held weight once; that
would count bytes no algorithm needs and let the share read high).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple


@dataclasses.dataclass(frozen=True)
class AfmoeShape:
    layer_types: Tuple[str, ...]
    dense_layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    expert_ffn: int
    router_width: int
    held: int
    top_k: int
    window: int
    vocab: int

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def window_layers(self) -> int:
        return sum(t == "sliding_attention" for t in self.layer_types)

    @property
    def full_layers(self) -> int:
        return self.layers - self.window_layers

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers


def model_of(config: dict) -> dict:
    """The model as this file and the reference read it: the
    configuration file's published keys (its top level) and its
    ``model`` group (what the published config lacks: dtype, the
    router's width, the experts held)."""
    return {**{k: v for k, v in config.items() if k != "model"},
            **config["model"]}


def model_shape(model: dict) -> AfmoeShape:
    """:func:`model_of` a configuration file -> :class:`AfmoeShape`."""
    return AfmoeShape(
        layer_types=tuple(model["layer_types"]),
        dense_layers=int(model["num_dense_layers"]),
        hidden=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        ffn=int(model["intermediate_size"]),
        expert_ffn=int(model["moe_intermediate_size"]),
        router_width=int(model["router_width"]),
        held=int(model["num_experts"]),
        top_k=int(model["num_experts_per_tok"]),
        window=int(model["sliding_window"]), vocab=int(model["vocab_size"]))


# -- parameters ---------------------------------------------------------------

def attention_params(m: AfmoeShape) -> int:
    """wq, wgate, wo ``[d, H dh]`` and wk, wv ``[d, Hkv dh]``."""
    return m.hidden * m.head_dim * (3 * m.heads + 2 * m.kv_heads)


def mlp_params(m: AfmoeShape, width: int) -> int:
    """One SwiGLU MLP: gate, up, down."""
    return 3 * m.hidden * width


def held_params(m: AfmoeShape) -> int:
    """Every GEMM weight this chip holds (norm gains and the router's
    bias left out: a few thousand)."""
    per_expert_layer = (mlp_params(m, m.expert_ffn) * (m.held + 1)
                        + m.hidden * m.router_width)
    return (m.layers * attention_params(m)
            + m.dense_layers * mlp_params(m, m.ffn)
            + m.expert_layers * per_expert_layer
            + 2 * m.vocab * m.hidden)


# -- FLOPs --------------------------------------------------------------------

def routed_pairs_per_token(m: AfmoeShape) -> float:
    """(token, expert) pairs a token sends to the experts held here, in
    expectation."""
    return m.top_k * m.held / m.router_width


def layer_matmul_flops_per_token(m: AfmoeShape) -> float:
    """Forward FLOPs of one token through the GEMMs of every layer: the
    projections and the gate, the dense MLPs, and in the expert layers
    the router, the shared expert and the routed pairs."""
    expert_layer = (2 * m.hidden * m.router_width
                    + 2 * mlp_params(m, m.expert_ffn)
                    * (1 + routed_pairs_per_token(m)))
    return (2 * m.layers * attention_params(m)
            + 2 * m.dense_layers * mlp_params(m, m.ffn)
            + m.expert_layers * expert_layer)


def head_flops_per_token(m: AfmoeShape) -> int:
    return 2 * m.hidden * m.vocab


def keys_seen(position: int, window=None) -> int:
    """Keys the query at 0-based ``position`` scores: itself and those
    before it, at most ``window`` of them."""
    seen = position + 1
    return seen if window is None else min(seen, window)


def attention_pairs(q_len: int, kv_len: int, window=None) -> int:
    """(query, key) pairs one head scores when the ``q_len`` queries are
    the last rows of a ``kv_len``-token context."""
    first = kv_len - q_len
    if window is None:
        return q_len * first + q_len * (q_len + 1) // 2
    # queries whose causal count is still under the window, then the rest
    rising = max(0, min(q_len, window - first))
    pairs = rising * first + rising * (rising + 1) // 2
    return pairs + (q_len - rising) * window


def attention_flops(m: AfmoeShape, q_len: int, kv_len: int) -> int:
    """Forward FLOPs of attention in every layer (QK^T and PV, 2 *
    head_dim each a pair and query head)."""
    pairs = (m.full_layers * attention_pairs(q_len, kv_len)
             + m.window_layers * attention_pairs(q_len, kv_len, m.window))
    return 4 * m.heads * m.head_dim * pairs


def prefill_flops(m: AfmoeShape, prompt_len: int) -> float:
    """Forward FLOPs to prefill ``prompt_len`` real tokens, whole or in
    chunks, and produce one next-token distribution."""
    return (prompt_len * layer_matmul_flops_per_token(m)
            + attention_flops(m, prompt_len, prompt_len)
            + head_flops_per_token(m))


def decode_flops(m: AfmoeShape, kv_len: int) -> float:
    """Forward FLOPs of one decoded token whose context, itself
    included, is ``kv_len`` tokens."""
    return (layer_matmul_flops_per_token(m) + head_flops_per_token(m)
            + attention_flops(m, 1, kv_len))


# -- least bytes --------------------------------------------------------------

def kv_token_bytes(m: AfmoeShape, itemsize: int) -> int:
    """K and V of one token in one layer."""
    return 2 * m.kv_heads * m.head_dim * itemsize


def window_attention_bytes(m: AfmoeShape, kv_lens: Iterable[int],
                           itemsize: int) -> int:
    """Least bytes the WINDOW layers' decode attention reads: each
    row's K and V under the window, once a layer."""
    return (m.window_layers * kv_token_bytes(m, itemsize)
            * sum(min(k, m.window) for k in kv_lens))


def full_attention_bytes(m: AfmoeShape, kv_lens: Iterable[int],
                         itemsize: int) -> int:
    return m.full_layers * kv_token_bytes(m, itemsize) * sum(kv_lens)


def experts_touched(m: AfmoeShape, rows: float) -> float:
    """Held experts that at least one of ``rows`` rows chose, in
    expectation under uniform routing."""
    return m.held * (1.0 - (1.0 - m.top_k / m.router_width) ** rows)


def decode_weight_bytes(m: AfmoeShape, rows: float, itemsize: int) -> float:
    """Weights one decode step of ``rows`` rows must read: all but the
    embedding (``rows`` rows of it) and the held experts nobody chose."""
    per_expert_layer = (mlp_params(m, m.expert_ffn)
                        * (1 + experts_touched(m, rows))
                        + m.hidden * m.router_width)
    params = (m.layers * attention_params(m)
              + m.dense_layers * mlp_params(m, m.ffn)
              + m.expert_layers * per_expert_layer
              + m.vocab * m.hidden + rows * m.hidden)
    return params * itemsize


def decode_steps_bytes(m: AfmoeShape, steps: int, kv_lens: Iterable[int],
                       itemsize: int) -> float:
    """Least bytes of ``steps`` decode steps that carried the rows
    ``kv_lens`` between them (the steps taken as equally full)."""
    kv_lens = list(kv_lens)
    if not steps:
        return 0.0
    return (steps * decode_weight_bytes(m, len(kv_lens) / steps, itemsize)
            + window_attention_bytes(m, kv_lens, itemsize)
            + full_attention_bytes(m, kv_lens, itemsize))

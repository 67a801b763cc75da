"""Work the algorithm needs, from shapes alone.

Every share of a peak or of a roofline in this benchmark divides work
counted here by a time measured on the chip.  Nothing here looks at the
program: the same work is counted whatever implements it, recomputation
is not work, and padding is not work.

A "model" below is the ``model`` group of a configuration file (see
``model_shape``).  FLOPs count a multiply-add as two.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable


@dataclasses.dataclass(frozen=True)
class ModelShape:
    """The sizes of a pre-LN GPT decoder that decide its work."""

    layers: int
    hidden: int
    heads: int
    ffn: int
    vocab: int
    positions: int

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def model_shape(model: dict) -> ModelShape:
    """``model`` group of a configuration file -> :class:`ModelShape`."""
    hidden = int(model["hidden_size"])
    return ModelShape(
        layers=int(model["num_layers"]), hidden=hidden,
        heads=int(model["num_attention_heads"]),
        ffn=int(model.get("ffn_hidden_size") or 4 * hidden),
        vocab=int(model["vocab_size"]),
        positions=int(model["max_position_embeddings"]))


def param_count(m: ModelShape) -> int:
    """Every parameter of the training model: per layer 12h^2 of GEMM
    weights (at ffn = 4h) and 13h of biases and LayerNorm vectors, the
    word and position embeddings, the final LayerNorm.  The head is tied
    to the word embedding."""
    per_layer = (4 * m.hidden * m.hidden + 2 * m.hidden * m.ffn
                 + 9 * m.hidden + m.ffn)
    return (m.layers * per_layer + (m.vocab + m.positions) * m.hidden
            + 2 * m.hidden)


def layer_matmul_flops_per_token(m: ModelShape) -> int:
    """Forward FLOPs of one token through the GEMMs of every layer
    (qkv, proj, h->ffn, ffn->h).  No attention, no head."""
    return 2 * m.layers * (4 * m.hidden * m.hidden + 2 * m.hidden * m.ffn)


def head_flops_per_token(m: ModelShape) -> int:
    """Forward FLOPs of one position through the LM head (tied
    embedding transpose).  The embedding lookup is a gather: no FLOPs."""
    return 2 * m.hidden * m.vocab


def attention_pairs(q_len: int, kv_len: int, causal: bool = True) -> int:
    """(query, key) pairs one head scores when the ``q_len`` queries are
    the last rows of a ``kv_len``-token context."""
    if not causal:
        return q_len * kv_len
    # query i (0-based among the q_len) sees kv_len - q_len + i + 1 keys
    return q_len * (kv_len - q_len) + q_len * (q_len + 1) // 2


def attention_flops(pairs: int, heads: int, head_dim: int) -> int:
    """Forward FLOPs of attention over ``pairs`` (query, key) pairs per
    head: QK^T and PV, 2 * head_dim each per pair."""
    return 4 * heads * head_dim * pairs


def attention_call(*, q_lens: Iterable[int], kv_lens: Iterable[int],
                   heads: int, head_dim: int, itemsize: int,
                   causal: bool = True, backward: bool = False) -> dict:
    """FLOPs and least HBM bytes of ONE attention call over a batch of
    rows (``q_lens[i]`` queries against ``kv_lens[i]`` keys).

    Bytes: q read and the output written once, k and v read once; the
    backward reads q, k, v, o, do and writes dq, dk, dv.  FLOPs of the
    backward are 2.5x the forward's (dq, dk, dv and the score
    recomputation the flash algorithm needs are 5 matmuls to the
    forward's 2)."""
    pairs = sum(attention_pairs(q, k, causal)
                for q, k in zip(q_lens, kv_lens))
    q_tok, kv_tok = sum(q_lens), sum(kv_lens)
    flops = attention_flops(pairs, heads, head_dim)
    row = heads * head_dim * itemsize
    nbytes = (2 * q_tok + 2 * kv_tok) * row
    if backward:
        flops = flops * 5 // 2
        nbytes = (4 * q_tok + 4 * kv_tok) * row
    return {"flops": flops, "bytes": nbytes}


def roofline_seconds(work: dict, peak: dict) -> dict:
    """The least time a chip with ``peak`` could take for ``work``
    (``{"flops", "bytes"}``) and which of the two bounds it."""
    t_f = work["flops"] / peak["bf16_flops_per_s"]
    t_b = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "bandwidth"}


def train_flops_per_token(m: ModelShape, seq: int) -> float:
    """Forward plus backward FLOPs per trained token at sequence length
    ``seq``: three times the forward (the backward is two matmuls to
    the forward's one), causal attention counted at its half triangle,
    recomputation not counted."""
    attn = attention_flops(attention_pairs(seq, seq), m.heads,
                           m.head_dim) * m.layers / seq
    fwd = layer_matmul_flops_per_token(m) + head_flops_per_token(m) + attn
    return 3.0 * fwd


def prefill_flops(m: ModelShape, prompt_len: int) -> int:
    """Forward FLOPs to prefill ``prompt_len`` real tokens and produce
    one next-token distribution (the head runs on one position)."""
    return (prompt_len * layer_matmul_flops_per_token(m)
            + m.layers * attention_flops(
                attention_pairs(prompt_len, prompt_len), m.heads,
                m.head_dim)
            + head_flops_per_token(m))


def decode_flops(m: ModelShape, kv_len: int) -> int:
    """Forward FLOPs of one decoded token whose context, itself
    included, is ``kv_len`` tokens."""
    return (layer_matmul_flops_per_token(m) + head_flops_per_token(m)
            + m.layers * attention_flops(kv_len, m.heads, m.head_dim))


def decode_attention_bytes(m: ModelShape, kv_lens: Iterable[int],
                           itemsize: int) -> int:
    """Least bytes ONE layer's decode attention reads: the K and V of
    every row's context, once."""
    return 2 * sum(kv_lens) * m.hidden * itemsize

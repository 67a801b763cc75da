"""Plain reference of the served ``lfm2_moe`` decoder (LiquidAI LFM2-MoE):
one full forward pass over a whole sequence, float32 at ``highest``
precision, no cache, no slots, no pages, no batching, no kernels,
nothing of the program imported.

Sizes are the configuration's: ``d`` hidden, ``H`` query and ``Hk`` K/V
heads of ``dh = d / H``, ``K`` convolution taps (``conv_L_cache``),
``E`` the router's width, of which the experts ``[lo, hi)`` are held
here (all of them where one chip holds a layer).

* ``RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g``.  ``x0 = embed[tokens]``.
* Block ``l``: ``h = x + Mixer_l(RMS(x; g_op))``, ``x' = h +
  FFN_l(RMS(h; g_ffn))``.  Final ``RMS(x; norm_f)``; ``logits = x
  embed^T`` (the head is the embedding).
* Convolution mixer (``layer_types[l] == "conv"``) on ``u``: ``[B | C |
  x~] = u w_in`` (``[d, 3d]``); ``v_t = B_t * x~_t``; ``c_t = sum_{j <
  K} conv_w[j] * v_{t - K + 1 + j}`` with ``v`` before the first token
  0 (no bias, no activation); ``y_t = (C_t * c_t) w_out``.
* Attention mixer (``"full_attention"``): ``q = RMS_head(u wq; gq)``,
  ``k = RMS_head(u wk; gk)`` over each head's ``dh``, ``v = u wv``;
  RoPE (rotate-half, all ``dh`` dimensions, absolute 0-based positions,
  ``theta`` unscaled) on q and k; query head ``n`` reads K/V head ``n //
  (H / Hk)``; scores ``q . k / sqrt(dh)``, causal softmax; ``concat(o_n)
  wo``.  No biases.
* FFN: ``(silu(u wg) * (u wu)) wd``, of width ``intermediate_size`` in
  the first ``num_dense_layers`` layers; after them the expert layer:
  ``s = sigmoid(u router)`` (``E`` wide); ``sel = top_k(s +
  expert_bias)``, the bias used for the choice only; ``w = s[sel] /
  (sum(s[sel]) + 1e-6) * routed_scaling_factor``; ``MoE(u) = sum_{i: lo
  <= sel_i < hi} w_i Expert_{sel_i}(u)``, each expert a SwiGLU of width
  ``moe_intermediate_size``; no shared expert.

Departures and readings of the published code are the configuration
file's ``assumed`` list (the ``in_proj`` split order ``B | C | x~``, a
tied head, ``expert_bias`` drawn from the seed).

Float32 copies of every weight would not fit beside the bfloat16 ones:
the layers are walked one at a time, each in its own jitted call, and
the experts one at a time inside it (a scan over the stacked weights,
each cast as it is used); attention is computed in blocks of queries.

What is compared is what ``afmoe_serve`` compares: per served position,
the reference's best logit, its logit of the served token, the logit of
the token a CONTROL puts first, and the least routing margin over the
expert layers.  Two controls: ``fp8`` (both operands of every matrix
product through float8, ``lowp``) and ``tail_reset`` (everything exact,
but each convolution layer's tail zeroed where decoding takes over from
the prompt's prefill: what a program that did not carry the tail from
prefill into decode would serve).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import lowp

Q_BLOCK = 256
# The selection bias, on the scale of a sigmoid score, as afmoe_serve's:
# it changes the choice for some of the tokens and leaves the experts'
# popularity even whatever the seed.
BIAS_STD = 0.001


class Shape(NamedTuple):
    """The sizes that decide the computation (hashable: a static
    argument of the jitted layers)."""

    vocab: int
    hidden: int
    heads: int
    kv_heads: int
    layer_types: Tuple[str, ...]
    dense_layers: int
    ffn: int
    expert_ffn: int
    router_width: int
    held: Tuple[int, int]
    top_k: int
    route_scale: float
    taps: int
    theta: float
    eps: float

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def rope_theta(model: dict) -> float:
    """``rope_parameters.rope_theta`` (the catalog's form) or
    ``rope_theta``."""
    params = model.get("rope_parameters") or {}
    return float(params.get("rope_theta", model.get("rope_theta", 1e4)))


def model_shape(model: dict) -> Shape:
    """The configuration file's published keys (its ``model`` group
    merged in: ``experts_held`` ``[lo, hi)``, all of them where absent)
    -> :class:`Shape`.  Refuses what this reference does not implement."""
    if model.get("conv_bias") or not model.get("norm_topk_prob", True) \
            or not model.get("use_expert_bias", True) \
            or not model.get("tie_word_embeddings", True):
        raise ValueError("a short convolution without bias, renormalised "
                         "sigmoid routing with a selection bias and a tied "
                         "head are implemented")
    kinds = set(model["layer_types"])
    if not kinds <= {"conv", "full_attention"}:
        raise ValueError(f"layer types {kinds} are not conv / "
                         "full_attention")
    if len(model["layer_types"]) != int(model["num_hidden_layers"]):
        raise ValueError("layer_types does not list num_hidden_layers")
    experts = int(model["num_experts"])
    lo, hi = model.get("experts_held", (0, experts))
    return Shape(
        vocab=int(model["vocab_size"]), hidden=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        layer_types=tuple(model["layer_types"]),
        dense_layers=int(model["num_dense_layers"]),
        ffn=int(model["intermediate_size"]),
        expert_ffn=int(model["moe_intermediate_size"]),
        router_width=experts, held=(int(lo), int(hi)),
        top_k=int(model["num_experts_per_tok"]),
        route_scale=float(model["routed_scaling_factor"]),
        taps=int(model["conv_L_cache"]), theta=rope_theta(model),
        eps=float(model["norm_eps"]))


def param_layout(model: dict) -> dict:
    """``{path: (shape, kind, std)}`` as ``weights.make`` reads it:
    ``matrix`` leaves normal with std 1/sqrt(fan_in) (``[in, out]``; the
    convolution's taps ``[K, d]``, 1/sqrt(K)), ``gain`` 1 + normal *
    0.02, ``bias`` (the router's selection bias) normal * ``BIAS_STD``.
    The held experts' matrices are stacked on a leading axis."""
    m = model_shape(model)
    d, hd = m.hidden, m.head_dim
    mat = lambda *s: (s, "matrix", 1.0 / math.sqrt(s[-2]))
    gain = lambda n: ((n,), "gain", 0.02)
    mlp = lambda *lead, f: {"wg": mat(*lead, d, f), "wu": mat(*lead, d, f),
                            "wd": mat(*lead, f, d)}
    layers = []
    for i, kind in enumerate(m.layer_types):
        layer = {"g_op": gain(d), "g_ffn": gain(d)}
        if kind == "conv":
            layer.update(w_in=mat(d, 3 * d), conv_w=mat(m.taps, d),
                         w_out=mat(d, d))
        else:
            layer.update(wq=mat(d, m.heads * hd), wk=mat(d, m.kv_heads * hd),
                         wv=mat(d, m.kv_heads * hd), wo=mat(m.heads * hd, d),
                         gq=gain(hd), gk=gain(hd))
        if i < m.dense_layers:
            layer["mlp"] = mlp(f=m.ffn)
        else:
            layer["moe"] = {
                "router": mat(d, m.router_width),
                "expert_bias": ((m.router_width,), "bias", BIAS_STD),
                "experts": mlp(m.held[1] - m.held[0], f=m.expert_ffn)}
        layers.append(layer)
    return {"embed": ((m.vocab, d), "matrix", 1.0 / math.sqrt(d)),
            "norm_f": gain(d), "layers": layers}


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(g)


def _rope(x, theta):
    """x [s, heads, dh], positions 0..s-1; rotate-half."""
    s, _, dh = x.shape
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _short_conv(u, layer, m: Shape, mm, reset_at):
    """The convolution mixer over a whole sequence.  ``reset_at``: from
    that position on, no ``v`` from before it enters a window (the
    ``tail_reset`` control; past the sequence's end for the reference
    proper)."""
    s, d = u.shape
    b, c, xt = jnp.split(mm(u, layer["w_in"]), 3, axis=-1)
    v = b * xt
    padded = jnp.concatenate([jnp.zeros((m.taps - 1, d), jnp.float32), v])
    w = _f32(layer["conv_w"])
    t = jnp.arange(s)
    conv = 0.0
    for j in range(m.taps):
        src = t - (m.taps - 1) + j
        keep = ~((t >= reset_at) & (src < reset_at))
        conv = conv + jnp.where(keep[:, None], w[j] * padded[j:j + s], 0.0)
    return mm(c * conv, layer["w_out"])


def _attention(u, layer, m: Shape, mm, cast):
    s = u.shape[0]
    hd, group = m.head_dim, m.heads // m.kv_heads
    q = _rms(mm(u, layer["wq"]).reshape(s, m.heads, hd), layer["gq"], m.eps)
    k = _rms(mm(u, layer["wk"]).reshape(s, m.kv_heads, hd), layer["gk"],
             m.eps)
    v = mm(u, layer["wv"]).reshape(s, m.kv_heads, hd)
    q, k = _rope(q, m.theta), _rope(k, m.theta)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = math.gcd(s, Q_BLOCK)
    cols = jnp.arange(s)

    def rows_of(i):
        rows = i * block + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=0)
        scores = jnp.einsum("qnd,knd->nqk", cast(qb), cast(k)) \
            / math.sqrt(hd)
        see = cols[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(see[None], scores, -jnp.inf), -1)
        return jnp.einsum("nqk,knd->qnd", cast(probs), cast(v))

    ctx = jax.lax.map(rows_of, jnp.arange(s // block))
    return mm(ctx.reshape(s, m.heads * hd), layer["wo"])


def _held_margin(held, kth, nxt):
    """By how little the choice of a HELD expert is decided (as
    ``afmoe_serve._held_margin``): ``held`` ``[s, n]`` the held experts'
    ``score + bias``, ``kth`` ``[s, 1]`` the last chosen value, ``nxt``
    the first one left out.  Where every expert is held it is the gap
    between the ``top_k``-th and the next."""
    return jnp.min(jnp.where(held >= kth, held - nxt, kth - held), axis=-1)


def _experts(u, moe, m: Shape, mm):
    """The expert layer: (its output, the least routing margin [s])."""
    lo, hi = m.held
    swiglu = lambda p: mm(jax.nn.silu(mm(u, p["wg"])) * mm(u, p["wu"]),
                          p["wd"])
    score = jax.nn.sigmoid(mm(u, moe["router"]))
    biased = score + _f32(moe["expert_bias"])
    top, sel = jax.lax.top_k(biased, m.top_k + 1)
    margin = _held_margin(biased[:, lo:hi], top[:, -2:-1], top[:, -1:])
    sel = sel[:, :m.top_k]
    w = jnp.take_along_axis(score, sel, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6) * m.route_scale

    def one_expert(y, ep):
        e, p = ep
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        return y + w_e[:, None] * swiglu(p), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                        (jnp.arange(lo, hi), moe["experts"]))
    return y, margin


@functools.partial(jax.jit, static_argnames=("m", "cast_name"))
def _layer(x, margin, layer, reset_at, m: Shape, cast_name: str):
    """One block, its weights cast to float32 in here."""
    cast = lowp.CASTS["fp8" if cast_name == "fp8" else "exact"]
    mm = lambda a, w: jnp.einsum("...i,io->...o", cast(a), cast(_f32(w)))
    with jax.default_matmul_precision("highest"):
        u = _rms(x, layer["g_op"], m.eps)
        if "w_in" in layer:
            h = x + _short_conv(u, layer, m, mm, reset_at)
        else:
            h = x + _attention(u, layer, m, mm, cast)
        u = _rms(h, layer["g_ffn"], m.eps)
        if "mlp" in layer:
            p = layer["mlp"]
            y = mm(jax.nn.silu(mm(u, p["wg"])) * mm(u, p["wu"]), p["wd"])
        else:
            y, layer_margin = _experts(u, layer["moe"], m, mm)
            margin = jnp.minimum(margin, layer_margin)
        return h + y, margin


def hidden_states(params, tokens, m: Shape, cast_name: str = "exact",
                  reset_at=None):
    """tokens [s] -> (final-norm hidden states [s, d], the least
    routing margin at each position [s]), float32; the layers walked
    one at a time.  ``cast_name`` ``tail_reset`` zeroes each
    convolution layer's tail at position ``reset_at``."""
    s = tokens.shape[0]
    reset = jnp.int32(reset_at if cast_name == "tail_reset" else s)
    x = _f32(params["embed"])[tokens]
    margin = jnp.full((s,), jnp.inf, jnp.float32)
    for layer in params["layers"]:
        x, margin = _layer(x, margin, layer, reset, m, cast_name)
    return _rms(x, params["norm_f"], m.eps), margin


@functools.partial(jax.jit, static_argnames=("cast_name",))
def _gaps(embed, hid, hid_low, margin, first, count, served,
          cast_name: str):
    n_max = served.shape[0]
    with jax.default_matmul_precision("highest"):
        head = _f32(embed).T
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, first, n_max, 0)
        logits = take(hid) @ head
        best = jnp.max(logits, axis=-1)
        chosen = jnp.take_along_axis(logits, served[:, None], 1)[:, 0]
        low = chosen
        if cast_name != "exact":
            cast = lowp.CASTS["fp8" if cast_name == "fp8" else "exact"]
            pick = jnp.argmax(cast(take(hid_low)) @ cast(head), axis=-1)
            low = jnp.take_along_axis(logits, pick[:, None], 1)[:, 0]
    real = jnp.arange(n_max) < count
    z = lambda a: jnp.where(real, a, 0.0)
    return z(best), z(chosen), z(low), jnp.where(real, take(margin), jnp.inf)


def served_gaps(params, tokens, first, count, served, *, shape: Shape,
                cast_name: str = "exact"):
    """For one request padded to a fixed length: ``tokens`` [s] is the
    prompt followed by the served tokens (then padding), ``first`` the
    index of the position that predicts the first served token (the
    prompt's last: decoding takes over at ``first + 1``), ``served``
    [n_max] the served tokens, of which ``count`` are real.

    Returns ``(best, chosen, control_first, margin)`` [n_max] each: the
    reference's best logit at each served position, its logit of the
    served token, (``cast_name`` ``fp8`` or ``tail_reset``) its logit of
    the token the control puts first there, and the least routing
    margin at the position.  Rows past ``count`` are zeroed (their
    margin is infinite)."""
    hid, margin = hidden_states(params, tokens, shape)
    hid_low = hid if cast_name == "exact" else hidden_states(
        params, tokens, shape, cast_name, reset_at=first + 1)[0]
    return _gaps(params["embed"], hid, hid_low, margin, first, count,
                 served, cast_name)


def logits_all(params, tokens, shape: Shape):
    """Every position's logits [s, vocab]: what the tests compare the
    program's prefill, decode and chunked prefill with."""
    with jax.default_matmul_precision("highest"):
        hid, _ = hidden_states(params, tokens, shape)
        return hid @ _f32(params["embed"]).T

"""Plain reference of the served ``afmoe`` decoder (arcee-ai Trinity):
one full forward pass over a whole sequence, float32 at ``highest``
precision, no cache, no pages, no batching, no kernels.

Sizes are the configuration's; ``E`` is the router's width, of which
the experts ``[lo, hi)`` are held here.

* ``x0 = embed[tokens] * sqrt(d)`` (muP).  ``RMS(x; g) = x *
  rsqrt(mean(x^2) + eps) * g``.
* Attention on ``u = RMS(x; g1)``: ``q = u wq`` as ``H`` heads, ``k =
  u wk``, ``v = u wv`` as ``Hkv`` heads of ``dh``, ``gate = u wgate``
  (``[d, H dh]``); ``q = RMS(q; gq)``, ``k = RMS(k; gk)`` over each
  head's ``dh``; on SLIDING layers only, RoPE (rotate-half, all ``dh``
  dimensions, absolute 0-based positions, ``theta`` unscaled) on q and
  k — full layers take no positional signal.  Query head ``n`` reads
  K/V head ``n // (H / Hkv)``.  Scores ``q.k / sqrt(dh)``; position
  ``p`` sees keys ``j <= p`` and, on sliding layers, only ``j > p - W``
  (itself and the ``W - 1`` before it).  ``a = softmax(scores) v *
  sigmoid(gate)``, ``Attn = a wo``.  No biases.
* Block: ``h = x + RMS(Attn(RMS(x; g1)); g2)``; ``x' = h +
  RMS(MLP(RMS(h; g3)); g4)``.  Final ``RMS(x; norm_f)``, then
  ``logits = x head`` over this chip's vocabulary slice.
* Dense MLP (the first ``num_dense_layers`` layers) and every expert:
  ``(silu(u wg) * (u wu)) wd``.
* Expert layer on ``u = RMS(h; g3)``: ``s = sigmoid(u router)`` (``E``
  wide); ``sel = top_k(s + expert_bias)``, the bias used for the choice
  only; ``w = s[sel]``; ``w = w / (sum(w) + 1e-20) * route_scale``;
  ``MoE(u) = sum_{i: lo <= sel_i < hi} w_i Expert_{sel_i}(u) +
  Shared(u)`` — what the absent experts would add is left out, as on
  one chip of the expert-parallel deployment, and that partial result
  goes on to the next layer.

Departures from the published model are the configuration file's
``assumed`` list (RoPE on sliding layers only; the gate from the normed
input, before ``wo``; the window inclusive of the query; the muP
factor; ``expert_bias`` drawn from the seed).

Float32 copies of every weight would not fit beside the bfloat16 ones:
the layers are walked one at a time and the held experts one at a time
(a scan over the stacked weights, each cast as it is used), and
attention over a sequence of some 17,000 positions is computed in
blocks of queries.

What is compared is what ``gpt_serve`` compares: per served position,
the reference's best logit, its logit of the served token, and the
token a lower precision puts first.  A fourth vector gives, per served
position, the least margin over the expert layers by which a HELD
expert's place inside or outside the ``top_k`` is decided
(:func:`_held_margin`): where it is tiny, the part of the result that is
computed here is decided by rounding, and the caller may leave such a
position out (PERF.md section 2).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import lowp

Q_BLOCK = 256
# The selection bias, on the scale of a sigmoid score (the fourth and
# the fifth score lie some 0.01 apart): wide enough to change the choice
# for about a tenth of the tokens, narrow enough that the 32 held
# experts' share of the (token, expert) pairs stays within a percent or
# so of an eighth whatever the seed.  The cell's time follows that share
# (measured: 4.8% more pairs, 1.5% more decode time), and at 0.02 an
# expert's popularity swings by half and the share by 9% from seed to
# seed, at 0.005 by 2.4% (PERF.md, PR 29).
BIAS_STD = 0.001


class Shape(NamedTuple):
    """The sizes that decide the computation (hashable: a static
    argument of the jitted forward)."""

    vocab: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]
    dense_layers: int
    ffn: int
    expert_ffn: int
    router_width: int
    held: Tuple[int, int]
    top_k: int
    route_scale: float
    window: int
    theta: float
    eps: float


def model_shape(model: dict) -> Shape:
    """The configuration file's keys (the published names; ``num_experts``
    counts the experts held here, ``router_width`` the router's
    outputs, ``experts_held`` the ``[lo, hi)`` of them) -> Shape."""
    lo, hi = model["experts_held"]
    if hi - lo != model["num_experts"]:
        raise ValueError("experts_held does not hold num_experts experts")
    return Shape(
        vocab=int(model["vocab_size"]), hidden=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        layer_types=tuple(model["layer_types"]),
        dense_layers=int(model["num_dense_layers"]),
        ffn=int(model["intermediate_size"]),
        expert_ffn=int(model["moe_intermediate_size"]),
        router_width=int(model["router_width"]), held=(int(lo), int(hi)),
        top_k=int(model["num_experts_per_tok"]),
        route_scale=float(model["route_scale"]),
        window=int(model["sliding_window"]),
        theta=float(model["rope_theta"]), eps=float(model["rms_norm_eps"]))


def param_layout(model: dict) -> dict:
    """``{path: (shape, kind, std)}`` (``weights.make``): ``matrix``
    leaves normal with std 1/sqrt(fan_in), ``gain`` 1 + normal * 0.02,
    ``bias`` (the router's selection bias) normal * ``BIAS_STD``.  Matrices are
    ``[in, out]``; the held experts' are stacked on a leading axis.
    ``layers`` is a list: the leading dense layers and the expert
    layers differ."""
    m = model_shape(model)
    d, hd = m.hidden, m.head_dim
    mat = lambda *s: (s, "matrix", 1.0 / math.sqrt(s[-2]))
    gain = lambda n: ((n,), "gain", 0.02)
    mlp = lambda *lead, f: {"wg": mat(*lead, d, f), "wu": mat(*lead, d, f),
                            "wd": mat(*lead, f, d)}
    layers = []
    for i in range(len(m.layer_types)):
        layer = {"g1": gain(d), "g2": gain(d), "g3": gain(d), "g4": gain(d),
                 "gq": gain(hd), "gk": gain(hd),
                 "wq": mat(d, m.heads * hd), "wk": mat(d, m.kv_heads * hd),
                 "wv": mat(d, m.kv_heads * hd),
                 "wgate": mat(d, m.heads * hd), "wo": mat(m.heads * hd, d)}
        if i < m.dense_layers:
            layer["mlp"] = mlp(f=m.ffn)
        else:
            layer["moe"] = {
                "router": mat(d, m.router_width),
                "expert_bias": ((m.router_width,), "bias", BIAS_STD),
                "experts": mlp(m.held[1] - m.held[0], f=m.expert_ffn),
                "shared": mlp(f=m.expert_ffn)}
        layers.append(layer)
    return {"embed": ((m.vocab, d), "matrix", 1.0 / math.sqrt(d)),
            "head": mat(d, m.vocab), "norm_f": gain(d), "layers": layers}


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


def _attention(q, k, v, window, cast):
    """q [s, H, dh], k/v [s, Hkv, dh] -> [s, H * dh]: causal softmax
    attention, ``window`` keys back where given, in blocks of queries."""
    s, heads, dh = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = math.gcd(s, Q_BLOCK)
    cols = jnp.arange(s)

    def rows_of(i):
        rows = i * block + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=0)
        scores = jnp.einsum("qnd,knd->nqk", cast(qb), cast(k)) \
            / math.sqrt(dh)
        see = cols[None, :] <= rows[:, None]
        if window is not None:
            see &= cols[None, :] > rows[:, None] - window
        probs = jax.nn.softmax(jnp.where(see[None], scores, -jnp.inf), -1)
        return jnp.einsum("nqk,knd->qnd", cast(probs), cast(v))

    out = jax.lax.map(rows_of, jnp.arange(s // block))
    return out.reshape(s, heads * dh)


def _held_margin(held, kth, nxt):
    """By how little the choice of a HELD expert is decided: ``held``
    ``[s, n]`` the held experts' ``score + bias``, ``kth`` ``[s, 1]``
    the last chosen value and ``nxt`` the first one left out.  A chosen
    expert stays chosen while it keeps above ``nxt``, one left out stays
    out while it keeps below ``kth``; the least of those distances over
    the held experts.  A swap among experts held elsewhere changes only
    the normalising sum here, by less than the margin itself."""
    return jnp.min(jnp.where(held >= kth, held - nxt, kth - held), axis=-1)


def hidden_states(params, tokens, m: Shape, cast=lowp.exact):
    """tokens [s] -> (final-norm hidden states [s, d], the least
    routing margin at each position [s]), float32."""
    s = tokens.shape[0]
    mm = lambda a, w: jnp.einsum("...i,io->...o", cast(a), cast(_f32(w)))
    swiglu = lambda u, p: mm(jax.nn.silu(mm(u, p["wg"])) * mm(u, p["wu"]),
                             p["wd"])
    lo, hi = m.held
    x = _f32(params["embed"])[tokens] * math.sqrt(m.hidden)
    margin = jnp.full((s,), jnp.inf, jnp.float32)
    for kind, layer in zip(m.layer_types, params["layers"]):
        sliding = kind == "sliding_attention"
        u = _rms(x, layer["g1"], m.eps)
        q = mm(u, layer["wq"]).reshape(s, m.heads, m.head_dim)
        k = mm(u, layer["wk"]).reshape(s, m.kv_heads, m.head_dim)
        v = mm(u, layer["wv"]).reshape(s, m.kv_heads, m.head_dim)
        gate = mm(u, layer["wgate"])
        q, k = _rms(q, layer["gq"], m.eps), _rms(k, layer["gk"], m.eps)
        if sliding:
            q, k = _rope(q, m.theta), _rope(k, m.theta)
        a = _attention(q, k, v, m.window if sliding else None, cast)
        h = x + _rms(mm(a * jax.nn.sigmoid(gate), layer["wo"]),
                     layer["g2"], m.eps)
        u = _rms(h, layer["g3"], m.eps)
        if "mlp" in layer:
            y = swiglu(u, layer["mlp"])
        else:
            moe = layer["moe"]
            score = jax.nn.sigmoid(mm(u, moe["router"]))
            biased = score + _f32(moe["expert_bias"])
            top, sel = jax.lax.top_k(biased, m.top_k + 1)
            margin = jnp.minimum(margin, _held_margin(
                biased[:, lo:hi], top[:, -2:-1], top[:, -1:]))
            sel = sel[:, :m.top_k]
            w = jnp.take_along_axis(score, sel, axis=-1)
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * m.route_scale

            def one_expert(y, ep, w=w, sel=sel, u=u):
                e, p = ep
                w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
                return y + w_e[:, None] * swiglu(u, p), None

            y, _ = jax.lax.scan(one_expert, swiglu(u, moe["shared"]),
                                (jnp.arange(lo, hi), moe["experts"]))
        x = h + _rms(y, layer["g4"], m.eps)
    return _rms(x, params["norm_f"], m.eps), margin


@functools.partial(jax.jit, static_argnames=("shape", "cast_name"))
def served_gaps(params, tokens, first, count, served, *, shape: Shape,
                cast_name: str = "exact"):
    """For one request padded to a fixed length: ``tokens`` [s] is the
    prompt followed by the served tokens (then padding), ``first`` the
    index of the position that predicts the first served token,
    ``served`` [n_max] the served tokens, of which ``count`` are real.

    Returns ``(best, chosen, lowp_first, margin)`` [n_max] each: the
    reference's best logit at each served position, its logit of the
    served token, (``cast_name`` other than ``exact``) its logit of the
    token that the lower precision puts first there, and the least
    routing margin at the position.  Rows past ``count`` are zeroed
    (their margin is infinite)."""
    n_max = served.shape[0]
    with jax.default_matmul_precision("highest"):
        head = _f32(params["head"])
        hid, margin = hidden_states(params, tokens, shape)
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, first, n_max, 0)
        logits = take(hid) @ head
        best = jnp.max(logits, axis=-1)
        chosen = jnp.take_along_axis(logits, served[:, None], 1)[:, 0]
        low = chosen
        if cast_name != "exact":
            cast = lowp.CASTS[cast_name]
            hid_l, _ = hidden_states(params, tokens, shape, cast=cast)
            pick = jnp.argmax(cast(take(hid_l)) @ cast(head), axis=-1)
            low = jnp.take_along_axis(logits, pick[:, None], 1)[:, 0]
    real = jnp.arange(n_max) < count
    z = lambda a: jnp.where(real, a, 0.0)
    return z(best), z(chosen), z(low), jnp.where(real, take(margin), jnp.inf)


def logits_all(params, tokens, shape: Shape):
    """Every position's logits [s, vocab]: what the tests compare the
    program's prefill, decode and chunked prefill with."""
    with jax.default_matmul_precision("highest"):
        hid, _ = hidden_states(params, tokens, shape)
        return hid @ _f32(params["head"])

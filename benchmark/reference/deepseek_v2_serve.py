"""Plain reference of the served ``deepseek_v2`` decoder (DeepSeek-V2):
one full forward pass over a whole sequence, float32 at ``highest``
precision, the EXPANDED form of latent attention only, no cache, no
pages, no batching, no kernels, nothing of the program imported.

Sizes are the configuration's; ``E`` is the router's width, of which
the experts ``[lo, hi)`` are held here.

* ``RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g``.  ``x0 =
  embed[tokens]``; block ``h = x + Attn(RMS(x; g1))``, ``x' = h +
  MLP(RMS(h; g2))``; final ``RMS(x; norm_f)``, ``logits = x head`` over
  this chip's vocabulary slice.
* Latent attention on ``u = RMS(x; g1)`` at position ``p``: ``cq =
  RMS(u wdq; gq)``; ``q = cq wuq`` as ``H`` heads of ``[q_nope (dn),
  q_pe (dr)]``; ``[c, k_pe] = u wdkv`` (``rank + dr``); ``c = RMS(c;
  gkv)``; for every head ``n``, ``k_nope_n = c wuk_n`` (``dn``) and
  ``v_n = c wuv_n`` (``dv``): ``wuk`` and ``wuv`` are the two column
  blocks of the published ``kv_b_proj``; ``q_pe`` and ``k_pe`` take
  RoPE at ``p``, ``k_pe`` ONE vector for all heads; ``k_n = [k_nope_n,
  k_pe]``; scores ``q_n . k_n * s``, ``s = (dn + dr)^-0.5 * m^2``,
  ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax; ``o_n
  = sum_j a_nj v_nj``; ``Attn = concat(o_n) wo``.
* YaRN RoPE over the ``dr`` rotary dimensions as ``dr / 2`` ADJACENT
  pairs ``(2i, 2i + 1)``: ``f_i = theta^(-2i / dr)``; ``corr(r) = dr
  ln(L0 / (2 pi r)) / (2 ln theta)``; ``low = floor(corr(beta_fast))``,
  ``high = ceil(corr(beta_slow))``; ``ramp_i = clip((i - low) / (high -
  low), 0, 1)``; ``inv_freq_i = (f_i / factor) ramp_i + f_i (1 -
  ramp_i)``; angle ``p inv_freq_i``; cos and sin times ``yarn_mscale(
  factor, mscale) / yarn_mscale(factor, mscale_all_dim)`` (1 here).
* Dense MLP (the first ``first_k_dense_replace`` layers), every routed
  expert and the shared expert: ``(silu(u wg) * (u wu)) wd``; the
  shared expert is ONE such MLP of width ``n_shared * expert width``.
* Expert layer on ``u = RMS(h; g2)``, router in float32: ``s =
  softmax(u router)`` over ``E``; the experts lie in ``n_group`` equal
  groups in order; a group scores as its best expert; the ``topk_group``
  best groups are kept and scores outside them set to 0; ``sel =
  top_k`` of what is left; ``w = s[sel] * routed_scaling_factor`` (no
  renormalisation, no bias); ``MoE(u) = sum_{i: lo <= sel_i < hi} w_i
  Expert_{sel_i}(u) + Shared(u)``: what the absent experts would add is
  left out, as on one chip of the expert-parallel deployment.

Departures and readings of the published code are the configuration
file's ``assumed`` list.

Float32 copies of every weight would not fit beside the bfloat16 ones,
nor would 128 heads' keys, values and scores over 18,000 positions: the
layers are walked one at a time, the held experts one at a time, and
attention a group of heads at a time (their queries, keys and values
made from ``cq`` and ``c`` inside the walk), in blocks of queries.

What is compared is what ``afmoe_serve`` compares, and ``served_gaps``
returns the same four vectors.  The routing margin of a position is the
least, over the expert layers, of two distances: by how little a held
expert's place inside or outside the ``top_k`` is decided, and by how
little the set of ``topk_group`` kept groups is decided where it
matters to the experts held here (:func:`_margins`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import lowp

Q_BLOCK = 256
HEAD_GROUP = 16


class Shape(NamedTuple):
    """The sizes that decide the computation (hashable: a static
    argument of the jitted forward)."""

    vocab: int
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    layers: int
    dense_layers: int
    ffn: int
    expert_ffn: int
    router_width: int
    held: Tuple[int, int]
    top_k: int
    n_group: int
    topk_group: int
    route_scale: float
    n_shared: int
    theta: float
    yarn: Tuple[float, int, float, float, float, float]
    eps: float


def model_shape(model: dict) -> Shape:
    """The configuration file's keys (the published names;
    ``n_routed_experts`` counts the experts held here, ``router_width``
    the router's outputs, ``experts_held`` the ``[lo, hi)`` of them) ->
    Shape."""
    lo, hi = model["experts_held"]
    if hi - lo != model["n_routed_experts"]:
        raise ValueError("experts_held does not hold n_routed_experts")
    y = model["rope_scaling"]
    return Shape(
        vocab=int(model["vocab_size"]), hidden=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        q_rank=int(model["q_lora_rank"]), kv_rank=int(model["kv_lora_rank"]),
        nope=int(model["qk_nope_head_dim"]),
        rope=int(model["qk_rope_head_dim"]), v_dim=int(model["v_head_dim"]),
        layers=int(model["num_hidden_layers"]),
        dense_layers=int(model["first_k_dense_replace"]),
        ffn=int(model["intermediate_size"]),
        expert_ffn=int(model["moe_intermediate_size"]),
        router_width=int(model["router_width"]), held=(int(lo), int(hi)),
        top_k=int(model["num_experts_per_tok"]),
        n_group=int(model["n_group"]), topk_group=int(model["topk_group"]),
        route_scale=float(model["routed_scaling_factor"]),
        n_shared=int(model["n_shared_experts"]),
        theta=float(model["rope_theta"]),
        yarn=(float(y["factor"]),
              int(y["original_max_position_embeddings"]),
              float(y["beta_fast"]), float(y["beta_slow"]),
              float(y["mscale"]), float(y["mscale_all_dim"])),
        eps=float(model["rms_norm_eps"]))


def param_layout(model: dict) -> dict:
    """``{path: (shape, kind, std)}`` (``weights.make``): ``matrix``
    leaves normal with std 1/sqrt(fan_in), ``gain`` 1 + normal * 0.02.
    Matrices are ``[in, out]``; the held experts' are stacked on a
    leading axis.  ``layers`` is a list: the leading dense layers and
    the expert layers differ."""
    m = model_shape(model)
    d, nh = m.hidden, m.heads
    mat = lambda *s: (s, "matrix", 1.0 / math.sqrt(s[-2]))
    gain = lambda n: ((n,), "gain", 0.02)
    mlp = lambda *lead, f: {"wg": mat(*lead, d, f), "wu": mat(*lead, d, f),
                            "wd": mat(*lead, f, d)}
    layers = []
    for i in range(m.layers):
        layer = {"g1": gain(d), "g2": gain(d), "gq": gain(m.q_rank),
                 "gkv": gain(m.kv_rank), "wdq": mat(d, m.q_rank),
                 "wuq": mat(m.q_rank, nh * (m.nope + m.rope)),
                 "wdkv": mat(d, m.kv_rank + m.rope),
                 "wuk": mat(m.kv_rank, nh * m.nope),
                 "wuv": mat(m.kv_rank, nh * m.v_dim),
                 "wo": mat(nh * m.v_dim, d)}
        if i < m.dense_layers:
            layer["mlp"] = mlp(f=m.ffn)
        else:
            layer["moe"] = {
                "router": mat(d, m.router_width),
                "experts": mlp(m.held[1] - m.held[0], f=m.expert_ffn),
                "shared": mlp(f=m.expert_ffn * m.n_shared)}
        layers.append(layer)
    return {"embed": ((m.vocab, d), "matrix", 1.0 / math.sqrt(d)),
            "head": mat(d, m.vocab), "norm_f": gain(d), "layers": layers}


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(g)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(m: Shape):
    """(inv_freq [rope / 2] float64, low, high)."""
    factor, original, beta_fast, beta_slow, _, _ = m.yarn
    i = np.arange(m.rope // 2, dtype=np.float64)
    f = m.theta ** (-2.0 * i / m.rope)
    corr = lambda r: (m.rope * math.log(original / (2 * math.pi * r))
                      / (2 * math.log(m.theta)))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), m.rope - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp), low, high


def softmax_scale(m: Shape) -> float:
    mscale = yarn_mscale(m.yarn[0], m.yarn[5])
    return (m.nope + m.rope) ** -0.5 * mscale * mscale


def _rope(x, m: Shape):
    """x [s, ..., rope], positions 0..s-1 on the first axis; adjacent
    pairs rotated in place."""
    inv_freq, _, _ = yarn_frequencies(m)
    gain = yarn_mscale(m.yarn[0], m.yarn[4]) / yarn_mscale(m.yarn[0],
                                                           m.yarn[5])
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(cq, c, k_pe, layer, m: Shape, cast):
    """``cq`` [s, q_rank], ``c`` [s, kv_rank] (both after their norms),
    ``k_pe`` [s, rope] (after RoPE) -> concat(o_n) [s, H * v_dim]: the
    equations as written, a group of heads at a time, in blocks of
    queries."""
    s = cq.shape[0]
    nh, g = m.heads, math.gcd(m.heads, HEAD_GROUP)
    scale = softmax_scale(m)
    block = math.gcd(s, Q_BLOCK)
    cols = jnp.arange(s)
    heads_of = lambda w, width: _f32(w).reshape(
        w.shape[0], nh // g, g, width).transpose(1, 0, 2, 3)
    mm = lambda a, w: jnp.einsum("si,ind->snd", cast(a), cast(w))

    def group(ws):
        wuq, wuk, wuv = ws
        q = mm(cq, wuq)
        q = jnp.concatenate([q[..., :m.nope], _rope(q[..., m.nope:], m)], -1)
        k = jnp.concatenate([mm(c, wuk), jnp.broadcast_to(
            k_pe[:, None, :], (s, g, m.rope))], -1)
        v = mm(c, wuv)

        def rows_of(i):
            rows = i * block + jnp.arange(block)
            qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=0)
            scores = jnp.einsum("qnd,knd->nqk", cast(qb), cast(k)) * scale
            see = cols[None, :] <= rows[:, None]
            probs = jax.nn.softmax(
                jnp.where(see[None], scores, -jnp.inf), -1)
            return jnp.einsum("nqk,knd->qnd", cast(probs), cast(v))

        return jax.lax.map(rows_of, jnp.arange(s // block)).reshape(
            s, g, m.v_dim)

    out = jax.lax.map(group, (heads_of(layer["wuq"], m.nope + m.rope),
                              heads_of(layer["wuk"], m.nope),
                              heads_of(layer["wuv"], m.v_dim)))
    return out.transpose(1, 0, 2, 3).reshape(s, nh * m.v_dim)


def route(score, m: Shape):
    """score [s, E] (the softmax) -> (sel [s, top_k], w [s, top_k],
    margin [s])."""
    s, E = score.shape
    per = E // m.n_group
    best = jnp.max(score.reshape(s, m.n_group, per), axis=-1)
    gtop, groups = jax.lax.top_k(best, m.topk_group + 1)
    kept = jnp.any(jnp.arange(m.n_group)[None, :, None]
                   == groups[:, None, :m.topk_group], axis=-1)
    limited = jnp.where(jnp.repeat(kept, per, axis=1), score, 0.0)
    top, sel = jax.lax.top_k(limited, m.top_k + 1)
    margin = _margins(limited, top, best, gtop, m)
    return sel[:, :m.top_k], top[:, :m.top_k] * m.route_scale, margin


def _margins(limited, top, best, gtop, m: Shape):
    """By how little the part of the choice that matters HERE is
    decided.  A held expert that is chosen stays chosen while it keeps
    above the first score left out, one left out stays out while it
    keeps below the last chosen.  A group that holds experts held here
    and is left out stays out while it keeps below the last kept
    group; while it is KEPT, its experts compete with those of the
    other kept groups, so any change of the kept set matters: the
    distance is that of the last kept group over the first left out,
    whichever they are.  The least of those distances."""
    lo, hi = m.held
    per = m.router_width // m.n_group
    held = limited[:, lo:hi]
    kth, nxt = top[:, -2:-1], top[:, -1:]
    expert = jnp.min(jnp.where(held >= kth, held - nxt, kth - held), -1)
    g_lo, g_hi = lo // per, -(-hi // per)
    mine = best[:, g_lo:g_hi]
    gk, gn = gtop[:, -2:-1], gtop[:, -1:]
    group = jnp.min(jnp.where(mine >= gk, gk - gn, gk - mine), -1)
    return jnp.minimum(expert, group)


def hidden_states(params, tokens, m: Shape, cast=lowp.exact):
    """tokens [s] -> (final-norm hidden states [s, d], the least
    routing margin at each position [s]), float32."""
    s = tokens.shape[0]
    mm = lambda a, w: jnp.einsum("...i,io->...o", cast(a), cast(_f32(w)))
    swiglu = lambda u, p: mm(jax.nn.silu(mm(u, p["wg"])) * mm(u, p["wu"]),
                             p["wd"])
    lo, hi = m.held
    x = _f32(params["embed"])[tokens]
    margin = jnp.full((s,), jnp.inf, jnp.float32)
    for layer in params["layers"]:
        u = _rms(x, layer["g1"], m.eps)
        cq = _rms(mm(u, layer["wdq"]), layer["gq"], m.eps)
        ckv = mm(u, layer["wdkv"])
        c = _rms(ckv[:, :m.kv_rank], layer["gkv"], m.eps)
        k_pe = _rope(ckv[:, m.kv_rank:], m)
        h = x + mm(_attention(cq, c, k_pe, layer, m, cast), layer["wo"])
        u = _rms(h, layer["g2"], m.eps)
        if "mlp" in layer:
            y = swiglu(u, layer["mlp"])
        else:
            moe = layer["moe"]
            score = jax.nn.softmax(mm(u, moe["router"]), axis=-1)
            sel, w, mg = route(score, m)
            margin = jnp.minimum(margin, mg)

            def one_expert(y, ep, w=w, sel=sel, u=u):
                e, p = ep
                w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
                return y + w_e[:, None] * swiglu(u, p), None

            y, _ = jax.lax.scan(one_expert, swiglu(u, moe["shared"]),
                                (jnp.arange(lo, hi), moe["experts"]))
        x = h + y
    return _rms(x, params["norm_f"], m.eps), margin


@functools.partial(jax.jit, static_argnames=("shape", "cast_name"))
def served_gaps(params, tokens, first, count, served, *, shape: Shape,
                cast_name: str = "exact"):
    """As ``afmoe_serve.served_gaps``: for one request padded to a
    fixed length, ``(best, chosen, lowp_first, margin)`` [n_max] each:
    the reference's best logit at each served position, its logit of
    the served token, (``cast_name`` other than ``exact``) its logit of
    the token that the lower precision puts first there, and the least
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

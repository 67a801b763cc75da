"""Plain reference of the served ``nemotron_h`` decoder with a latent
expert layer (NVIDIA Nemotron 3 Super): one full forward pass over a
whole sequence, float32 at ``highest`` precision, the state-space
RECURRENCE TOKEN BY TOKEN (never the chunked form), the expert layer
DENSE over the held experts and masked by the routing, attention dense
and causal, no cache, no slots, no pages, no batching, no kernels,
nothing of the program imported.

Sizes are the configuration's: ``d`` hidden, ``H`` state-space heads of
``P`` channels in ``G`` groups, ``N`` the state size, ``K`` convolution
taps, ``Hq`` query and ``Hk`` K/V heads of ``dh``, ``l`` the experts'
latent, ``E`` the router's width, of which the experts ``[lo, hi)`` are
held here.

* ``RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g``.  ``x0 =
  embed[tokens]``; no position enters anywhere.
* Layer ``l``: ``x' = x + Mixer_l(RMS(x; norm))``, ONE mixer a layer,
  named by ``hybrid_override_pattern`` (``M``, ``E``, ``*``).  Final
  ``RMS(x; norm_f)``; ``logits = x head`` (untied).
* Mamba-2 mixer (``M``) on ``u_t``: ``[z_t (H P), xBC_t (H P + 2 G N),
  dt_t (H)] = u_t win``; ``c_t = silu(sum_{j < K} conv_w[j] * xBC_{t - K
  + 1 + j} + conv_b)`` with ``xBC`` before the first token 0; ``[x_t (H
  x P), B_t (G x N), C_t (G x N)] = c_t``; head ``h`` reads group ``g(h)
  = h // (H / G)``; ``delta_t = softplus(dt_t + dt_bias)``; ``A =
  -exp(a_log)``; ``S_t^h = exp(delta_t^h A_h) S_{t-1}^h + delta_t^h
  x_t^h (outer) B_{t, g(h)}`` (``[P, N]``, ``S_{-1} = 0``); ``y_t^h =
  S_t^h C_{t, g(h)} + d_skip_h x_t^h``; ``q_t = y_t * silu(z_t)``;
  ``RMS`` of ``q_t`` within each group's ``H P / G`` channels, times
  ``gn``; times ``wout``.
* Attention mixer (``*``): ``q = u wq`` as ``Hq`` heads of ``dh``, ``k =
  u wk``, ``v = u wv`` as ``Hk`` heads; query head ``n`` reads K/V head
  ``n // (Hq / Hk)``; scores ``q . k / sqrt(dh)``; causal softmax;
  ``concat(o_n) wo``.  No rotary, no bias.
* Expert mixer (``E``) on ``u``: ``s = sigmoid(u router)`` (``E`` wide,
  at full width); ``sel = top_k(s + expert_bias)``, the bias used for
  the choice only; ``w = s[sel] / (sum(s[sel]) + 1e-20) *
  routed_scaling_factor``; ``lat = u latent_down``; ``MoE(u) = (sum_{i:
  lo <= sel_i < hi} w_i relu(lat wu_i)^2 wd_i) latent_up + relu(u
  shared_wu)^2 shared_wd``.

Departures and readings of the published code are the configuration
file's ``assumed`` list.

Float32 copies of every weight would not fit beside the bfloat16 ones:
the layers are walked one at a time, each in its own jitted call, the
experts one at a time inside it (a scan over the stacked weights, each
cast as it is used), and attention in blocks of queries.

What is compared is what ``lfm2_moe_serve`` compares: per served
position, the reference's best logit, its logit of the served token,
the logit of the token a CONTROL puts first, and the least routing
margin over the expert layers.  Two controls: ``fp8`` (both operands of
every matrix product through float8, ``lowp``) and ``state_bf16``
(everything exact, but the recurrent state rounded to bfloat16 after
every token: what a pool that kept it in the activations' type would
do).  As for Granite, the first layer's recurrent state itself is
compared too (:func:`first_layer_state`, :func:`state_gap`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import lowp

Q_BLOCK = 256
#: the selection bias, on the scale of a sigmoid score (as the other
#: expert references'): it changes the choice for some of the tokens and
#: leaves the experts' popularity even whatever the seed
BIAS_STD = 0.001
MIXERS = {"M": "mamba", "E": "moe", "*": "attention"}


class Shape(NamedTuple):
    """The sizes that decide the computation (hashable: a static
    argument of the jitted layers)."""

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
    router_width: int
    held: Tuple[int, int]
    top_k: int
    route_scale: float
    vocab: int
    eps: float

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state


def model_shape(model: dict) -> Shape:
    """The configuration file's published keys (its ``model`` group
    merged in: ``router_width``, the published ``n_routed_experts``
    where absent, and ``experts_held`` ``[lo, hi)``, all of them where
    absent) -> :class:`Shape`.  Refuses what this reference does not
    implement."""
    pattern = model["hybrid_override_pattern"]
    if set(pattern) - set(MIXERS):
        raise ValueError(f"layers {sorted(set(pattern))}: only M, E and * "
                         "are implemented")
    if len(pattern) != int(model["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern does not list "
                         "num_hidden_layers")
    if model["mlp_hidden_act"] != "relu2" or not model["norm_topk_prob"] \
            or int(model["n_group"]) != 1 \
            or model["tie_word_embeddings"] or not model["use_conv_bias"]:
        raise ValueError("relu2 experts, a renormalised router with no "
                         "group limit, an untied head and a convolution "
                         "bias are implemented")
    # the router's width where the file gives the experts held under
    # the published key
    experts = int(model.get("router_width", model["n_routed_experts"]))
    lo, hi = model.get("experts_held", (0, experts))
    shape = Shape(
        layer_types=tuple(MIXERS[c] for c in pattern),
        hidden=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        ssm_heads=int(model["mamba_num_heads"]),
        ssm_head_dim=int(model["mamba_head_dim"]),
        groups=int(model["n_groups"]), state=int(model["ssm_state_size"]),
        taps=int(model["conv_kernel"]),
        latent=int(model["moe_latent_size"]),
        expert_ffn=int(model["moe_intermediate_size"]),
        shared_ffn=int(model["moe_shared_expert_intermediate_size"]),
        router_width=experts, held=(int(lo), int(hi)),
        top_k=int(model["num_experts_per_tok"]),
        route_scale=float(model["routed_scaling_factor"]),
        vocab=int(model["vocab_size"]),
        eps=float(model["layer_norm_epsilon"]))
    if shape.d_inner != int(model["expand"]) * shape.hidden:
        raise ValueError("mamba_num_heads * mamba_head_dim is not "
                         "expand * hidden_size")
    return shape


def param_layout(model: dict) -> dict:
    """``{path: (shape, kind, std)}`` as ``weights.make`` reads it:
    ``matrix`` leaves normal with std 1/sqrt(fan_in) (``[in, out]``),
    the mixers' output projections (``wout``, ``wo``, ``latent_up``, the
    shared expert's ``wd``) with that over sqrt(num_hidden_layers) more,
    so that each layer adds about 1/L of the residual stream's variance
    and it stays O(1) through the layers under ``routed_scaling_factor``
    and the squared ReLU; ``gain`` 1 + normal * 0.02; ``bias`` normal
    times its std; the embedding's entries unit normal.  The held
    experts' matrices are stacked on a leading axis.  The recurrence's
    scalars ``dt_bias`` and ``a_log`` are ``gain`` leaves here and take
    their values in :func:`finish`."""
    m = model_shape(model)
    d, hd, nh, di, ch = (m.hidden, m.head_dim, m.ssm_heads, m.d_inner,
                         m.conv_dim)
    out = 1.0 / math.sqrt(len(m.layer_types))
    mat = lambda *s, scale=1.0: (s, "matrix", scale / math.sqrt(s[-2]))
    gain = lambda n: ((n,), "gain", 0.02)
    n_held = m.held[1] - m.held[0]
    layers = []
    for kind in m.layer_types:
        layer = {"norm": gain(d)}
        if kind == "mamba":
            layer.update(
                win=mat(d, di + ch + nh), conv_w=mat(m.taps, ch),
                conv_b=((ch,), "bias", 0.02), dt_bias=gain(nh),
                a_log=gain(nh), d_skip=gain(nh), gn=gain(di),
                wout=mat(di, d, scale=out))
        elif kind == "attention":
            layer.update(wq=mat(d, m.heads * hd), wk=mat(d, m.kv_heads * hd),
                         wv=mat(d, m.kv_heads * hd),
                         wo=mat(m.heads * hd, d, scale=out))
        else:
            layer["moe"] = {
                "router": mat(d, m.router_width),
                "expert_bias": ((m.router_width,), "bias", BIAS_STD),
                "latent_down": mat(d, m.latent),
                "latent_up": mat(m.latent, d, scale=out),
                "experts": {"wu": mat(n_held, m.latent, m.expert_ffn),
                            "wd": mat(n_held, m.expert_ffn, m.latent)},
                "shared": {"wu": mat(d, m.shared_ffn),
                           "wd": mat(m.shared_ffn, d, scale=out)}}
        layers.append(layer)
    return {"embed": ((m.vocab, d), "matrix", 1.0),
            "head": mat(d, m.vocab), "norm_f": gain(d), "layers": layers}


def finish(params: dict) -> dict:
    """The seeded tree with the recurrence's scalars as Mamba-2
    initialises them (``granite_hybrid_serve.finish``): ``A =
    exp(a_log)`` spread over [1, 16] and the step ``softplus(dt_bias)``
    log-spread over [0.001, 0.1], head by head, each times the seeded
    factor (1 + 0.02 normal) the leaf came with."""
    out = dict(params)
    out["layers"] = []
    for layer in params["layers"]:
        if "a_log" in layer:
            n = layer["a_log"].shape[0]
            dt = layer["a_log"].dtype
            f32 = lambda a: a.astype(jnp.float32)
            step = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), n))
            layer = dict(
                layer,
                a_log=(jnp.log(jnp.linspace(1.0, 16.0, n))
                       * f32(layer["a_log"])).astype(dt),
                dt_bias=(jnp.log(jnp.expm1(step))
                         * f32(layer["dt_bias"])).astype(dt))
        out["layers"].append(layer)
    return out


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(g)


def _bf16_state(s):
    """float32 rounded to bfloat16's 8 bits of significand (not a cast
    there and back, which XLA may fold away on the TPU)."""
    return jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)


def _mamba(u, layer, m: Shape, mm, round_state, count=None):
    """The Mamba-2 mixer over a whole sequence ``u [s, d]``, the
    recurrence token by token.  Returns (the mixer's output, the state
    ``[H, P, N]`` after the last token, or after the first ``count``)."""
    s = u.shape[0]
    di, n, h, p, g = (m.d_inner, m.state, m.ssm_heads, m.ssm_head_dim,
                      m.groups)
    zxbcdt = mm(u, layer["win"])
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:di + m.conv_dim]
    dt = zxbcdt[:, di + m.conv_dim:]
    padded = jnp.concatenate(
        [jnp.zeros((m.taps - 1, m.conv_dim), jnp.float32), xbc])
    w = _f32(layer["conv_w"])
    conv = jax.nn.silu(_f32(layer["conv_b"]) + sum(
        w[j] * padded[j:j + s] for j in range(m.taps)))
    x = conv[:, :di].reshape(s, h, p)
    # each head's group's B and C
    per_head = lambda a: jnp.repeat(a.reshape(s, g, n), h // g, axis=1)
    b = per_head(conv[:, di:di + g * n])                       # [s, h, n]
    c = per_head(conv[:, di + g * n:])
    delta = jax.nn.softplus(dt + _f32(layer["dt_bias"]))      # [s, h]
    decay = jnp.exp(-jnp.exp(_f32(layer["a_log"])) * delta)
    live = jnp.arange(s) < (s if count is None else count)

    def token(state, t):
        x_t, b_t, c_t, delta_t, decay_t, live_t = t
        new = (decay_t[:, None, None] * state
               + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        state = jnp.where(live_t, round_state(new), state)
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    state, y = jax.lax.scan(token, jnp.zeros((h, p, n), jnp.float32),
                            (x, b, c, delta, decay, live))
    y = y + _f32(layer["d_skip"])[:, None] * x
    q = (y.reshape(s, di) * jax.nn.silu(z)).reshape(s, g, di // g)
    q = _rms(q, layer["gn"].reshape(g, di // g), m.eps).reshape(s, di)
    return mm(q, layer["wout"]), state


def _attention(u, layer, m: Shape, mm, cast):
    s = u.shape[0]
    hd, group = m.head_dim, m.heads // m.kv_heads
    q = mm(u, layer["wq"]).reshape(s, m.heads, hd)
    k = jnp.repeat(mm(u, layer["wk"]).reshape(s, m.kv_heads, hd), group, 1)
    v = jnp.repeat(mm(u, layer["wv"]).reshape(s, m.kv_heads, hd), group, 1)
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
    the first one left out."""
    return jnp.min(jnp.where(held >= kth, held - nxt, kth - held), axis=-1)


def _experts(u, moe, m: Shape, mm):
    """The expert mixer: (its output, the least routing margin [s])."""
    lo, hi = m.held
    relu2 = lambda a, p: mm(jnp.square(jax.nn.relu(mm(a, p["wu"]))),
                            p["wd"])
    score = jax.nn.sigmoid(mm(u, moe["router"]))
    biased = score + _f32(moe["expert_bias"])
    top, sel = jax.lax.top_k(biased, m.top_k + 1)
    margin = _held_margin(biased[:, lo:hi], top[:, -2:-1], top[:, -1:])
    sel = sel[:, :m.top_k]
    w = jnp.take_along_axis(score, sel, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * m.route_scale
    lat = mm(u, moe["latent_down"])

    def one_expert(y, ep):
        e, p = ep
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        return y + w_e[:, None] * relu2(lat, p), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(lat),
                        (jnp.arange(lo, hi), moe["experts"]))
    return mm(y, moe["latent_up"]) + relu2(u, moe["shared"]), margin


def _casts(cast_name: str):
    """(the matrix products' operand cast, the state's rounding)."""
    cast = lowp.CASTS["fp8" if cast_name == "fp8" else "exact"]
    round_state = _bf16_state if cast_name == "state_bf16" else lowp.exact
    return cast, round_state


@functools.partial(jax.jit, static_argnames=("li", "m", "cast_name"))
def _layer(x, margin, layer, li: int, m: Shape, cast_name: str):
    """One layer, its weights cast to float32 in here."""
    cast, round_state = _casts(cast_name)
    mm = lambda a, w: jnp.einsum("...i,io->...o", cast(a), cast(_f32(w)))
    with jax.default_matmul_precision("highest"):
        u = _rms(x, layer["norm"], m.eps)
        kind = m.layer_types[li]
        if kind == "mamba":
            mixed, _ = _mamba(u, layer, m, mm, round_state)
        elif kind == "attention":
            mixed = _attention(u, layer, m, mm, cast)
        else:
            mixed, layer_margin = _experts(u, layer["moe"], m, mm)
            margin = jnp.minimum(margin, layer_margin)
        return x + mixed, margin


def hidden_states(params, tokens, m: Shape, cast_name: str = "exact"):
    """tokens [s] -> (final-norm hidden states [s, d], the least
    routing margin at each position [s]), float32; the layers walked
    one at a time."""
    x = _f32(params["embed"])[tokens]
    margin = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    for li, layer in enumerate(params["layers"]):
        x, margin = _layer(x, margin, layer, li, m, cast_name)
    return _rms(x, params["norm_f"], m.eps), margin


@functools.partial(jax.jit, static_argnames=("m", "cast_name"))
def _first_state(x, layer, count, m: Shape, cast_name: str):
    cast, round_state = _casts(cast_name)
    mm = lambda a, w: jnp.einsum("...i,io->...o", cast(a), cast(_f32(w)))
    with jax.default_matmul_precision("highest"):
        return _mamba(_rms(x, layer["norm"], m.eps), layer, m, mm,
                      round_state, count)[1]


def first_layer_state(params, tokens, count, m: Shape,
                      cast_name: str = "exact"):
    """The recurrent state ``[H, P, N]`` of the FIRST layer (a Mamba-2
    layer) after the first ``count`` of ``tokens`` [s]: what a
    request's slot holds of that layer once it has taken that many
    tokens (``granite_hybrid_serve.first_layer_state``)."""
    if m.layer_types[0] != "mamba":
        raise ValueError("the first layer keeps no recurrent state")
    return _first_state(_f32(params["embed"])[tokens], params["layers"][0],
                        count, m, cast_name)


def state_gap(got, want) -> float:
    """How far a first-layer state ``got`` lies from ``want`` (both ``[H,
    P, N]``): the relative L2 distance of the head it is worst in."""
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    err = jnp.sqrt(jnp.sum((got - want) ** 2, axis=(1, 2)))
    return float(jnp.max(err / jnp.sqrt(jnp.sum(want ** 2, axis=(1, 2)))))


@functools.partial(jax.jit, static_argnames=("cast_name",))
def _gaps(head, hid, hid_low, margin, first, count, served, cast_name: str):
    n_max = served.shape[0]
    with jax.default_matmul_precision("highest"):
        head = _f32(head)
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
    """As ``lfm2_moe_serve.served_gaps``: for one request padded to a
    fixed length, ``(best, chosen, control_first, margin)`` [n_max]
    each: the reference's best logit at each served position, its logit
    of the served token, (``cast_name`` ``fp8`` or ``state_bf16``) its
    logit of the token the control puts first there, and the least
    routing margin at the position.  Rows past ``count`` are zeroed
    (their margin is infinite)."""
    hid, margin = hidden_states(params, tokens, shape)
    hid_low = hid if cast_name == "exact" else hidden_states(
        params, tokens, shape, cast_name)[0]
    return _gaps(params["head"], hid, hid_low, margin, first, count,
                 served, cast_name)


def logits_all(params, tokens, shape: Shape):
    """Every position's logits [s, vocab]: what the tests compare the
    program's prefill, decode and chunked prefill with."""
    with jax.default_matmul_precision("highest"):
        hid, _ = hidden_states(params, tokens, shape)
        return hid @ _f32(params["head"])


def expert_layer(params_moe, u, shape: Shape):
    """One expert mixer alone on ``u`` [s, d] (float32): what the share
    test adds the shares of up to."""
    mm = lambda a, w: jnp.einsum("...i,io->...o", a, _f32(w))
    with jax.default_matmul_precision("highest"):
        return _experts(_f32(u), params_moe, shape, mm)[0]

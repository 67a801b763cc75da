"""Plain reference of the served ``granitemoehybrid`` decoder without
experts (ibm-granite Granite 4.0-H): one full forward pass over a whole
sequence, float32 at ``highest`` precision, the state-space RECURRENCE
TOKEN BY TOKEN (never the chunked form), no cache, no slots, no pages,
no batching, no kernels, nothing of the program imported.

Sizes are the configuration's: ``d`` hidden, ``H`` state-space heads of
``P`` channels, ``N`` the state size, ``K`` convolution taps.

* ``RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g``.  ``x0 =
  embedding_multiplier * embed[tokens]``; no position enters anywhere.
* Block ``l``: ``h = x + r Mixer_l(RMS(x; g1))``, ``x' = h + r
  MLP(RMS(h; g2))``, ``r = residual_multiplier``.  Final ``RMS(x;
  norm_f)``; ``logits = (x embed^T) / logits_scaling``.
* ``MLP(u) = (silu(u wg) * (u wu)) wd`` (``wg | wu`` are the halves of
  the published ``input_linear``).
* Attention mixer (``layer_types[l] == "attention"``): ``q = u wq`` as
  ``Hq`` heads of ``dh``, ``k = u wk``, ``v = u wv`` as ``Hk`` heads;
  query head ``n`` reads K/V head ``n // (Hq / Hk)``; scores ``q . k *
  attention_multiplier``; causal softmax; ``concat(o_n) wo``.  No
  rotary, no bias.
* Mamba-2 mixer (``"mamba"``) on ``u_t``: ``[z_t (H P), xBC_t (H P + 2
  N), dt_t (H)] = u_t win``; ``c_t = silu(sum_{j < K} conv_w[j] *
  xBC_{t - K + 1 + j} + conv_b)`` with ``xBC`` before the first token
  0; ``[x_t (H x P), B_t (N), C_t (N)] = c_t``; ``delta_t = softplus(
  dt_t + dt_bias)``; ``A = -exp(a_log)``; ``S_t^h = exp(delta_t^h A_h)
  S_{t-1}^h + delta_t^h x_t^h (outer) B_t`` (``[P, N]``, ``S_{-1} =
  0``); ``y_t^h = S_t^h C_t + d_skip_h x_t^h``; ``g_t = y_t *
  silu(z_t)``; ``RMS(g_t; gn)`` over all ``H P``; times ``wout``.

Departures and readings of the published code are the configuration
file's ``assumed`` list.

Float32 copies of every weight (12.8 GB at the published sizes) would
not fit beside the bfloat16 ones: the layers are walked one at a time,
each cast inside its own jitted call.

What is compared is what ``gpt_serve`` compares: per served position,
the reference's best logit, its logit of the served token and its logit
of the token a CONTROL puts first.  Two controls: ``fp8`` (both
operands of every matrix product through float8, ``lowp``) and
``state_bf16`` (everything exact, but the recurrent state rounded to
bfloat16 after every token: what a pool that kept it in the
activations' type would do).  The second moves the logits less than
bfloat16 activations do, so a second thing is compared that it does
move: the first layer's recurrent state itself, as a request's slot
holds it (:func:`first_layer_state`, :func:`state_gap`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import lowp


class Shape(NamedTuple):
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
    eps: float
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.state


def model_shape(model: dict) -> Shape:
    """The configuration file's published keys -> :class:`Shape`."""
    if int(model["mamba_n_groups"]) != 1 or int(model["num_local_experts"]):
        raise ValueError("one B/C group and no experts are implemented")
    if not model["tie_word_embeddings"] or \
            model["position_embedding_type"] != "nope":
        raise ValueError("a tied head and no positions are implemented")
    shape = Shape(
        layer_types=tuple(model["layer_types"]),
        hidden=int(model["hidden_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        ffn=int(model["shared_intermediate_size"]),
        ssm_heads=int(model["mamba_n_heads"]),
        ssm_head_dim=int(model["mamba_d_head"]),
        state=int(model["mamba_d_state"]), taps=int(model["mamba_d_conv"]),
        vocab=int(model["vocab_size"]), eps=float(model["rms_norm_eps"]),
        embedding_multiplier=float(model["embedding_multiplier"]),
        residual_multiplier=float(model["residual_multiplier"]),
        attention_multiplier=float(model["attention_multiplier"]),
        logits_scaling=float(model["logits_scaling"]))
    if shape.d_inner != int(model["mamba_expand"]) * shape.hidden:
        raise ValueError("mamba_n_heads * mamba_d_head is not "
                         "mamba_expand * hidden_size")
    return shape


def param_layout(model: dict) -> dict:
    """``{path: (shape, kind, std)}`` as ``weights.make`` reads it:
    ``matrix`` leaves normal with std 1/sqrt(fan_in) (``[in, out]``),
    ``gain`` 1 + normal * 0.02, ``bias`` normal * 0.02.  The embedding
    (and so the head) has rows of norm 1 AFTER the embedding
    multiplier: rows the multiplier made 12 times the usual would, the
    head being the same matrix, make every token predict itself.  The
    recurrence's scalars ``dt_bias`` and ``a_log`` are ``gain`` leaves
    here and take their values in :func:`finish`."""
    m = model_shape(model)
    d, f = m.hidden, m.ffn
    mat = lambda i, o: ((i, o), "matrix", 1.0 / math.sqrt(i))
    gain = lambda n: ((n,), "gain", 0.02)
    layers = []
    for kind in m.layer_types:
        layer = {"g1": gain(d), "g2": gain(d),
                 "mlp": {"wg": mat(d, f), "wu": mat(d, f), "wd": mat(f, d)}}
        if kind == "mamba":
            layer.update(
                win=mat(d, m.d_inner + m.conv_dim + m.ssm_heads),
                conv_w=mat(m.taps, m.conv_dim),
                conv_b=((m.conv_dim,), "bias", 0.02),
                dt_bias=gain(m.ssm_heads), a_log=gain(m.ssm_heads),
                d_skip=gain(m.ssm_heads), gn=gain(m.d_inner),
                wout=mat(m.d_inner, d))
        else:
            kv = m.kv_heads * m.head_dim
            layer.update(wq=mat(d, d), wk=mat(d, kv), wv=mat(d, kv),
                         wo=mat(d, d))
        layers.append(layer)
    return {"embed": ((m.vocab, d), "matrix",
                      1.0 / (m.embedding_multiplier * math.sqrt(d))),
            "norm_f": gain(d), "layers": layers}


def finish(params: dict) -> dict:
    """The seeded tree with the recurrence's scalars as Mamba-2
    initialises them, so that some heads remember over a few tokens and
    some over thousands: ``A = exp(a_log)`` spread over [1, 16] and the
    step ``softplus(dt_bias)`` log-spread over [0.001, 0.1], head by
    head, each times the seeded factor (1 + 0.02 normal) the leaf came
    with."""
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
    """float32 rounded to bfloat16's 8 bits of significand.  Not a cast
    there and back: XLA folds that pair away on the TPU (excess
    precision is allowed by default) and the control would round
    nothing."""
    return jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)


def _mamba(u, layer, m: Shape, mm, round_state, count=None):
    """The Mamba-2 mixer over a whole sequence ``u [s, d]``, the
    recurrence token by token.  Returns (the mixer's output, the state
    ``[H, P, N]`` after the last token, or after the first ``count``)."""
    s = u.shape[0]
    di, n, h, p = m.d_inner, m.state, m.ssm_heads, m.ssm_head_dim
    zxbcdt = mm(u, layer["win"])
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:di + m.conv_dim]
    dt = zxbcdt[:, di + m.conv_dim:]
    padded = jnp.concatenate(
        [jnp.zeros((m.taps - 1, m.conv_dim), jnp.float32), xbc])
    w = _f32(layer["conv_w"])
    conv = _f32(layer["conv_b"]) + sum(
        w[j] * padded[j:j + s] for j in range(m.taps))
    conv = jax.nn.silu(conv)
    x = conv[:, :di].reshape(s, h, p)
    b, c = conv[:, di:di + n], conv[:, di + n:]
    delta = jax.nn.softplus(dt + _f32(layer["dt_bias"]))      # [s, h]
    decay = jnp.exp(-jnp.exp(_f32(layer["a_log"])) * delta)

    live = jnp.arange(s) < (s if count is None else count)

    def token(state, t):
        x_t, b_t, c_t, delta_t, decay_t, live_t = t
        new = (decay_t[:, None, None] * state
               + (delta_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        state = jnp.where(live_t, round_state(new), state)
        return state, jnp.einsum("hpn,n->hp", state, c_t)

    state, y = jax.lax.scan(token, jnp.zeros((h, p, n), jnp.float32),
                            (x, b, c, delta, decay, live))
    y = y + _f32(layer["d_skip"])[:, None] * x
    g = y.reshape(s, di) * jax.nn.silu(z)
    return mm(_rms(g, layer["gn"], m.eps), layer["wout"]), state


def _attention(u, layer, m: Shape, mm, cast):
    s = u.shape[0]
    hd, group = m.head_dim, m.heads // m.kv_heads
    q = mm(u, layer["wq"]).reshape(s, m.kv_heads, group, hd)
    k = mm(u, layer["wk"]).reshape(s, m.kv_heads, hd)
    v = mm(u, layer["wv"]).reshape(s, m.kv_heads, hd)
    scores = jnp.einsum("qngd,knd->ngqk", cast(q), cast(k)) \
        * m.attention_multiplier
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("ngqk,knd->qngd", cast(probs), cast(v))
    return mm(ctx.reshape(s, -1), layer["wo"])


def _casts(cast_name: str):
    """(the matrix products' operand cast, the state's rounding)."""
    cast = lowp.CASTS["fp8" if cast_name == "fp8" else "exact"]
    round_state = _bf16_state if cast_name == "state_bf16" else lowp.exact
    return cast, round_state


@functools.partial(jax.jit, static_argnames=("m", "cast_name"))
def _layer(x, layer, m: Shape, cast_name: str):
    """One block, its weights cast to float32 in here."""
    cast, round_state = _casts(cast_name)
    mm = lambda a, w: jnp.einsum("...i,io->...o", cast(a), cast(_f32(w)))
    with jax.default_matmul_precision("highest"):
        u = _rms(x, layer["g1"], m.eps)
        if "win" in layer:
            mixed, _ = _mamba(u, layer, m, mm, round_state)
        else:
            mixed = _attention(u, layer, m, mm, cast)
        h = x + m.residual_multiplier * mixed
        u = _rms(h, layer["g2"], m.eps)
        mlp = layer["mlp"]
        y = mm(jax.nn.silu(mm(u, mlp["wg"])) * mm(u, mlp["wu"]), mlp["wd"])
        return h + m.residual_multiplier * y


@functools.partial(jax.jit, static_argnames=("m", "cast_name"))
def _first_state(x, layer, count, m: Shape, cast_name: str):
    cast, round_state = _casts(cast_name)
    mm = lambda a, w: jnp.einsum("...i,io->...o", cast(a), cast(_f32(w)))
    with jax.default_matmul_precision("highest"):
        return _mamba(_rms(x, layer["g1"], m.eps), layer, m, mm,
                      round_state, count)[1]


def first_layer_state(params, tokens, count, m: Shape,
                      cast_name: str = "exact"):
    """The recurrent state ``[H, P, N]`` of the FIRST layer (a
    state-space layer) after the first ``count`` of ``tokens`` [s]: what
    a request's slot holds of that layer once it has taken that many
    tokens.  Nothing but the embedding, a norm, a projection and a
    convolution lies between the tokens and this state, so it shows the
    recurrence's own precision: a state rounded every token drifts here
    by percents in the heads that remember longest, where the logits,
    40 layers later, barely move."""
    if m.layer_types[0] != "mamba":
        raise ValueError("the first layer keeps no recurrent state")
    x = m.embedding_multiplier * _f32(params["embed"][tokens])
    return _first_state(x, params["layers"][0], count, m, cast_name)


def state_gap(got, want) -> float:
    """How far a first-layer state ``got`` lies from ``want`` (both ``[H,
    P, N]``): the relative L2 distance of the head it is worst in."""
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    err = jnp.sqrt(jnp.sum((got - want) ** 2, axis=(1, 2)))
    return float(jnp.max(err / jnp.sqrt(jnp.sum(want ** 2, axis=(1, 2)))))


def hidden_states(params, tokens, m: Shape, cast_name: str = "exact"):
    """tokens [s] -> final-norm hidden states [s, d], float32; the
    layers walked one at a time."""
    x = m.embedding_multiplier * _f32(params["embed"][tokens])
    for layer in params["layers"]:
        x = _layer(x, layer, m, cast_name)
    return _rms(x, params["norm_f"], m.eps)


@functools.partial(jax.jit, static_argnames=("m", "cast_name"))
def _gaps(embed, hid, hid_low, first, count, served, m: Shape,
          cast_name: str):
    n_max = served.shape[0]
    with jax.default_matmul_precision("highest"):
        head = _f32(embed).T
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, first, n_max, 0)
        logits = take(hid) @ head / m.logits_scaling
        best = jnp.max(logits, axis=-1)
        chosen = jnp.take_along_axis(logits, served[:, None], 1)[:, 0]
        low = chosen
        if cast_name != "exact":
            cast = lowp.CASTS["fp8" if cast_name == "fp8" else "exact"]
            pick = jnp.argmax(cast(take(hid_low)) @ cast(head), axis=-1)
            low = jnp.take_along_axis(logits, pick[:, None], 1)[:, 0]
    real = jnp.arange(n_max) < count
    z = lambda a: jnp.where(real, a, 0.0)
    return z(best), z(chosen), z(low)


def served_gaps(params, tokens, first, count, served, *, shape: Shape,
                cast_name: str = "exact"):
    """As ``gpt_serve.served_gaps``: for one request padded to a fixed
    length, ``(best, chosen, control_first)`` [n_max] each: the
    reference's best logit at each served position, its logit of the
    served token, and (``cast_name`` ``fp8`` or ``state_bf16``) its
    logit of the token the control puts first there.  Rows past
    ``count`` are zeroed."""
    hid = hidden_states(params, tokens, shape)
    hid_low = (hid if cast_name == "exact" else
               hidden_states(params, tokens, shape, cast_name))
    return _gaps(params["embed"], hid, hid_low, first, count, served,
                 shape, cast_name)


def logits_all(params, tokens, shape: Shape):
    """Every position's logits [s, vocab]: what the tests compare the
    program's prefill, decode and chunked prefill with."""
    with jax.default_matmul_precision("highest"):
        hid = hidden_states(params, tokens, shape)
        return hid @ _f32(params["embed"]).T / shape.logits_scaling

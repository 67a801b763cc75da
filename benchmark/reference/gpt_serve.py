"""Plain reference of the served pre-LN GPT decoder: one full forward
pass over a whole sequence, float32 at ``highest`` precision, no cache,
no pages, no batching.

Per layer: LayerNorm -> x @ wqkv ([h, 3h], columns q|k|v, each split
into heads of d) -> causal softmax attention scaled by 1/sqrt(d) ->
@ wo -> residual -> LayerNorm -> @ w1 -> GELU (tanh form) -> @ w2 ->
residual; no biases on the projections; final LayerNorm; logits through
the word embedding transposed.  Positions are learned, 0-based.

What is compared is the served (greedy) token's logit against the
reference's best at every served position, so the function returns,
per served position, the reference's best logit, the logit of the
served token, and the token a lower precision puts first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import lowp


def param_layout(model: dict) -> dict:
    """``{path: (shape, kind, std)}``: ``matrix`` leaves are normal
    with std 1/sqrt(fan_in), ``bias`` normal with std 0.02, ``gain``
    1 + normal * 0.02.  Layer leaves carry a leading ``num_layers``
    axis.  Matrices are ``[in, out]``."""
    h = model["hidden_size"]
    f = model.get("ffn_hidden_size") or 4 * h
    L = model["num_layers"]
    ln = lambda *lead: {"g": ((*lead, h), "gain", 0.02),
                        "b": ((*lead, h), "bias", 0.02)}
    mat = lambda i, o: ((L, i, o), "matrix", 1.0 / math.sqrt(i))
    return {"embed": ((model["vocab_size"], h), "matrix", 1 / math.sqrt(h)),
            "pos": ((model["max_position_embeddings"], h), "matrix",
                    1 / math.sqrt(h)),
            "ln_f": ln(),
            "layers": {"ln1": ln(L), "wqkv": mat(h, 3 * h), "wo": mat(h, h),
                       "ln2": ln(L), "w1": mat(h, f), "w2": mat(f, h)}}


def _ln(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p["g"].astype(jnp.float32)
            + p["b"].astype(jnp.float32))


def hidden_states(params, tokens, *, heads: int, cast=lowp.exact):
    """tokens [s] -> final-LayerNorm hidden states [s, h], float32."""
    s = tokens.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    mm = lambda a, w: jnp.einsum("...i,io->...o", cast(a), cast(f32(w)))
    x = f32(params["embed"])[tokens] + f32(params["pos"])[:s]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, layer):
        q, k, v = jnp.split(mm(_ln(x, layer["ln1"]), layer["wqkv"]), 3, -1)
        d = q.shape[-1] // heads
        q, k, v = (t.reshape(s, heads, d) for t in (q, k, v))
        scores = jnp.einsum("qnd,knd->nqk", cast(q), cast(k)) / math.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("nqk,knd->qnd", cast(probs), cast(v))
        x = x + mm(ctx.reshape(s, -1), layer["wo"])
        y = jax.nn.gelu(mm(_ln(x, layer["ln2"]), layer["w1"]),
                        approximate=True)
        return x + mm(y, layer["w2"]), None

    x, _ = jax.lax.scan(block, x, params["layers"])
    return _ln(x, params["ln_f"])


@functools.partial(jax.jit, static_argnames=("heads", "cast_name"))
def served_gaps(params, tokens, first, count, served, *, heads: int,
                cast_name: str = "exact"):
    """For one request padded to a fixed length: ``tokens`` [s] is the
    prompt followed by the served tokens (then padding), ``first`` the
    index of the position that predicts the first served token,
    ``served`` [n_max] the served tokens, of which ``count`` are real.

    Returns ``(best, chosen, lowp_first)`` [n_max] each: the
    reference's best logit at each served position, the reference's
    logit of the served token, and (``cast_name`` other than
    ``exact``) the reference's logit of the token that the lower
    precision puts first there.  Rows past ``count`` are zeroed."""
    n_max = served.shape[0]
    with jax.default_matmul_precision("highest"):
        emb = params["embed"].astype(jnp.float32)
        hid = hidden_states(params, tokens, heads=heads)
        rows = jax.lax.dynamic_slice_in_dim(hid, first, n_max, axis=0)
        logits = rows @ emb.T
        best = jnp.max(logits, axis=-1)
        chosen = jnp.take_along_axis(logits, served[:, None], 1)[:, 0]
        low = chosen
        if cast_name != "exact":
            cast = lowp.CASTS[cast_name]
            hid_l = hidden_states(params, tokens, heads=heads, cast=cast)
            rows_l = jax.lax.dynamic_slice_in_dim(hid_l, first, n_max, 0)
            pick = jnp.argmax(cast(rows_l) @ cast(emb).T, axis=-1)
            low = jnp.take_along_axis(logits, pick[:, None], 1)[:, 0]
    real = jnp.arange(n_max) < count
    z = lambda a: jnp.where(real, a, 0.0)
    return z(best), z(chosen), z(low)

"""Plain reference of the pre-LN GPT trainer: forward, loss, gradients
and Adam, float32 at ``highest`` precision.

It follows Brown et al. 2020 / Megatron-LM's GPT: word plus learned
position embeddings; per layer LayerNorm -> fused QKV projection (per
head the 3*d outputs are laid out q|k|v) -> causal softmax attention
scaled by 1/sqrt(d) -> output projection -> residual -> LayerNorm ->
h->ffn -> GELU (tanh form) -> ffn->h -> residual; final LayerNorm; the
LM head is the word embedding transposed; the loss is the mean
cross-entropy over every position of the batch.  The optimizer is Adam
with bias correction (AdamW with the configuration's weight decay).

Departures, all stated by the configuration and none a precision of
the arithmetic: values are rounded to the storage type the
configuration's ``state_dtypes`` name at the points where the plan
stores them (parameters, the gradient handed to the optimizer, the
first moment), since a bfloat16 parameter that moves by about one ulp a
step does not move as a float32 one does.  All arithmetic between those
points is float32.

Memory: parameters are kept in their storage type and widened layer by
layer inside the scan; every layer is rematerialised; attention runs
one sequence at a time and the head in blocks of rows, so three steps
of the full-width 12-layer model fit beside nothing else on one chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import lowp

HEAD_BLOCK = 1024


def param_layout(model: dict) -> dict:
    """``{path: (shape, kind, std)}`` of every parameter; ``kind`` is
    ``matrix`` (normal * std), ``bias`` (normal * std) or ``gain``
    (1 + normal * std).  Layer leaves carry a leading ``num_layers``
    axis.  Weights are ``[out, in]``."""
    h = model["hidden_size"]
    f = model.get("ffn_hidden_size") or 4 * h
    L = model["num_layers"]
    std = model.get("init_method_std", 0.02)
    ln = lambda *lead: {"weight": ((*lead, h), "gain", std),
                        "bias": ((*lead, h), "bias", std)}
    lin = lambda o, i: {"weight": ((L, o, i), "matrix", std),
                        "bias": ((L, o), "bias", std)}
    return {
        "embedding": {"weight": ((model["vocab_size"], h), "matrix", std)},
        "position_embeddings": {"weight": (
            (model["max_position_embeddings"], h), "matrix", std)},
        "transformer": {"layers": {
            "input_layernorm": ln(L),
            "attention": {"qkv": lin(3 * h, h), "proj": lin(h, h)},
            "post_attention_layernorm": ln(L),
            "mlp": {"dense_h_to_4h": lin(f, h),
                    "dense_4h_to_h": lin(h, f)},
        }},
        "final_layernorm": ln(),
    }


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _attention(qkv, heads, cast):
    """qkv [s, 3h] of ONE sequence -> context [s, h]."""
    s, three_h = qkv.shape
    d = three_h // (3 * heads)
    qkv = qkv.reshape(s, heads, 3 * d)
    q, k, v = qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:]
    scores = jnp.einsum("qnd,knd->nqk", cast(q), cast(k)) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("nqk,knd->qnd", cast(probs), cast(v)).reshape(s, -1)


def _layer(x, lp, *, heads, eps, cast):
    """x [b, s, h] float32; lp one layer's parameters."""
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    mm = lambda a, w: jnp.einsum("...i,oi->...o", cast(a), cast(w))
    y = _layer_norm(x, lp["input_layernorm"]["weight"],
                    lp["input_layernorm"]["bias"], eps)
    qkv = mm(y, lp["attention"]["qkv"]["weight"]) \
        + lp["attention"]["qkv"]["bias"]
    ctx = jax.lax.map(
        jax.checkpoint(functools.partial(_attention, heads=heads,
                                         cast=cast)), qkv)
    x = x + mm(ctx, lp["attention"]["proj"]["weight"]) \
        + lp["attention"]["proj"]["bias"]
    y = _layer_norm(x, lp["post_attention_layernorm"]["weight"],
                    lp["post_attention_layernorm"]["bias"], eps)
    y = mm(y, lp["mlp"]["dense_h_to_4h"]["weight"]) \
        + lp["mlp"]["dense_h_to_4h"]["bias"]
    y = jax.nn.gelu(y, approximate=True)
    return x + mm(y, lp["mlp"]["dense_4h_to_h"]["weight"]) \
        + lp["mlp"]["dense_4h_to_h"]["bias"]


def loss_fn(params, tokens, labels, *, model: dict, cast=lowp.exact):
    """Mean cross-entropy of ``tokens`` [b, s] against ``labels``."""
    heads = model["num_attention_heads"]
    eps = model.get("layernorm_epsilon", 1e-5)
    b, s = tokens.shape
    emb = params["embedding"]["weight"].astype(jnp.float32)
    pos = params["position_embeddings"]["weight"].astype(jnp.float32)
    x = emb[tokens] + pos[:s][None]

    body = jax.checkpoint(functools.partial(
        _layer, heads=heads, eps=eps, cast=cast))
    x, _ = jax.lax.scan(lambda c, lp: (body(c, lp), None), x,
                        params["transformer"]["layers"])
    x = _layer_norm(x, params["final_layernorm"]["weight"].astype(jnp.float32),
                    params["final_layernorm"]["bias"].astype(jnp.float32),
                    eps)
    rows = x.reshape(b * s, -1)
    gold = labels.reshape(b * s)
    block = math.gcd(b * s, HEAD_BLOCK)

    @jax.checkpoint
    def block_loss(args):
        r, g = args
        logits = jnp.einsum("rh,vh->rv", cast(r), cast(emb))
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, g[:, None], 1)[:, 0])

    sums = jax.lax.map(block_loss, (rows.reshape(-1, block, rows.shape[-1]),
                                    gold.reshape(-1, block)))
    return jnp.sum(sums) / (b * s)


def make_step(model: dict, optimizer: dict, state_dtypes: dict,
              cast_name: str = "exact"):
    """A jitted ``(params, m, v, count, tokens, labels) ->
    (params, m, v, loss, grad_sq)``: one Adam step.  ``grad_sq`` is
    :func:`column_squares` of the gradient as the optimizer gets it."""
    cast = lowp.CASTS[cast_name]
    lr, (b1, b2) = optimizer["lr"], optimizer["betas"]
    eps, wd = optimizer["eps"], optimizer.get("weight_decay", 0.0)
    p_dt = jnp.dtype(state_dtypes["params"])
    g_dt = jnp.dtype(state_dtypes["grads"])
    m_dt = jnp.dtype(state_dtypes["exp_avg"])

    def step(params, m, v, count, tokens, labels):
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, tokens, labels, model=model, cast=cast)
        count = count + 1
        c1 = 1.0 - b1 ** count
        c2 = 1.0 - b2 ** count

        def leaf(p, g, m_, v_):
            g = g.astype(g_dt).astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            m32 = b1 * m_.astype(jnp.float32) + (1 - b1) * g
            v32 = b2 * v_ + (1 - b2) * g * g
            upd = (m32 / c1) / (jnp.sqrt(v32 / c2) + eps) + wd * p32
            return ((p32 - lr * upd).astype(p_dt), m32.astype(m_dt), v32,
                    column_squares(g))

        out = jax.tree_util.tree_map(leaf, params, grads, m, v)
        pick = lambda i: jax.tree_util.tree_map(
            lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2), loss, pick(3)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def init_state(params, state_dtypes: dict):
    m = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.dtype(state_dtypes["exp_avg"])),
        params)
    v = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return m, v


def follow(params, batches, *, model, optimizer, state_dtypes,
           cast_name="exact"):
    """Drive the reference through ``batches`` ([(tokens, labels)])
    from ``params`` (storage type, consumed).  Returns ``{"losses",
    "grad_sq" (of step 1's gradient), "delta_sq" (of the parameters'
    change after the last step)}``, the last two as ``{path:
    column_squares}``."""
    step = make_step(model, optimizer, state_dtypes, cast_name)
    start = jax.tree_util.tree_map(jnp.copy, params)
    m, v = init_state(params, state_dtypes)
    losses, first_norms = [], None
    for i, (tokens, labels) in enumerate(batches):
        params, m, v, loss, norms = step(
            params, m, v, jnp.float32(i), tokens, labels)
        losses.append(float(loss))
        if first_norms is None:
            first_norms = by_path(norms)
    return {"losses": losses, "grad_sq": first_norms,
            "delta_sq": by_path(change_squares(params, start))}


def column_squares(a):
    """Sum of squares over every axis but the last: one number per
    index of the last axis, so that a part of a leaf (the key third of
    a fused QKV bias) can be told from the rest.  Their sum is the
    leaf's squared norm."""
    a = a.astype(jnp.float32)
    return jnp.sum(jnp.square(a.reshape(-1, a.shape[-1])), axis=0)


@jax.jit
def change_squares(after, before):
    """:func:`column_squares` of ``after - before``, leaf by leaf."""
    return jax.tree_util.tree_map(
        lambda x, y: column_squares(
            x.astype(jnp.float32) - y.astype(jnp.float32)), after, before)


def by_path(tree) -> dict:
    """``{"a/b/c": numpy array}`` of a tree of arrays."""
    import numpy as np

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(x)
            for path, x in leaves}

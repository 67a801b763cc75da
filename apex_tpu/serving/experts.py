"""A dropless top-k expert layer that holds a share of the experts.

Not :class:`apex_tpu.transformer.moe.SwitchMLP` (top-1, a capacity
factor, overflow tokens dropped): the routing of today's large sparse
models, as one chip of an expert-parallel deployment runs it.

* The ROUTER keeps its published width.  Scores are a sigmoid over all
  ``num_experts`` (float32, ``highest`` precision: the choice is
  discrete, so its input is not rounded further than the activations
  already are), the top ``top_k`` are chosen on ``score + bias`` (the
  bias steers the choice only), and the chosen scores are renormalised
  to sum to one and scaled.
* The layer is told which experts it HOLDS, ``[lo, hi)``.  It computes
  their part of the result for the (token, expert) pairs that land on
  them; what the absent experts would add is left out, as it is on one
  chip before the exchange, and no code stands in for the other chips.
* A second router, :func:`route_grouped` (DeepSeek-V2): a softmax, the
  experts in groups of which only the best few may be chosen from, no
  renormalisation.  What follows the choice is the same code.
* NO TOKEN IS DROPPED and every shape is static: the ``T * top_k``
  pairs are sorted so that those of held experts come first, expert by
  expert; three :func:`jax.lax.ragged_dot` calls (gate, up, down) run
  the SwiGLU experts over the sorted rows, group by group; the rows are
  put back in pair order and summed with their weights.  On TPU
  ``ragged_dot`` is a grouped matrix product that visits only the row
  tiles its groups cover, so the work follows the pairs that really
  landed here (an eighth of them when 32 of 256 experts are held) and
  each touched expert's weights are read once; the rows after the last
  group are never computed and are masked.  One path serves a decode
  step of 64 rows and a prefill chunk of 2,048 (PERF.md, PR 29).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def route(u, router, bias, *, top_k: int, route_scale: float,
          eps: float = 1e-20):
    """u ``[T, d]`` -> (experts ``[T, k]`` int32, weights ``[T, k]``
    float32): sigmoid scores over all experts, top-k on ``score +
    bias``, the chosen scores renormalised (``w / (sum w + eps)``: the
    published epsilon, 1e-20 in afmoe, 1e-6 in lfm2_moe) and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps) * route_scale
    return experts.astype(jnp.int32), w


def route_grouped(u, router, *, top_k: int, route_scale: float,
                  n_group: int, topk_group: int):
    """The group-limited greedy router (DeepSeek-V2): u ``[T, d]`` ->
    (experts ``[T, k]`` int32, weights ``[T, k]`` float32).  Softmax
    over all experts in float32; the experts lie in ``n_group`` equal
    groups in order, a group scores as its best expert, the
    ``topk_group`` best groups are kept and the scores outside them set
    to 0; top-k of what is left; the chosen scores times
    ``route_scale``, NOT renormalised, no bias."""
    scores = jax.nn.softmax(jnp.dot(
        u.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    T, E = scores.shape
    best = jnp.max(scores.reshape(T, n_group, E // n_group), axis=-1)
    _, groups = jax.lax.top_k(best, topk_group)
    kept = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], groups].set(True)
    limited = jnp.where(jnp.repeat(kept, E // n_group, axis=1), scores, 0.0)
    w, experts = jax.lax.top_k(limited, top_k)
    return experts.astype(jnp.int32), w * route_scale


def swiglu(u, p):
    """``(silu(u Wg) * (u Wu)) Wd``: the dense MLP and the shared expert."""
    return (jax.nn.silu(u @ p["wg"]) * (u @ p["wu"])) @ p["wd"]


def held_experts(u, experts, weights, p, held: Tuple[int, int],
                 valid=None):
    """The routed part of the layer that the experts ``[lo, hi)`` give.

    ``u`` ``[T, d]``; ``experts``/``weights`` ``[T, k]`` from
    :func:`route`; ``p`` the held experts' stacked weights (``wg``,
    ``wu`` ``[n, d, f]``, ``wd`` ``[n, f, d]``); ``valid`` ``[T]`` bool,
    where given: a token that is padding takes no expert.  Returns (y
    ``[T, d]``, load ``[n]`` int32: the pairs each held expert took)."""
    lo, hi = held
    n = hi - lo
    T, k = experts.shape
    local = experts.reshape(-1) - lo
    here = (local >= 0) & (local < n)
    if valid is not None:
        here &= jnp.repeat(valid.reshape(-1), k)
    # absent experts sort last, as group n
    key = jnp.where(here, local, n)
    order = jnp.argsort(key, stable=True)
    load = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    rows = jnp.take(u, order // k, axis=0)
    with jax.named_scope("moe_experts"):
        h = (jax.nn.silu(jax.lax.ragged_dot(rows, p["wg"], load))
             * jax.lax.ragged_dot(rows, p["wu"], load))
        y = jax.lax.ragged_dot(h, p["wd"], load)
    # rows past the last group were not computed
    y = jnp.where((jnp.arange(T * k) < jnp.sum(load))[:, None], y, 0)
    # back to pair order, weighted, summed over a token's k pairs
    back = jnp.argsort(order)
    y = jnp.take(y, back, axis=0).reshape(T, k, -1)
    w = jnp.where(here.reshape(T, k), weights, 0.0)
    y = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), w)
    return y.astype(u.dtype), load


def expert_layer(u, p, *, held: Tuple[int, int], top_k: int,
                 route_scale: float, valid=None, groups=None,
                 route_eps: float = 1e-20):
    """``sum_i w_i Expert_i(u)`` over the held experts among the chosen,
    plus the shared expert where ``p`` has one.  ``u`` ``[..., d]``;
    ``p`` has ``router`` ``[d, E]``, ``experts``, and ``shared`` where
    the model has a shared expert (afmoe, deepseek_v2; lfm2_moe has
    none), and for the sigmoid router ``expert_bias`` ``[E]`` and its
    renormalisation's ``route_eps``; ``groups`` ``(n_group,
    topk_group)`` chooses :func:`route_grouped` instead; ``valid``
    (bool, ``u``'s lead shape) marks the tokens that are not padding.
    Returns (y like ``u``, load ``[n_held]``)."""
    lead = u.shape[:-1]
    u2 = u.reshape(-1, u.shape[-1])
    with jax.named_scope("moe_router"):
        if groups is None:
            experts, weights = route(u2, p["router"], p["expert_bias"],
                                     top_k=top_k, route_scale=route_scale,
                                     eps=route_eps)
        else:
            experts, weights = route_grouped(
                u2, p["router"], top_k=top_k, route_scale=route_scale,
                n_group=groups[0], topk_group=groups[1])
    y, load = held_experts(u2, experts, weights, p["experts"], held, valid)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            y = y + swiglu(u2, p["shared"])
    return y.reshape(*lead, -1), load

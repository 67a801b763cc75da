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
* NO TOKEN IS DROPPED and every shape is static: the pairs of held
  experts are laid out expert by expert, each expert's group starting
  on a whole row tile (:func:`apex_tpu.ops.grouped.group_rows`: the
  pairs of absent experts and of padding tokens are left out), two
  Pallas kernels run the SwiGLU experts tile by tile
  (:func:`apex_tpu.ops.grouped.grouped_swiglu`: no tile holds two
  experts' rows, each touched expert's weights are read once, the tile
  is the mean pairs an expert takes), and the rows are put back in pair
  order and summed with their weights.  One path serves a decode step
  of 64 rows and a prefill chunk of 2,048.
* The experts are SwiGLU (afmoe, deepseek_v2, lfm2_moe) or squared
  ReLU, ``relu(x Wu)^2 Wd`` (``act="relu2"``, Nemotron-H: not gated,
  :func:`apex_tpu.ops.grouped.grouped_relu2`), and so is the shared
  expert.  A LATENT layer (Nemotron-H's LatentMoE) routes on the
  full-width input but runs its routed experts in a narrower latent:
  ``latent_down`` before the grouped product, the weighted sum taken in
  the latent, ``latent_up`` after it; the shared expert stays at full
  width.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.grouped import (group_rows, grouped_relu2, grouped_swiglu,
                                  row_tile)


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


def relu2(u, p):
    """``relu(u Wu)^2 Wd``: Nemotron-H's shared expert, not gated."""
    return jnp.square(jax.nn.relu(u @ p["wu"])) @ p["wd"]


#: an expert's activation -> (its dense form, its grouped kernels)
ACTS = {"swiglu": (swiglu, lambda rows, te, active, p, tm: grouped_swiglu(
            rows, te, active, p["wg"], p["wu"], p["wd"], tm=tm)),
        "relu2": (relu2, lambda rows, te, active, p, tm: grouped_relu2(
            rows, te, active, p["wu"], p["wd"], tm=tm))}


def held_experts(u, experts, weights, p, held: Tuple[int, int],
                 valid=None, *, tm: int, act: str = "swiglu"):
    """The routed part of the layer that the experts ``[lo, hi)`` give.

    ``u`` ``[T, d]``; ``experts``/``weights`` ``[T, k]`` from
    :func:`route`; ``p`` the held experts' stacked weights (``wg``
    where ``act`` is ``"swiglu"``, ``wu`` ``[n, d, f]``, ``wd`` ``[n,
    f, d]``); ``valid`` ``[T]`` bool,
    where given: a token that is padding takes no expert; ``tm`` the
    row tile (:func:`apex_tpu.ops.grouped.row_tile`).  Returns (y
    ``[T, d]``, load ``[n]`` int32: the pairs each held expert took)."""
    lo, hi = held
    n = hi - lo
    T, k = experts.shape
    if n == 0:
        return jnp.zeros_like(u), jnp.zeros((0,), jnp.int32)
    local = experts.reshape(-1) - lo
    here = (local >= 0) & (local < n)
    if valid is not None:
        here &= jnp.repeat(valid.reshape(-1), k)
    # absent experts are group n: they take no row of the layout
    layout = group_rows(jnp.where(here, local, n), n, tm)
    rows = jnp.take(u, layout.src // k, axis=0, mode="fill", fill_value=0)
    with jax.named_scope("moe_experts"):
        y = ACTS[act][1](rows, layout.tile_expert, layout.active, p, tm)
    # back to pair order (a pair of no held expert reads 0), weighted,
    # summed over a token's k pairs
    y = jnp.take(y, layout.dest, axis=0, mode="fill",
                 fill_value=0).reshape(T, k, -1)
    w = jnp.where(here.reshape(T, k), weights, 0.0)
    y = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), w)
    return y.astype(u.dtype), layout.load


def expert_layer(u, p, *, held: Tuple[int, int], top_k: int,
                 route_scale: float, valid=None, groups=None,
                 route_eps: float = 1e-20, act: str = "swiglu"):
    """``sum_i w_i Expert_i(u)`` over the held experts among the chosen,
    plus the shared expert where ``p`` has one.  ``u`` ``[..., d]``;
    ``p`` has ``router`` ``[d, E]``, ``experts``, and ``shared`` where
    the model has a shared expert (afmoe, deepseek_v2, nemotron_h;
    lfm2_moe has none), ``latent_down`` ``[d, l]`` and ``latent_up``
    ``[l, d]`` where the routed experts work in a latent of width ``l``
    (``sum_i w_i Expert_i(u latent_down)`` then ``latent_up``), and for
    the sigmoid router ``expert_bias`` ``[E]`` and its
    renormalisation's ``route_eps``; ``groups`` ``(n_group,
    topk_group)`` chooses :func:`route_grouped` instead; ``valid``
    (bool, ``u``'s lead shape) marks the tokens that are not padding;
    ``act`` the experts' and the shared expert's activation (``ACTS``).
    The row tile is :func:`expert_tile`'s.  Returns (y like ``u``, load
    ``[n_held]``)."""
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
    latent = "latent_down" in p
    rows = u2
    if latent:
        with jax.named_scope("moe_latent_down"):
            rows = u2 @ p["latent_down"]
    y, load = held_experts(rows, experts, weights, p["experts"], held, valid,
                           tm=expert_tile(u, p, top_k), act=act)
    if latent:
        with jax.named_scope("moe_latent_up"):
            y = y @ p["latent_up"]
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            y = y + ACTS[act][0](u2, p["shared"])
    return y.reshape(*lead, -1), load


def expert_tile(u, p, top_k: int) -> int:
    """The row tile of :func:`expert_layer` over ``u`` ``[..., d]``: the
    mean pairs an expert takes, from the static shape (``T * top_k``
    over the router's ``E``)."""
    return row_tile(u.size // u.shape[-1] * top_k, p["router"].shape[1])

#!/usr/bin/env python3
"""Start the system on the chip, once, through the entry points a user calls.

    python3 chip_smoke.py

One process, one pass, no options.  It needs a TPU: where
``jax.default_backend()`` is anything else it exits 2 before doing any
work, and it has no CPU mode.  With random weights made from a seed it

* checks one forward+backward of packed-QKV attention and five
  ``flash_decode`` calls against the XLA routes at the flagship shapes;
* trains the full-width GPT-1.3B flagship step (``bf16_fit`` ZeRO plan)
  for a few steps on a fixed batch: finite, falling loss, the first
  step's loss the forward's at the same parameters;
* serves a seeded Poisson trace on the 1.3B-geometry ``ServingEngine``,
  then the same prompts again: every request completes, nothing
  compiles after warm-up, the streams repeat;
* prefills one prompt a rung of the engine's ladder of row widths, at
  the rung and at the widest row: the same first token, the same K/V
  to bf16 rounding, every rung on the widest row's kernel route;
* warms the verify, chunk and int8 executables at two layers;
* runs one period of a state-space hybrid (nine Mamba-2 layers, one
  attention layer, published widths): the in-place decode update
  against the chunked scan on the same tokens, a slot's bytes across
  another row's step, a reused slot starting from zero;
* runs the expert layer's grouped product at the three expert cells'
  decode and chunk shapes (published widths) on integer-valued operands,
  bitwise against the three ``ragged_dot`` calls it replaced;
* with four or more devices, repeats train on a ``(2, 2, 1)`` mesh and
  serve at ``tp=4`` and checks that state is spread over the mesh.

Width is never cut.  Depth is: the train legs run ``TRAIN_LAYERS`` of
the model's 24 layers, because XLA needs 24.6 GiB for the 24-layer
``bf16_fit`` step on a 15.75 GiB chip, at batch 1 as at batch 4
(PERF.md, "Where the time goes").  Times and bytes are printed as information;
no utilization is computed.  Any failed check is a non-zero exit.  The
last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import functools
import gc
import json
import sys
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu import _native
from apex_tpu.analysis import hot_path_guard
from apex_tpu.ops import (flash_attention_qkv, flash_attention_qkv_route,
                          flash_attention_route, flash_decode,
                          flash_decode_latent, flash_decode_latent_route,
                          flash_decode_route, latent_walk_tiles,
                          routing_override, ssm_decode_route,
                          ssm_decode_update)
from apex_tpu.serving import (DeepseekV2Config, GraniteHybridConfig,
                              Lfm2MoeConfig, PagedDecoder, ServingEngine,
                              ServingModelConfig, SpecConfig, poisson_trace)
from apex_tpu.ops.grouped import row_tile
from apex_tpu.serving.experts import held_experts
from apex_tpu.serving.model import StateIO
from apex_tpu.serving.engine import prefill_route
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import (build_flagship_train_step,
                                          gpt1p3b_config)
from apex_tpu.utils import configure_compile_cache

# Of 24.  The compiler places 13 layers at most on one v5e chip; 12
# leave it about 1 GiB of the 15.75 (see the module docstring).
TRAIN_LAYERS = 12
WARM_LAYERS = 2

ROUTES_ON_TPU = {"decode": "decode", "qkv": "packed", "prefill_fwd": "varlen"}

# bf16 against an fp32-accumulating XLA reference: relative L2 error.
FWD_TOL = 2e-2
GRAD_TOL = 3e-2
# Latent attention's two forms, bf16, two layers at the published widths:
# they round at different places.  Measured (PR 33): 3.7e-2 between
# them, where each lies 4.1e-2 and 4.3e-2 from the float32 reference.
LATENT_FORMS_TOL = 6e-2
# The recurrence's two forms, bf16 activations, a float32 state: the
# chunked scan's matrix products round where the token-by-token update
# does not.  Measured (PR 35), one period at the published widths:
# 2.3e-2 on the logits and on the state, 1.4e-2 on the tail.
SSM_FORMS_TOL = 6e-2
# The short convolution's two forms (LFM2), bf16 activations: a row's
# convolution and projections against a token's, the same arithmetic in
# other fusions; taken as the recurrence's until measured on the chip.
SHORT_CONV_FORMS_TOL = 6e-2
# The grouped expert product at the expert cells' shapes: name ->
# (tokens, top_k, router width, experts held, d, f, documents whose rows
# route alike (0: rows route independently), padding tokens first).
GROUPED_SHAPES = {
    "lfm2_decode": (64, 4, 64, (0, 64), 2048, 1536, 0, 0),
    "lfm2_row": (1024, 4, 64, (0, 64), 2048, 1536, 0, 100),
    "trinity_decode": (64, 4, 256, (0, 32), 3072, 3072, 0, 0),
    "trinity_chunk": (2048, 4, 256, (0, 32), 3072, 3072, 0, 300),
    "dsv2_decode": (64, 6, 160, (0, 20), 5120, 1536, 8, 0),
    "dsv2_chunk": (512, 6, 160, (0, 20), 5120, 1536, 1, 100),
}
# The grouped squared-ReLU product at the Nemotron-H cell's decode and
# row shapes, in the latent (d 1,024) where its experts work.
GROUPED_RELU2_SHAPES = {
    "nemotron_decode": (64, 22, 512, (0, 128), 1024, 2688, 0, 0),
    "nemotron_row": (1024, 22, 512, (0, 128), 1024, 2688, 0, 100),
}
# The in-place state update with eight B/C groups at the Nemotron-H
# cell's decode shape: rows, slots, heads, head dim, groups, state, taps.
GROUPED_STATE_SHAPE = (64, 66, 128, 64, 8, 128, 4)
# The kernel and the XLA route compute the same float32 arithmetic in
# other fusions: relative L2 of y, the state and the tail.
GROUPED_STATE_TOL = 1e-5
# Paged decode over a pool of 2 K/V heads, kept lane-dense, at the
# Nemotron-H cell's decode shape: rows, query heads, K/V heads, head dim,
# pages, page size, p_max.
LANE_DENSE_SHAPE = (64, 32, 2, 128, 1600, 64, 32)
# Chips holding equal shards of one program's state.
BALANCE_FACTOR = 1.5


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def memory_by_device() -> list:
    """[{"in_use", "peak", "limit"}] per device; None where the
    backend keeps no statistics."""
    out = []
    for dev in jax.devices():
        stats = dev.memory_stats()
        out.append(None if stats is None else {
            "in_use": stats.get("bytes_in_use"),
            "peak": stats.get("peak_bytes_in_use"),
            "limit": stats.get("bytes_limit")})
    return out


def require_on_whole_mesh(tree, mesh, what: str) -> None:
    want = set(mesh.devices.flat)
    for leaf in jax.tree_util.tree_leaves(tree):
        require(set(leaf.sharding.device_set) == want,
                f"{what}: a leaf lives on {len(leaf.sharding.device_set)} "
                f"of the mesh's {len(want)} devices")


def require_balanced(what: str) -> None:
    used = [m["in_use"] for m in memory_by_device() if m is not None]
    if used:
        require(max(used) <= BALANCE_FACTOR * min(used),
                f"{what}: bytes in use differ by more than "
                f"{BALANCE_FACTOR}x across chips: {used}")


def free_device_memory() -> None:
    gc.collect()
    jax.clear_caches()


# -- legs ---------------------------------------------------------------------

def leg_kernels(*, batch, seq, heads, head_dim, block, pages, page_size,
                max_batch, pages_per_request, seed=0) -> dict:
    """The attention kernels against the XLA routes the repo keeps."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    routes = {
        "qkv": flash_attention_qkv_route(batch, seq, heads, head_dim,
                                         block=block),
    }
    qkv = jax.random.normal(keys[0], (batch, seq, heads * 3 * head_dim),
                            jnp.bfloat16)
    dctx = jax.random.normal(keys[1], (batch, seq, heads * head_dim),
                             jnp.bfloat16)

    def fwd_bwd(qkv, dctx, **kw):
        ctx, vjp = jax.vjp(lambda x: flash_attention_qkv(
            x, heads, causal=True, block=block, **kw), qkv)
        return ctx, vjp(dctx)[0]

    ctx, dqkv = jax.jit(fwd_bwd)(qkv, dctx)
    with routing_override(fwd="xla", bwd="xla"):
        # a block_k differing from block is the wrapper's generic path
        ctx_ref, dqkv_ref = jax.jit(functools.partial(
            fwd_bwd, block_k=block // 2))(qkv, dctx)
    errors = {"qkv_fwd": rel_l2(ctx, ctx_ref),
              "qkv_bwd": rel_l2(dqkv, dqkv_ref)}

    rng = np.random.RandomState(seed)
    table = jnp.asarray(rng.randint(
        1, pages, (max_batch, pages_per_request)), jnp.int32)
    # the last: grouped-query heads (six to a K/V head, Trinity's 48 on
    # 8 at the flagship's 16) under a window, over the compact table a
    # window pool hands over: kv_len runs past what the table holds
    window = (pages_per_request - 2) * page_size
    for name, q_len, quantized, group in (("decode", 1, False, 1),
                                          ("verify", 5, False, 1),
                                          ("chunk", 128, False, 1),
                                          ("decode_int8", 1, True, 1),
                                          ("decode_gqa_window", 1, False, 6)):
        kv_heads = heads if group == 1 else max(1, heads // 2)
        q = jax.random.normal(
            keys[2], (max_batch, kv_heads * group, q_len, head_dim),
            jnp.bfloat16)
        pool_shape = (pages, page_size, kv_heads, head_dim)
        kv_len = rng.randint(
            q_len, (1 if group == 1 else 3) * pages_per_request * page_size
            + 1, (max_batch,))
        kw, win = {}, None
        if group > 1:
            win = window
            kw = dict(kv_start=jnp.asarray(
                np.maximum(0, kv_len - q_len - window + 1)
                // page_size * page_size, jnp.int32))
        kv_len = jnp.asarray(kv_len, jnp.int32)
        if quantized:
            k = jax.random.randint(keys[3], pool_shape, -127, 128, jnp.int8)
            v = jax.random.randint(keys[4], pool_shape, -127, 128, jnp.int8)
            kw.update(
                k_scale=jax.random.uniform(keys[5], pool_shape[:3]) / 64,
                v_scale=jax.random.uniform(keys[6], pool_shape[:3]) / 64)
        else:
            k = jax.random.normal(keys[3], pool_shape, jnp.bfloat16)
            v = jax.random.normal(keys[4], pool_shape, jnp.bfloat16)
        routes.setdefault("decode", flash_decode_route(q, k))

        def decode(**route):
            # a fresh jit each time: the route is chosen while tracing
            with routing_override(**route):
                return jax.jit(lambda a, kw: flash_decode(
                    *a, window=win, **kw))((q, k, v, table, kv_len), kw)

        errors[name] = rel_l2(decode(), decode(decode="xla"))

    for name, err in errors.items():
        require(np.isfinite(err), f"kernels: {name} is not finite")
        require(err <= (GRAD_TOL if name == "qkv_bwd" else FWD_TOL),
                f"kernels: {name} differs from the XLA route by {err:.3e}")
    return {"routes": routes, "rel_l2": errors}


def leg_train(cfg, *, batch_per_chip, seq, steps, mesh_shape=None,
              devices=None) -> dict:
    """A few steps of the flagship ZeRO train step on a fixed batch
    (``devices=None``: every device, ZeRO over all of them)."""
    fs = build_flagship_train_step(cfg, plan="bf16_fit", devices=devices,
                                   mesh_shape=mesh_shape)
    require_on_whole_mesh((fs.params, fs.opt_state), fs.mesh, "train state")
    dp = fs.mesh.shape[parallel_state.DATA_AXIS]
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (batch_per_chip * dp, seq), 0,
                                cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=-1)
    params, opt_state, step, mesh = fs.params, fs.opt_state, fs.step, fs.mesh

    forward_loss = None
    if mesh_shape is None:
        # the loss a user logs is the loss of the step: up to PR 37 the
        # donating step returned 1.1% less than the forward at the same
        # parameters from 10 layers on (PERF.md section 7, row 0)
        forward = jax.jit(shard_map(
            lambda p, t, l: jax.lax.pmean(
                jnp.mean(fs.model.apply(p, t, labels=l)),
                parallel_state.DATA_AXIS),
            mesh=mesh, in_specs=(P(), P("data"), P("data")), out_specs=P(),
            check_rep=False))
        forward_loss = float(forward(params, tokens, labels))

    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, tokens, labels)
    losses = [float(loss)]
    first_step_s = time.perf_counter() - t0
    if forward_loss is not None:
        require(abs(losses[0] - forward_loss) <= 1e-3 * abs(forward_loss),
                f"train: the step's loss {losses[0]} is not the forward's "
                f"{forward_loss} at the same parameters")
    step_ms = []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(float(loss))
        step_ms.append(round((time.perf_counter() - t0) * 1e3, 1))
    require(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    require(losses[-1] < losses[0], f"train: loss did not fall {losses}")
    require_on_whole_mesh((params, opt_state), mesh, "train state, stepped")
    if mesh.size > 1:
        require_balanced("train")
    memory = memory_by_device()
    parallel_state.destroy_model_parallel()
    return {"layers": cfg.num_layers, "batch": int(tokens.shape[0]),
            "seq": seq, "mesh": dict(mesh.shape), "losses": losses,
            "forward_loss": forward_loss, "first_step_s": round(first_step_s, 1), "step_ms": step_ms,
            "memory": memory}


def pool_geometry(*, page_size, max_batch, prompt_len, max_new, **_):
    """(pages, pages per request):
    1.5x the worst footprint of ``max_batch`` requests, plus the
    scratch page."""
    per_request = -(-(prompt_len[1] + max_new[1]) // page_size)
    return 1 + max_batch * per_request * 3 // 2, per_request


def _engine(cfg, *, page_size, max_batch, prompt_len, max_new, **kw):
    pages, per_request = pool_geometry(
        page_size=page_size, max_batch=max_batch, prompt_len=prompt_len,
        max_new=max_new)
    return ServingEngine(
        cfg, num_pages=pages, page_size=page_size, max_batch=max_batch,
        max_pages_per_request=per_request,
        prefill_budget=cfg.max_position, **kw)


def _serve_twice(eng, trace):
    """Serve ``trace`` on the engine's clock, then submit the same
    prompts again and drain.  Returns the token streams and whether
    the second pass repeated them."""
    prompts = [(list(r.prompt), r.max_new_tokens) for r in trace]
    eng.serve(trace)
    again = [eng.submit(p, n) for p, n in prompts]
    eng.run()
    for r in list(trace) + again:
        require(r.finish_reason in ("length", "eos"),
                f"serve: request {r.rid} ended as {r.finish_reason!r}")
        require(r.finish_reason == "eos"
                or len(r.generated) == r.max_new_tokens,
                f"serve: request {r.rid} made {len(r.generated)} of "
                f"{r.max_new_tokens} tokens")
    streams = [list(r.generated) for r in trace]
    return streams, streams == [list(r.generated) for r in again]


def leg_serve(cfg, *, requests, seed, rate, prompt_len, max_new, page_size,
              max_batch, tp=1) -> dict:
    """warmup(), a Poisson trace on the wall clock, the prompts again."""
    eng = _engine(cfg, page_size=page_size, max_batch=max_batch,
                  prompt_len=prompt_len, max_new=max_new, tp=tp)
    if tp > 1:
        require_on_whole_mesh((eng.params, eng.cache.k, eng.cache.v),
                              eng._mesh, "serve state")
    # what one device's kernels see: its head slice of a layer's pool
    sds = jax.ShapeDtypeStruct
    heads, d = cfg.num_heads // tp, cfg.head_dim
    routes = {
        "decode": flash_decode_route(
            sds((max_batch, heads, 1, d), cfg.dtype),
            sds((eng.cache.num_pages, page_size, heads, d), cfg.dtype)),
        "prefill_fwd": flash_attention_route(
            sds((heads, cfg.max_position, d), cfg.dtype),
            segment_ids=True)["fwd"]}
    warmup_s = eng.warmup()
    trace = poisson_trace(seed, requests, rate=rate, prompt_len=prompt_len,
                          max_new=max_new, vocab_size=cfg.vocab_size)
    t0 = time.perf_counter()
    with hot_path_guard("serving after warm-up", transfers=None,
                        tripwire=False):
        streams, repeated = _serve_twice(eng, trace)
    serve_s = time.perf_counter() - t0
    # one decode executable makes every token: batching cannot show
    require(repeated, "serve: the same prompts gave different streams")
    if tp > 1:
        require_balanced("serve")
    return {"layers": cfg.num_layers, "tp": tp, "routes": routes,
            "requests": 2 * requests,
            "tokens": 2 * sum(len(s) for s in streams),
            "warmup_s": round(warmup_s, 1), "serve_s": round(serve_s, 1),
            "decode_steps": eng.decode_steps, "memory": memory_by_device(),
            "streams": streams}


def leg_prefill_rungs(cfg, *, seed, page_size, max_batch, prompt_len,
                      max_new, **_) -> dict:
    """One prompt a rung of the engine's prefill ladder, through the
    engine's own executable at the rung's width and at the widest: a
    row as wide as the prompt needs must serve what the full row did.
    The route of every rung is reported, so that a rung a new compiler
    takes another way shows here and not in a cell's numbers."""
    eng = _engine(cfg, page_size=page_size, max_batch=max_batch,
                  prompt_len=prompt_len, max_new=max_new)
    widest = eng.prefill_widths[-1]
    rng = np.random.RandomState(seed)

    def row(prompt, width):
        n = len(prompt)
        tokens, seg, positions = np.zeros((3, 1, width), np.int32)
        tokens[0, :n], seg[0, :n], positions[0, :n] = prompt, 1, np.arange(n)
        first, k, v = eng._prefill_fn(
            eng.params, jnp.asarray(tokens), jnp.asarray(seg),
            jnp.asarray(positions), np.int32(n - 1))
        return int(first), k[:, :n], v[:, :n]

    rungs = {}
    for width in eng.prefill_widths:
        # a context this rung is the narrowest for
        prompt = rng.randint(0, cfg.vocab_size, width - 3)
        require(eng.prefill_width(len(prompt)) == width,
                f"prefill rungs: {len(prompt)} tokens do not take the "
                f"{width} row of {eng.prefill_widths}")
        first, k, v = row(prompt, width)
        first_wide, k_wide, v_wide = row(prompt, widest)
        rungs[width] = {"route": prefill_route(cfg, eng.tp, width),
                        "first": first, "first_widest": first_wide,
                        "k": rel_l2(k, k_wide), "v": rel_l2(v, v_wide)}
    for width, r in rungs.items():
        require(r["first"] == r["first_widest"],
                f"prefill rungs: the {width} row serves token {r['first']}, "
                f"the {widest} row {r['first_widest']}")
        require(max(r["k"], r["v"]) <= FWD_TOL,
                f"prefill rungs: K/V of the {width} row differ from the "
                f"{widest} row's by {max(r['k'], r['v']):.3e}")
    return {"layers": cfg.num_layers, "widest": widest, "rungs": rungs}


def leg_warm(cfg, *, spec_k, chunk_size, seed, rate, prompt_len, max_new,
             page_size, max_batch) -> dict:
    """The verify, chunk and int8 executables: compile, then a short
    trace through them (a prompt longer than ``chunk_size`` prefills
    through the chunk step, a boundary with a draft goes through
    verify).  Whether the streams repeat is reported, not required: a
    boundary runs the verify executable when any row of the batch has
    a draft and the decode executable otherwise, and on the chip the
    two agree to bf16 rounding, not bit for bit, so a greedy near-tie
    can fall either way with the batch's composition."""
    out = {}
    for kv_quant in (None, "int8"):
        eng = _engine(cfg, page_size=page_size, max_batch=max_batch,
                      prompt_len=prompt_len, max_new=max_new,
                      spec=SpecConfig(k=spec_k, chunk_size=chunk_size),
                      kv_quant=kv_quant)
        warmup_s = eng.warmup()
        trace = poisson_trace(seed, 4, rate=rate, prompt_len=prompt_len,
                              max_new=max_new, vocab_size=cfg.vocab_size)
        with hot_path_guard("spec serving after warm-up", transfers=None,
                            tripwire=False):
            streams, repeated = _serve_twice(eng, trace)
        out[kv_quant or "bf16"] = {
            "warmup_s": round(warmup_s, 1),
            "tokens": 2 * sum(len(s) for s in streams),
            "streams_repeated": repeated}
        del eng
        free_device_memory()
    return out


# -- the run ------------------------------------------------------------------

SERVE_TRAFFIC = dict(rate=8.0, prompt_len=(64, 256), max_new=(16, 64),
                     page_size=64, max_batch=8)


def grouped_walk(cfg, width, *, seed, page_size, rows, documents, doc_pages,
                 own_pages) -> dict:
    """Decode rows that read the same latent pages (ISSUE 36): ``rows``
    rows over ``documents`` documents of ``doc_pages`` pages and up to
    ``own_pages`` of their own.  What a row returns in the batch is what
    it returns called alone; on the chip, how long a layer's call takes
    with the documents shared, with no page shared, and as a quarter of
    the rows at four query positions each (the tile the shared walk
    scores with, against one walk: ISSUE 36's step 0)."""
    rng = np.random.RandomState(seed)
    heads, rank = cfg.num_heads, cfg.kv_lora_rank
    p_max = doc_pages + own_pages
    n_pages = 1 + documents * doc_pages + rows * own_pages
    key = jax.random.PRNGKey(seed)
    pool = jax.random.normal(key, (1, n_pages, page_size, width), cfg.dtype)
    q = jax.random.normal(key, (rows, 1, heads, width), cfg.dtype)
    table = np.zeros((rows, p_max), np.int32)
    kv_len = np.zeros((rows,), np.int32)
    free = 1 + documents * doc_pages
    for r in range(rows):
        own = rng.randint(1, own_pages * page_size)
        held = -(-own // page_size)
        table[r, :doc_pages] = 1 + (r % documents) * doc_pages + np.arange(
            doc_pages)
        table[r, doc_pages:doc_pages + held] = free + np.arange(held)
        free += held
        kv_len[r] = doc_pages * page_size + own
    # the same lengths over pages no two rows hold in the same place
    apart = table.copy()
    apart[:, :doc_pages] = rng.randint(1, n_pages, (rows, doc_pages))
    scale = cfg.softmax_scale
    call = jax.jit(lambda q, table, kv_len: flash_decode_latent(
        q, pool, table, kv_len, v_dim=rank, scale=scale))
    together = np.asarray(call(q, table, kv_len), np.float32)
    gap = max(float(np.abs(together[r] - np.asarray(call(
        q[r:r + 1], table[r:r + 1], kv_len[r:r + 1]), np.float32)[0]).max())
        for r in range(0, rows, max(1, rows // 8)))
    tiles = latent_walk_tiles(table, kv_len, q_len=1, heads=heads,
                              page_size=page_size)
    out = {"alone_max_abs_gap": gap, "blocks_walked": int(tiles.walked),
           "blocks_fetched": int(tiles.fetched), "call_ms": "not measured"}
    if jax.default_backend() == "tpu":
        def ms(*args, n=20):
            jax.block_until_ready(call(*args))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(n):
                    o = call(*args)
                jax.block_until_ready(o)
                best = min(best, (time.perf_counter() - t0) / n * 1e3)
            return best

        few = rows // 4
        out["call_ms"] = {
            "documents_shared": ms(q, table, kv_len),
            "no_page_shared": ms(q, apart, kv_len),
            "quarter_of_the_rows_at_4_positions": ms(
                q.reshape(few, 4, heads, width), table[:few], kv_len[:few])}
    return out


def leg_latent(cfg, *, seed, page_size, row, doc_pages, max_new,
               walk) -> dict:
    """Latent attention's two forms on the same tokens, a shared page
    under a second reader, and rows that walk shared pages together
    (``walk``: :func:`grouped_walk`'s sizes).

    A row of ``row`` tokens goes through the decoder whole (expanded,
    no cache), then its second half again over the latent pages its
    first half filled (absorbed): one mathematics, so the logits agree
    to rounding.  Then an engine with prefix sharing serves a document
    of ``doc_pages`` pages and a second request on the same document:
    the second computes only its own tokens, and the document's pages
    hold bit for bit what they held before it read them."""
    dec = PagedDecoder(cfg)
    params = cfg.init_params(seed)
    rng = np.random.RandomState(seed)
    seq = rng.randint(0, cfg.vocab_size, row)
    half = row // 2
    one = lambda a: jnp.asarray(np.asarray(a, np.int32)[None])
    logits, latent, _ = jax.jit(dec.prefill)(
        params, one(seq), one(np.ones(row)), one(np.arange(row)))
    eng = ServingEngine(
        cfg, params, num_pages=4 * doc_pages + 8, page_size=page_size,
        max_batch=2, max_pages_per_request=2 * doc_pages,
        prefill_budget=half, prefix_sharing=True)
    cache = eng.cache
    route = flash_decode_latent_route(
        jax.ShapeDtypeStruct((1, half, cfg.num_heads, cache.k.shape[-1]),
                             cfg.dtype), cache.k)
    pages = cache.allocate(cache.pages_needed(row), 0)
    idx = np.arange(half)
    cache.write_tokens(latent[:, 0, :half], None,
                       np.asarray(pages)[idx // page_size], idx % page_size)
    pos = np.arange(half, row)
    out = jax.jit(dec.extend)(
        params, cache.k, None, one(seq[half:]), one(pos),
        one(np.asarray(pages)[pos // page_size]), one(pos % page_size),
        cache.page_table([pages]), jnp.asarray([row], jnp.int32))
    cache.k = out[1]
    forms = rel_l2(out[0][0], logits[0, half:])
    require(forms < LATENT_FORMS_TOL,
            f"latent: absorbed against expanded {forms:.2e}")
    cache.free(pages)

    eng.warmup()
    doc = [int(t) for t in rng.randint(0, cfg.vocab_size,
                                       doc_pages * page_size)]
    first = eng.submit(doc, max_new)
    eng.run()
    [(held, _)] = eng.prefix_index._entries.values()
    before = np.asarray(cache.k[:, np.asarray(held)])
    second = eng.submit(doc + [int(t) for t in rng.randint(
        0, cfg.vocab_size, page_size // 2)], max_new)
    with hot_path_guard("latent serving after warm-up", transfers=None,
                        tripwire=False):
        eng.run()
    require(second.prefix_tokens == len(doc),
            f"latent: the second request shared {second.prefix_tokens} of "
            f"{len(doc)} tokens")
    require(np.array_equal(before, np.asarray(cache.k[:, np.asarray(held)])),
            "latent: a shared page changed under its second reader")
    for r in (first, second):
        require(len(r.generated) == max_new,
                f"latent: request {r.rid} made {len(r.generated)} tokens")
    width = int(cache.k.shape[-1])
    del eng, cache, before
    free_device_memory()
    grouped = grouped_walk(cfg, width, seed=seed, page_size=page_size,
                           **walk)
    # the MXU gives a query row the same numbers whatever rows share its
    # product; the XLA route batches its einsum otherwise
    require(grouped["alone_max_abs_gap"] <= (0 if route == "decode"
                                             else 1e-5),
            f"latent: a row alone differs by "
            f"{grouped['alone_max_abs_gap']:.2e} from the row in its batch")
    return {"layers": cfg.num_layers, "route": route,
            "rel_l2": {"absorbed_vs_expanded": forms},
            "shared_tokens": second.prefix_tokens,
            "pool_width": width, "grouped_walk": grouped,
            "memory": memory_by_device()}


def leg_state_space(cfg, *, seed, page_size, row, max_new) -> dict:
    """The recurrence's two forms on the same tokens, and the slots.

    A row of ``row`` tokens goes through the decoder whole (the chunked
    scan from zero); then its first half again as a row, written into a
    slot, and its second half token by token through the in-place
    decode update: one mathematics, so the final state and the logits
    agree to rounding.  The step that advances that slot leaves another
    slot's bytes as they were.  Then an engine with ONE slot to hand
    out serves two requests one after the other: the second takes the
    slot the first gave back and serves what it serves alone."""
    dec = PagedDecoder(cfg)
    params = cfg.init_params(seed)
    rng = np.random.RandomState(seed)
    seq = rng.randint(0, cfg.vocab_size, row)
    half = row // 2
    one = lambda a: jnp.asarray(np.asarray(a, np.int32)[None])
    prefill = jax.jit(dec.prefill)
    logits, _, _, state, tail = prefill(
        params, one(seq), one(np.ones(row)), one(np.arange(row)))
    eng = ServingEngine(cfg, params, num_pages=row // page_size + 8,
                        page_size=page_size, max_batch=2,
                        prefill_budget=half, state_slots=4)
    cache, spool = eng.cache, eng.cache.state_pool
    route = ssm_decode_route(spool.ssm)
    tokens = np.zeros((row,), np.int32)
    tokens[:half] = seq[:half]
    seg = (np.arange(row) < half).astype(np.int32)
    _, k, v, s_half, t_half = prefill(
        params, one(tokens), one(seg), one(np.arange(row) * seg))
    pages = cache.allocate(cache.pages_needed(row), 0)
    idx = np.arange(row)
    cache.write_tokens(
        k[:, 0], v[:, 0], np.where(seg, np.asarray(pages)[idx // page_size],
                                   0), np.where(seg, idx % page_size, 0))
    spool.write(2, s_half[:, 0], t_half[:, 0])
    spool.write(3, state[:, 0], tail[:, 0])         # a bystander
    bystander = np.asarray(spool.ssm[:, 3])
    decode = jax.jit(dec.decode, donate_argnums=(1, 2))
    table = cache.page_table([pages])
    steps = []
    for p in range(half, row):
        out = decode(params, cache.k, cache.v, one(seq[p]), one(p), table,
                     one(p + 1),
                     state=StateIO(spool.ssm, spool.conv, one(2), one(0)))
        cache.k, cache.v, spool.ssm, spool.conv = out[1:]
        steps.append(out[0][0])
    forms = {"logits": rel_l2(jnp.stack(steps), logits[0, half:]),
             "state": rel_l2(spool.ssm[:, 2], state[:, 0]),
             "tail": rel_l2(spool.conv[:, 2], tail[:, 0])}
    require(max(forms.values()) < SSM_FORMS_TOL,
            f"state space: decode form against chunked form {forms}")
    require(np.array_equal(bystander, np.asarray(spool.ssm[:, 3])),
            "state space: a slot changed under another row's steps")
    cache.free(pages)

    def serve(engine, prompts):
        engine.warmup()
        reqs = []
        for prompt in prompts:
            reqs.append(engine.submit(prompt, max_new))
            with hot_path_guard("state-space serving after warm-up",
                                transfers=None, tripwire=False):
                engine.run()
        return [r.generated for r in reqs]

    prompts = [[int(t) for t in rng.randint(0, cfg.vocab_size, n)]
               for n in (half // 2, half + page_size)]
    kw = dict(num_pages=row // page_size + 8, page_size=page_size,
              max_batch=2, prefill_budget=half)
    shared = serve(ServingEngine(cfg, params, state_slots=2, **kw), prompts)
    alone = serve(ServingEngine(cfg, params, **kw), prompts[1:])
    require(shared[1] == alone[0],
            "state space: a reused slot did not start from zero")
    return {"layers": cfg.num_layers, "route": route, "rel_l2": forms,
            "page_head_dim": cfg.page_head_dim,
            "memory": memory_by_device()}


def leg_short_conv(cfg, *, seed, page_size, row, max_new) -> dict:
    """LFM2's gated short convolution in its two forms on the same
    tokens, and its tail-only slots (ISSUE 39).

    A row of ``row`` tokens goes through the decoder whole; then its
    first half again as a row, whose tails are written into a slot, and
    its second half token by token, each convolution layer reading and
    advancing the slot's tail in place: the logits and the final tails
    agree to rounding, and a bystander slot keeps its bytes.  Then an
    engine with ONE slot to hand out serves two requests one after the
    other: the second takes the slot the first gave back and serves
    what it serves alone."""
    dec = PagedDecoder(cfg)
    params = cfg.init_params(seed)
    rng = np.random.RandomState(seed)
    seq = rng.randint(0, cfg.vocab_size, row)
    half = row // 2
    one = lambda a: jnp.asarray(np.asarray(a, np.int32)[None])
    prefill = jax.jit(dec.prefill)
    logits, _, _, tail, _ = prefill(
        params, one(seq), one(np.ones(row)), one(np.arange(row)))
    eng = ServingEngine(cfg, params, num_pages=row // page_size + 8,
                        page_size=page_size, max_batch=2,
                        prefill_budget=half, state_slots=4)
    cache, spool = eng.cache, eng.cache.state_pool
    tokens = np.zeros((row,), np.int32)
    tokens[:half] = seq[:half]
    seg = (np.arange(row) < half).astype(np.int32)
    _, k, v, t_half, _ = prefill(
        params, one(tokens), one(seg), one(np.arange(row) * seg))
    pages = cache.allocate(cache.pages_needed(row), 0)
    idx = np.arange(row)
    cache.write_tokens(
        k[:, 0], v[:, 0], np.where(seg, np.asarray(pages)[idx // page_size],
                                   0), np.where(seg, idx % page_size, 0))
    spool.write(2, t_half[:, 0])
    spool.write(3, tail[:, 0])                  # a bystander
    bystander = np.asarray(spool.conv[:, 3])
    decode = jax.jit(dec.decode, donate_argnums=(1, 2))
    table = cache.page_table([pages])
    steps = []
    for p in range(half, row):
        out = decode(params, cache.k, cache.v, one(seq[p]), one(p), table,
                     one(p + 1),
                     state=StateIO(None, spool.conv, one(2), one(0)))
        cache.k, cache.v, spool.conv = out[1:4]
        steps.append(out[0][0])
    forms = {"logits": rel_l2(jnp.stack(steps), logits[0, half:]),
             "tail": rel_l2(spool.conv[:, 2], tail[:, 0])}
    require(max(forms.values()) < SHORT_CONV_FORMS_TOL,
            f"short conv: decode form against row form {forms}")
    require(np.array_equal(bystander, np.asarray(spool.conv[:, 3])),
            "short conv: a slot changed under another row's steps")
    cache.free(pages)

    def serve(engine, prompts):
        engine.warmup()
        reqs = []
        for prompt in prompts:
            reqs.append(engine.submit(prompt, max_new))
            with hot_path_guard("short-conv serving after warm-up",
                                transfers=None, tripwire=False):
                engine.run()
        return [r.generated for r in reqs]

    prompts = [[int(t) for t in rng.randint(0, cfg.vocab_size, n)]
               for n in (half // 2, half + page_size)]
    kw = dict(num_pages=row // page_size + 8, page_size=page_size,
              max_batch=2, prefill_budget=half)
    shared = serve(ServingEngine(cfg, params, state_slots=2, **kw), prompts)
    alone = serve(ServingEngine(cfg, params, **kw), prompts[1:])
    require(shared[1] == alone[0],
            "short conv: a reused slot did not start from zero")
    return {"layers": cfg.num_layers, "rel_l2": forms,
            "route": flash_decode_route(
                jax.ShapeDtypeStruct((2, cfg.num_heads, 1,
                                      dec.page_head_dim), cfg.dtype),
                jax.ShapeDtypeStruct(cache.k.shape[1:], cache.k.dtype)),
            "tail_pool": list(spool.conv.shape),
            "memory": memory_by_device()}


def ragged_held_experts(u, experts, weights, p, held, valid=None,
                        act="swiglu"):
    """The expert layer's routed part as three ``jax.lax.ragged_dot``
    calls over the pairs sorted expert by expert (what
    ``experts.held_experts`` ran before the grouped kernels); two for
    squared-ReLU experts (``act="relu2"``)."""
    lo, hi = held
    n = hi - lo
    T, k = experts.shape
    local = experts.reshape(-1) - lo
    here = (local >= 0) & (local < n)
    if valid is not None:
        here &= jnp.repeat(valid.reshape(-1), k)
    key = jnp.where(here, local, n)
    order = jnp.argsort(key, stable=True)
    load = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    rows = jnp.take(u, order // k, axis=0)
    up = jax.lax.ragged_dot(rows, p["wu"], load)
    h = (jnp.square(jax.nn.relu(up)) if act == "relu2" else
         jax.nn.silu(jax.lax.ragged_dot(rows, p["wg"], load)) * up)
    y = jax.lax.ragged_dot(h, p["wd"], load)
    y = jnp.where((jnp.arange(T * k) < jnp.sum(load))[:, None], y, 0)
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(T, k, -1)
    w = jnp.where(here.reshape(T, k), weights, 0.0)
    y = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), w)
    return y.astype(u.dtype), load


def grouped_operands(*, seed, tokens, top_k, width, held, d, f,
                     documents=0, padding=0, dtype=jnp.bfloat16):
    """One expert layer call's operands, integer-valued so that every
    product and sum of the layer is exact in float32 and a lost,
    doubled or misrouted row is an exact mismatch: a token row has four
    ones; gate weights are 0 or 32 (so a gate is 0 or at least 32, where
    silu is the identity to float32 rounding and ``h`` rounds to the
    same bf16 either way), up weights -2..2, down weights -1..1, the
    routing weights 1..3.  The routing: each row's ``top_k`` of
    ``width`` experts at random, or, with ``documents``, rows of one
    document (consecutive rows) prefer the same few experts, as rows on
    one shared document do.  Returns (u, experts, weights, p, valid)."""
    rng = np.random.default_rng(seed)
    x = np.zeros((tokens, d), np.float32)
    for t in range(tokens):
        x[t, rng.choice(d, 4, replace=False)] = 1.0
    scores = rng.random((tokens, width))
    if documents:
        doc = np.arange(tokens) * documents // tokens
        scores += 4.0 * rng.random((documents, width))[doc] ** 16
    experts = np.argsort(-scores, axis=1)[:, :top_k].astype(np.int32)
    weights = rng.integers(1, 4, (tokens, top_k)).astype(np.float32)
    n = held[1] - held[0]
    kg, ku, kd = jax.random.split(jax.random.PRNGKey(seed), 3)
    make = jax.jit(lambda kg, ku, kd: dict(
        wg=(32 * jax.random.bernoulli(kg, 0.5, (n, d, f))).astype(dtype),
        wu=jax.random.randint(ku, (n, d, f), -2, 3).astype(dtype),
        wd=jax.random.randint(kd, (n, f, d), -1, 2).astype(dtype)))
    return (jnp.asarray(x, dtype), jnp.asarray(experts),
            jnp.asarray(weights), make(kg, ku, kd),
            jnp.asarray(np.arange(tokens) >= padding))


def _call_ms(fn, args, reps: int) -> float:
    """Wall ms a call, ``reps`` calls dispatched back to back."""
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps * 1e3


def leg_grouped(shapes, *, seed, reps=10, act="swiglu") -> dict:
    """The expert layer's routed part (``experts.held_experts``: the
    padded layout and the two grouped kernels) against the three
    ``ragged_dot`` calls it replaced (two for squared-ReLU experts,
    ``act="relu2"``: ``grouped_relu2_up`` and the down kernel), at each
    of ``shapes`` (``GROUPED_SHAPES``' form), on integer-valued
    operands: equal bit for bit, output and load.  Times (wall ms a
    call, the layout and the unsort included) and the touched experts'
    bytes a second are information."""
    out = {}
    for name, (tokens, top_k, width, held, d, f, documents,
               padding) in shapes.items():
        u, experts, weights, p, valid = grouped_operands(
            seed=seed, tokens=tokens, top_k=top_k, width=width, held=held,
            d=d, f=f, documents=documents, padding=padding)
        tm = row_tile(tokens * top_k, width)
        paths = {"grouped": jax.jit(
                     lambda u, e, w, p, v: held_experts(u, e, w, p, held, v,
                                                        tm=tm, act=act)),
                 "ragged_dot": jax.jit(
                     lambda u, e, w, p, v: ragged_held_experts(
                         u, e, w, p, held, v, act))}
        args = (u, experts, weights, p, valid)
        (y, load), (y_r, load_r) = (fn(*args) for fn in paths.values())
        require(np.array_equal(np.asarray(load), np.asarray(load_r)),
                f"grouped {name}: the loads differ")
        bad = int(np.sum(np.asarray(y, np.float32)
                         != np.asarray(y_r, np.float32)))
        require(bad == 0, f"grouped {name}: {bad} numbers differ from "
                "ragged_dot's")
        load = np.asarray(load)
        touched = int(np.sum(load > 0))
        ms = {path: _call_ms(fn, args, reps) for path, fn in paths.items()}
        mats = 2 if act == "relu2" else 3
        weight_bytes = touched * mats * d * f * p["wg"].dtype.itemsize
        out[name] = {"tm": tm, "pairs_held": int(load.sum()),
                     "touched": touched,
                     "tiles": int(np.sum(-(-load // tm))),
                     "ms": ms,
                     "GBps": {path: weight_bytes / t / 1e6
                              for path, t in ms.items()}}
        del p, paths
        free_device_memory()
    return out


def leg_grouped_state(shape, *, seed, reps=10) -> dict:
    """The in-place state update with several B/C groups
    (``ops/ssm.ssm_decode_update``: a grid axis of groups, a group one
    block of state, channels and tail) against its XLA route on the same
    pools, at ``shape`` (``GROUPED_STATE_SHAPE``' form): one layer of
    random float32 states and bf16 tails, half the rows real, the rest
    on the scratch slot.  ``y``, the state and the tail of every real
    row agree within ``GROUPED_STATE_TOL``; wall ms a call and the
    states' bytes a second are information."""
    rows, slots, heads, head_dim, groups, n, taps = shape
    lanes = heads * head_dim
    ch = lanes + 2 * groups * n
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    bf = jnp.bfloat16
    state = jax.random.normal(ks[0], (1, slots, n, lanes), jnp.float32)
    tail = jax.random.normal(ks[1], (1, slots, 1, (taps - 1) * ch), bf)
    real = rows // 2
    table = jnp.concatenate([jnp.arange(1, real + 1, dtype=jnp.int32),
                             jnp.zeros((rows - real,), jnp.int32)])
    xbc = jax.random.normal(ks[2], (rows, ch), bf)
    dt = jax.random.normal(ks[3], (rows, heads), bf)
    w = dict(conv_w=(jax.random.normal(ks[4], (taps, ch)) / 2).astype(bf),
             conv_b=(jax.random.normal(ks[5], (ch,)) / 10).astype(bf),
             dt_bias=(jax.random.normal(ks[6], (heads,)) - 3).astype(bf),
             a_log=(jax.random.normal(ks[7], (heads,)) / 2).astype(bf),
             d_skip=(1 + jax.random.normal(ks[8], (heads,)) / 10).astype(bf))
    outs, ms = {}, {}
    for route in ("decode", "xla"):
        with routing_override(decode=route):
            fn = jax.jit(lambda s, t, tb, x, d: ssm_decode_update(
                s, t, tb, x, d, layer=0, heads=heads, groups=groups, **w))
            args = (state, tail, table, xbc, dt)
            y, s1, t1 = fn(*args)
            outs[route] = (y[:real], s1[0, 1:real + 1],
                           t1[0, 1:real + 1].astype(jnp.float32))
            ms[route] = _call_ms(fn, args, reps)
    gaps = {name: rel_l2(a, b) for name, a, b in zip(
        ("y", "state", "tail"), outs["decode"], outs["xla"])}
    require(max(gaps.values()) < GROUPED_STATE_TOL,
            f"grouped state update: kernel and XLA route differ {gaps}")
    moved = 2 * real * n * lanes * 4
    return {"rel_l2": gaps, "ms": ms,
            "GBps": {k: moved / t / 1e6 for k, t in ms.items()}}


def leg_lane_dense_decode(shape, *, seed, reps=10) -> dict:
    """Paged decode over a pool whose heads fill no sublane tile, kept
    lane-dense (``[1, pages, page_size, heads * head_dim]``,
    ``ops.attention.lane_dense_pages``), at ``shape``
    (``LANE_DENSE_SHAPE``' form; rows' lengths uniform up to the table):
    the kernel within ``FWD_TOL`` of the XLA route on the same pool, and
    equal bit for bit to the kernel on the same numbers stored ``[1,
    pages, page_size, heads, head_dim]``.  Wall ms a call and K/V bytes
    a second are information."""
    rows, hq, heads, head_dim, pages, page_size, p_max = shape
    rng = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    split = (1, pages, page_size, heads, head_dim)
    k5, v5 = (jax.random.normal(key, split, jnp.bfloat16) for key in ks[:2])
    pools = {"heads": (k5, v5),
             "lane_dense": (k5.reshape(split[:3] + (-1,)),
                            v5.reshape(split[:3] + (-1,)))}
    kv_len = rng.randint(1, p_max * page_size + 1, (rows,))
    table = np.zeros((rows, p_max), np.int32)
    used = -(-kv_len // page_size)
    ids = rng.permutation(np.arange(1, pages))[:int(used.sum())]
    for i, (a, n) in enumerate(zip(np.cumsum(used) - used, used)):
        table[i, :n] = ids[a:a + n]
    q = jax.random.normal(ks[2], (rows, hq, 1, head_dim), jnp.bfloat16)
    args = (q, jnp.asarray(table), jnp.asarray(kv_len, jnp.int32))
    outs, ms = {}, {}
    for name, route in (("lane_dense", None), ("heads", None),
                        ("xla", "xla")):
        k, v = pools["heads" if name == "heads" else "lane_dense"]
        with routing_override(decode=route):
            fn = jax.jit(lambda q, pt, kl, k, v: flash_decode(
                q, k, v, pt, kl))
            outs[name] = np.asarray(fn(*args, k, v), np.float32)
            ms[name] = _call_ms(fn, args + (k, v), reps)
    gap = rel_l2(outs["lane_dense"], outs["xla"])
    require(gap <= FWD_TOL, f"lane-dense decode: kernel and XLA route "
            f"differ by {gap:.3e}")
    bad = int(np.sum(outs["lane_dense"] != outs["heads"]))
    require(bad == 0, f"lane-dense decode: {bad} numbers differ from the "
            "head-split pool's")
    moved = int(used.sum()) * 2 * page_size * heads * head_dim * 2
    return {"route": flash_decode_route(q, pools["lane_dense"][0]),
            "rel_l2": gap, "live_pages": int(used.sum()), "ms": ms,
            "GBps": {k: moved / t / 1e6 for k, t in ms.items()}}


def _lfm2_config() -> Lfm2MoeConfig:
    """LFM2-24B-A2B's published widths: the two dense layers and one
    period (attention, conv, conv, conv), an eighth of the experts and
    of the vocabulary."""
    return Lfm2MoeConfig(
        vocab_size=8192, hidden_size=2048, num_heads=32, num_kv_heads=8,
        layer_types=("conv", "conv", "full_attention", "conv", "conv",
                     "conv"),
        num_dense_layers=2, intermediate_size=11776,
        moe_intermediate_size=1536, num_experts=64, experts_held=(0, 8),
        top_k=4, dtype=jnp.bfloat16)


def _hybrid_config(periods: int) -> GraniteHybridConfig:
    """Granite 4.0-H Micro's published widths, ``periods`` periods of
    ten layers deep, an eighth of the vocabulary."""
    return GraniteHybridConfig(
        vocab_size=12544, hidden_size=2048, num_heads=32, num_kv_heads=8,
        layer_types=tuple("attention" if i % 10 == 5 else "mamba"
                          for i in range(10 * periods)),
        intermediate_size=8192, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, attention_multiplier=0.015625,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, dtype=jnp.bfloat16)


def _latent_config(layers: int) -> DeepseekV2Config:
    """DeepSeek-V2's published widths, ``layers`` deep (one dense), an
    eighth of the experts and of the vocabulary."""
    return DeepseekV2Config(
        vocab_size=12800, hidden_size=5120, num_heads=128, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, num_layers=layers, first_k_dense_replace=1,
        intermediate_size=12288, moe_intermediate_size=1536,
        n_routed_experts=160, experts_held=(0, 20), top_k=6, n_group=8,
        topk_group=3, routed_scaling_factor=16.0, n_shared_experts=2,
        dtype=jnp.bfloat16)


def _serving_config(layers: int) -> ServingModelConfig:
    return ServingModelConfig(51200, 2048, 16, layers, max_position=1024,
                              dtype=jnp.bfloat16)


def _report(name: str, result: dict) -> dict:
    shown = {k: v for k, v in result.items() if k != "streams"}
    print(f"[{name}] {json.dumps(shown)}", flush=True)
    free_device_memory()
    return result


def main() -> int:
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, found backend "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    # a donation that cannot alias means state is being copied per step
    warnings.filterwarnings(
        "error", message=".*donated buffers were not usable.*")
    cache_dir = configure_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    import jaxlib
    from importlib.metadata import version
    print(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
          f"libtpu {version('libtpu')}")
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"devices={device['count']} cache_dir={cache_dir}")
    print(f"_native.available()={_native.available()} "
          f"build_error()={_native.build_error()}", flush=True)

    flagship = gpt1p3b_config()
    pages, pages_per_request = pool_geometry(**SERVE_TRAFFIC)
    kernels = _report("kernels", leg_kernels(
        batch=4, seq=flagship.max_position_embeddings,
        heads=flagship.num_attention_heads, head_dim=flagship.kv_channels,
        block=flagship.flash_block_q, pages=pages,
        page_size=SERVE_TRAFFIC["page_size"],
        max_batch=SERVE_TRAFFIC["max_batch"],
        pages_per_request=pages_per_request))

    print(f"train legs: {TRAIN_LAYERS} of {flagship.num_layers} layers "
          "(depth cut; width, batch and sequence are the flagship's)")
    _report("train", leg_train(
        gpt1p3b_config(num_layers=TRAIN_LAYERS), batch_per_chip=4,
        seq=flagship.max_position_embeddings, steps=5))

    serve = _report("serve", leg_serve(
        _serving_config(24), requests=10, seed=0, **SERVE_TRAFFIC))

    routes = {**kernels["routes"], **serve["routes"]}
    print(f"routes: {json.dumps(routes)}", flush=True)
    require(routes == ROUTES_ON_TPU,
            f"routes {routes} are not {ROUTES_ON_TPU}")

    rungs = _report("prefill_rungs", leg_prefill_rungs(
        _serving_config(WARM_LAYERS), seed=2, **SERVE_TRAFFIC))["rungs"]
    require({r["route"] for r in rungs.values()}
            == {ROUTES_ON_TPU["prefill_fwd"]},
            f"prefill rungs: a rung left the "
            f"{ROUTES_ON_TPU['prefill_fwd']} route: {rungs}")

    _report("warm", leg_warm(
        _serving_config(WARM_LAYERS), spec_k=4, chunk_size=128, seed=1,
        **SERVE_TRAFFIC))

    latent = _report("latent", leg_latent(
        _latent_config(WARM_LAYERS), seed=3, page_size=64, row=1024,
        doc_pages=32, max_new=8,
        # the cell's decode step: 64 rows on 8 documents of 16,384 tokens
        walk=dict(rows=64, documents=8, doc_pages=256, own_pages=24)))
    require(latent["route"] == ROUTES_ON_TPU["decode"],
            f"latent: the paged route is {latent['route']}")

    hybrid = _report("state_space", leg_state_space(
        _hybrid_config(1), seed=4, page_size=64, row=1024, max_new=8))
    require(hybrid["route"] == ROUTES_ON_TPU["decode"],
            f"state space: the update's route is {hybrid['route']}")

    short = _report("short_conv", leg_short_conv(
        _lfm2_config(), seed=5, page_size=64, row=1024, max_new=8))
    require(short["route"] == ROUTES_ON_TPU["decode"],
            f"short conv: the paged route is {short['route']}")

    _report("grouped", leg_grouped(GROUPED_SHAPES, seed=6))
    _report("grouped_relu2", leg_grouped(GROUPED_RELU2_SHAPES, seed=6,
                                         act="relu2"))
    _report("grouped_state", leg_grouped_state(GROUPED_STATE_SHAPE, seed=7))
    lane_dense = _report("lane_dense_decode", leg_lane_dense_decode(
        LANE_DENSE_SHAPE, seed=8))
    require(lane_dense["route"] == ROUTES_ON_TPU["decode"],
            f"lane-dense decode: the paged route is {lane_dense['route']}")

    if len(devices) >= 4:
        _report("train_mesh", leg_train(
            gpt1p3b_config(num_layers=TRAIN_LAYERS), batch_per_chip=4,
            seq=flagship.max_position_embeddings, steps=5,
            mesh_shape=(2, 2, 1), devices=devices[:4]))
        serve_tp = _report("serve_tp4", leg_serve(
            _serving_config(24), requests=10, seed=0, tp=4,
            **SERVE_TRAFFIC))
        print("tp=4 streams equal tp=1 streams: "
              f"{serve_tp['streams'] == serve['streams']}")
    else:
        print(f"multi-chip legs not run: {len(devices)} device(s)")

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

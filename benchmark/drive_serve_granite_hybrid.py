#!/usr/bin/env python3
"""The window driver of ``kind: serve_granite_hybrid`` configurations: a
``granitemoehybrid`` decoder without experts (Granite 4.0-H: Mamba-2
state-space layers beside a few NoPE grouped-query layers) served whole
through the same ``ServingEngine`` as the other three models, its
requests owning a slot of recurrent state beside their pages.

The window, the drain, the sample and the bookkeeping are
``drive_serve``'s, imported; what this kind brings is its own ``build``
and its comparison.  No router is discrete here, so the comparison is
the GPT cells': after the window the reference runs over a seeded sample
of finished requests (each padded to the longest a request may be) and
reads, at every served position, how far the served token's logit lies
below the reference's best: the widest (``served_logit_gap``) and the
mean over every served position (``served_logit_gap_mean``) are
compared.  And, because this model's requests own a recurrent state
whose precision the logits barely show (bfloat16 activations move them
more than a state rounded every token does), the state itself: at the
window's close the first layer's state of the two running requests that
have taken the most tokens is read out of their slots and compared with
the reference's state after the same tokens, in the head it is worst in
(``first_layer_state_gap``).

As a script it is ``control.py`` for this kind: the program's readings
and, with ``--control 1``, two controls', for several seeds in one
process: the reference computed in fp8 (as for every other kind), and
the reference exact but for the recurrent state, rounded to bfloat16
after every token (what a state pool in the activations' type would
do).
"""

from __future__ import annotations

import importlib
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402
import weights  # noqa: E402
from drive_serve import (drain, generated_counts, latencies,  # noqa: E402
                         sample_finished, window, work_done)
from flops_granite_hybrid import model_of  # noqa: E402

SAMPLE_REQUESTS = 6
STATE_SAMPLE = 2
CONTROLS = ("fp8", "state_bf16")


def build(ctx):
    """(engine, make_weights, reference module, its Shape)."""
    import jax.numpy as jnp
    from apex_tpu.serving import GraniteHybridConfig, ServingEngine

    model = model_of(ctx.config)
    dtype = jnp.dtype(model["dtype"])
    ref = importlib.import_module("reference." + ctx.config["reference"])
    s = ref.model_shape(model)
    cfg = GraniteHybridConfig(
        vocab_size=s.vocab, hidden_size=s.hidden, num_heads=s.heads,
        num_kv_heads=s.kv_heads, layer_types=s.layer_types,
        intermediate_size=s.ffn, mamba_n_heads=s.ssm_heads,
        mamba_d_head=s.ssm_head_dim, mamba_d_state=s.state,
        mamba_d_conv=s.taps, mamba_chunk_size=int(model["mamba_chunk_size"]),
        embedding_multiplier=s.embedding_multiplier,
        residual_multiplier=s.residual_multiplier,
        attention_multiplier=s.attention_multiplier,
        logits_scaling=s.logits_scaling, rms_norm_eps=s.eps, dtype=dtype,
        state_dtype=jnp.dtype(model["ssm_state_dtype"]))
    layout = ref.param_layout(model)
    make = lambda: ref.finish(weights.make(layout, ctx.seed, dtype))
    eng = ServingEngine(cfg, make(), **ctx.config["builder"])
    return eng, make, ref, s


def position_gaps(ctx, ref, shape, params, sample, cast_name="exact"):
    """Per served position of ``sample``, as two vectors: the gap of the
    served token below the reference's best logit, and the gap of the
    token the control ``cast_name`` puts first."""
    import jax.numpy as jnp

    builder = ctx.config["builder"]
    n_max = int(ctx.mix["max_new"].get("hi") or
                max(ctx.mix["max_new"]["values"]))
    length = builder["max_pages_per_request"] * builder["page_size"]
    gaps, lows = [], []
    for req in sample:
        seq = req.prompt + req.generated
        # the engine admits only prompt + max_new <= length
        tokens = np.zeros((length,), np.int32)
        tokens[:len(seq)] = seq
        n = len(req.generated)
        served = np.zeros((n_max,), np.int32)
        served[:n] = req.generated
        # position first predicts the first served token; the n_max rows
        # from there lie inside the padded sequence
        first = len(req.prompt) - 1
        best, chosen, low = (
            np.asarray(a)[:n] for a in ref.served_gaps(
                params, jnp.asarray(tokens), np.int32(first), np.int32(n),
                jnp.asarray(served), shape=shape, cast_name=cast_name))
        gaps.append(best - chosen)
        lows.append(best - low)
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros((0,), np.float32)
    return cat(gaps), cat(lows)


def sample_states(eng, n: int = STATE_SAMPLE) -> list:
    """(the tokens it has taken, the first layer's state ``[H, P, N]``
    as its slot holds it) for the ``n`` running requests that have taken
    the most tokens.  The launch in flight is landed first, so a slot
    holds the state after exactly ``kv_len`` tokens."""
    eng.snapshot()
    cfg, spool = eng.cfg, eng.cache.state_pool
    rows = sorted((r for r in eng.sched.running
                   if r.prefill_pos is None and r.slot is not None),
                  key=lambda r: -r.kv_len)[:n]
    out = []
    for r in rows:
        state = np.asarray(spool.ssm[0, r.slot], np.float32)  # [N, H * P]
        out.append((list(r.context[:r.kv_len]), state.reshape(
            cfg.mamba_d_state, cfg.mamba_n_heads,
            cfg.mamba_d_head).transpose(1, 2, 0)))
    return out


def state_gaps(ctx, ref, shape, params, states, cast_name="exact"):
    """Per sampled state, (the program's gap from the reference's state
    after the same tokens, the gap of the control ``cast_name``'s)."""
    import jax.numpy as jnp

    builder = ctx.config["builder"]
    length = builder["max_pages_per_request"] * builder["page_size"]
    gaps, lows = [], []
    for taken, got in states:
        tokens = np.zeros((length,), np.int32)
        tokens[:len(taken)] = taken
        first = lambda cast: ref.first_layer_state(
            params, jnp.asarray(tokens), np.int32(len(taken)), shape, cast)
        want = first("exact")
        gaps.append(ref.state_gap(got, want))
        lows.append(ref.state_gap(first(cast_name), want)
                    if cast_name != "exact" else gaps[-1])
    return gaps, lows


def run(ctx) -> harness.Result:
    import jax
    from apex_tpu.analysis import hot_path_guard

    eng, make_weights, ref, shape = build(ctx)
    eng.warmup()
    devices = jax.devices()[:ctx.config["chips"]]
    tracer = harness.Tracer(ctx.trace, ctx.seconds)
    steps0, decode0 = eng.steps, eng.decode_steps

    with hot_path_guard("serve window", transfers=None,
                        tripwire=False) as guard:
        t_wall = time.perf_counter()
        offered, t0, window_s, late, traced_from = window(
            ctx, eng, tracer, ctx.seconds)
        in_window = generated_counts(offered)
        decode_at_close = eng.decode_steps
        steps_in = eng.steps - steps0
        decode_in = eng.decode_steps - decode0
        # the drain comes before the profiler's stop, which takes
        # seconds that the requests still in flight would wait through
        drain_s = drain(eng) if ctx.mix["loop"] == "open" else 0.0
        tracer.stop()
        recompiles = guard.recompiles
    tokens_out = sum(in_window.values())
    lat = latencies(offered, t0 + window_s, eng.clock())
    failed = sum(1 for r in offered if r.finish_reason in
                 ("rejected", "timeout", "failed"))
    spool = eng.cache.state_pool
    result = harness.Result(
        attempted=len(offered), failed=failed,
        end_to_end={"serve_tokens_per_s": tokens_out / window_s},
        window_start=t_wall, window_s=window_s,
        memory_peak_bytes=harness.memory_peak_bytes(devices), checks=[],
        counters={
            "tokens_out": tokens_out, "engine_steps": steps_in,
            "decode_steps": decode_in, "drain_s": drain_s,
            "requests_finished": sum(
                1 for r in offered if r.finish_reason in ("length", "eos")),
            "preemptions": sum(r.preemptions for r in offered),
            "generator_late_ms_max": float(max(late, default=0.0)) * 1e3,
            "recompiles_in_window": recompiles,
            "max_batch": eng.max_batch, "prefill_row": eng.prefill_budget,
            "chunk": eng.chunk_size, "page_size": eng.cache.page_size,
            "pages": eng.cache.num_pages, "pages_used": eng.cache.pages_used,
            "state_slots": spool.num_slots,
            "state_slots_held": spool.slots_used,
            **work_done(offered, in_window), **lat})
    if ctx.trace:
        result.trace, result.trace_window_ns, result.trace_window_s = \
            tracer.reduce()
        result.counters["traced"] = {
            "decode_steps": decode_at_close - traced_from[1],
            **work_done(offered, in_window, since=traced_from[0])}

    sample = sample_finished(offered, ctx.seed, SAMPLE_REQUESTS)
    n_sample = len(sample)
    states = sample_states(eng)
    del eng, spool
    harness.free_device_memory()
    params = make_weights()
    gaps, _ = position_gaps(ctx, ref, shape, params, sample)
    drift, _ = state_gaps(ctx, ref, shape, params, states)
    result.counters["served_tokens_compared"] = len(gaps)
    result.counters["state_tokens_taken"] = [len(t) for t, _ in states]
    nan = float("nan")
    result.checks = [
        harness.Check("served_logit_gap",
                      float(np.max(gaps)) if n_sample else nan,
                      ctx.limits["served_logit_gap"]),
        harness.Check("served_logit_gap_mean",
                      float(np.mean(gaps)) if len(gaps) else nan,
                      ctx.limits["served_logit_gap_mean"]),
        harness.Check("first_layer_state_gap",
                      max(drift) if drift else nan,
                      ctx.limits["first_layer_state_gap"]),
        harness.Check("recompiles_in_window", float(recompiles), 0.0),
    ]
    return result


def readings(ctx, control: bool) -> dict:
    """The program's three readings over one window and, with
    ``control``, those of each of :data:`CONTROLS` in the program's
    place."""
    eng, make_weights, ref, shape = build(ctx)
    eng.warmup()
    offered, _, window_s, _, _ = window(ctx, eng, harness.Tracer(False),
                                        ctx.seconds)
    if ctx.mix["loop"] == "open":
        drain(eng)
    sample = sample_finished(offered, ctx.seed, SAMPLE_REQUESTS)
    states = sample_states(eng)
    tokens = sum(len(r.generated) for r in offered)
    preemptions = sum(r.preemptions for r in offered)
    del eng
    harness.free_device_memory()
    params = make_weights()
    three = lambda g, d: {"served_logit_gap": float(np.max(g)),
                          "served_logit_gap_mean": float(np.mean(g)),
                          "first_layer_state_gap": max(d)}
    gaps, _ = position_gaps(ctx, ref, shape, params, sample)
    drift, _ = state_gaps(ctx, ref, shape, params, states)
    out = {"compared": len(gaps), "sample": len(sample),
           "sample_lens": [r.seq_len for r in sample],
           "state_tokens_taken": [len(t) for t, _ in states],
           "tokens_per_s": tokens / window_s, "preemptions": preemptions,
           "program": three(gaps, drift)}
    if control:
        for name in CONTROLS:
            _, lows = position_gaps(ctx, ref, shape, params, sample,
                                    cast_name=name)
            _, low_drift = state_gaps(ctx, ref, shape, params, states,
                                      cast_name=name)
            out[name] = three(lows, low_drift)
    del params
    harness.free_device_memory()
    return out


def main(argv=None) -> int:
    import argparse
    import json

    import run as run_py

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    _, _, ctx = run_py.open_cell(args.workload, seed=seeds[0],
                                 seconds=args.seconds)
    rows = []
    for seed in seeds:
        ctx.seed = seed
        t0 = time.perf_counter()
        row = {"seed": seed, **readings(ctx, bool(args.control)),
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print("control: " + json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

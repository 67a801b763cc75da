#!/usr/bin/env python3
"""The window driver of ``kind: serve_lfm2_moe`` configurations: an
``lfm2_moe`` decoder (LiquidAI LFM2-MoE: gated short-convolution layers
beside QK-normed grouped-query layers, a top-4 sigmoid expert layer)
served through the same ``ServingEngine`` as the other four models, its
requests owning a slot that holds a convolution tail alone beside their
pages.

The window, the drain, the sample and the bookkeeping are
``drive_serve``'s, imported; what this kind brings is its own ``build``
and its comparison, which is ``drive_serve_afmoe``'s, because top-4 of
64 is discrete: at every served position of a seeded sample of finished
requests the reference reads how far the served token's logit lies
below its best.  Positions whose routing is decided by less than
``limits["route_margin_min"]`` in some expert layer (the gap between
the fourth and the fifth ``score + bias``: every expert is held here)
are left out of the widest gap (``served_logit_gap``), the share left
out is itself compared (``route_left_out_share``), and the MEAN gap is
compared over every served position, none left out
(``served_logit_gap_mean``).  The program's choices are never forced on
the reference.

As a script it is ``control.py`` for this kind: the program's readings
and, with ``--control 1``, two controls', for several seeds in one
process: the reference computed in fp8 (as for every other kind), and
the reference exact but for each convolution layer's tail, zeroed
where decoding takes over from the prompt's prefill (what a program
that did not carry the tail from prefill into decode would serve).
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
from drive_serve_afmoe import MARGINS, widest  # noqa: E402
from flops_lfm2_moe import model_of  # noqa: E402

SAMPLE_REQUESTS = 6
CONTROLS = ("fp8", "tail_reset")


def build(ctx):
    """(engine, make_weights, reference module, its Shape)."""
    import jax.numpy as jnp
    from apex_tpu.serving import Lfm2MoeConfig, ServingEngine

    model = model_of(ctx.config)
    dtype = jnp.dtype(model["dtype"])
    ref = importlib.import_module("reference." + ctx.config["reference"])
    s = ref.model_shape(model)
    cfg = Lfm2MoeConfig(
        vocab_size=s.vocab, hidden_size=s.hidden, num_heads=s.heads,
        num_kv_heads=s.kv_heads, layer_types=s.layer_types,
        num_dense_layers=s.dense_layers, intermediate_size=s.ffn,
        moe_intermediate_size=s.expert_ffn, num_experts=s.router_width,
        experts_held=s.held, top_k=s.top_k, route_scale=s.route_scale,
        conv_L_cache=s.taps, rope_theta=s.theta, norm_eps=s.eps,
        dtype=dtype)
    layout = ref.param_layout(model)
    make = lambda: weights.make(layout, ctx.seed, dtype)
    eng = ServingEngine(cfg, make(), **ctx.config["builder"])
    return eng, make, ref, s


def position_gaps(ctx, ref, shape, params, sample, cast_name="exact"):
    """Per served position of ``sample``, as three vectors: the gap of
    the served token below the reference's best logit, the gap of the
    token the control ``cast_name`` puts first, and the reference's
    least routing margin there."""
    import jax.numpy as jnp

    builder = ctx.config["builder"]
    n_max = int(ctx.mix["max_new"].get("hi") or
                max(ctx.mix["max_new"]["values"]))
    length = builder["max_pages_per_request"] * builder["page_size"]
    gaps, lows, margins = [], [], []
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
        best, chosen, low, margin = (
            np.asarray(a)[:n] for a in ref.served_gaps(
                params, jnp.asarray(tokens), np.int32(first), np.int32(n),
                jnp.asarray(served), shape=shape, cast_name=cast_name))
        gaps.append(best - chosen)
        lows.append(best - low)
        margins.append(margin)
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros((0,), np.float32)
    return cat(gaps), cat(lows), cat(margins)


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
    del eng, spool
    harness.free_device_memory()
    params = make_weights()
    gaps, _, margins = position_gaps(ctx, ref, shape, params, sample)
    gap, left_out = widest(gaps, margins, ctx.limits["route_margin_min"])
    result.counters["served_tokens_compared"] = len(gaps)
    nan = float("nan")
    result.checks = [
        harness.Check("served_logit_gap", gap if n_sample else nan,
                      ctx.limits["served_logit_gap"]),
        harness.Check("served_logit_gap_mean",
                      float(np.mean(gaps)) if len(gaps) else nan,
                      ctx.limits["served_logit_gap_mean"]),
        harness.Check("route_left_out_share", left_out,
                      ctx.limits["route_left_out_share"]),
        harness.Check("recompiles_in_window", float(recompiles), 0.0),
    ]
    return result


def readings(ctx, control: bool) -> dict:
    """The program's readings over one window and, with ``control``,
    those of each of :data:`CONTROLS` in the program's place, for each
    of ``MARGINS`` as ``route_margin_min``."""
    eng, make_weights, ref, shape = build(ctx)
    eng.warmup()
    offered, _, window_s, _, _ = window(ctx, eng, harness.Tracer(False),
                                        ctx.seconds)
    if ctx.mix["loop"] == "open":
        drain(eng)
    sample = sample_finished(offered, ctx.seed, SAMPLE_REQUESTS)
    tokens = sum(len(r.generated) for r in offered)
    preemptions = sum(r.preemptions for r in offered)
    del eng
    harness.free_device_memory()
    params = make_weights()
    gaps, _, margins = position_gaps(ctx, ref, shape, params, sample)
    out = {"compared": len(gaps), "sample": len(sample),
           "sample_lens": [r.seq_len for r in sample],
           "tokens_per_s": tokens / window_s, "preemptions": preemptions,
           "mean": {"program": float(np.mean(gaps))}, "by_margin_min": {}}
    lows = {}
    if control:
        for name in CONTROLS:
            _, lows[name], _ = position_gaps(ctx, ref, shape, params, sample,
                                             cast_name=name)
            out["mean"][name] = float(np.mean(lows[name]))
    for margin_min in MARGINS:
        gap, left_out = widest(gaps, margins, margin_min)
        row = {"program": gap, "left_out": left_out}
        for name, low in lows.items():
            row[name] = widest(low, margins, margin_min)[0]
        out["by_margin_min"][str(margin_min)] = row
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

#!/usr/bin/env python3
"""The window driver of ``kind: serve_afmoe`` configurations: an
``afmoe`` decoder (arcee-ai Trinity) served through the same
``ServingEngine`` as the GPT cells.

``drive_serve.build`` constructs a GPT's ``ServingModelConfig`` from
fixed keys, so a second architecture brings its own ``build`` and its
own comparison; the window, the drain, the sample and the bookkeeping
are ``drive_serve``'s, imported.

The comparison is ``drive_serve``'s too in what it compares (the widest
gap by which a served token's logit lies below the reference's best,
greedy traffic), with two differences.  Sequences are padded to one of
two lengths (the prefill row plus the longest output; the longest
request), since a reference pass over 17,000 positions is not worth
paying for a 300-token request.  And the reference chooses its own
experts: top-4 of 256 is discrete, so where two scores lie closer than
rounding the program may choose another, and its logits then differ by
more than rounding.  The reference returns, per served position, the
least margin by which one of the experts HELD here is inside or outside
its own top-4 in any layer; positions under
``limits["route_margin_min"]`` are left out of the widest gap, and the
share left out is itself compared (``route_left_out_share``).  So that
what is left out is no hiding place, the MEAN gap is compared over every
served position, none left out (``served_logit_gap_mean``): a choice
decided by rounding moves a few positions in a hundred, a lower
precision a third of them.  The program's choices are never forced on
the reference: that would hide a wrong router.

As a script it is ``control.py`` for this kind (``control.py`` builds a
GPT): the program's readings and, with ``--control 1``, the fp8
control's, for several seeds in one process, each at a row of candidate
margins.
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
from flops_afmoe import model_of  # noqa: E402
from drive_serve import (drain, generated_counts, latencies,  # noqa: E402
                         sample_finished, window, work_done)

SAMPLE_REQUESTS = 4
# the thresholds the control's table is printed for
MARGINS = (0.0, 3e-4, 1e-3, 2e-3, 3e-3, 5e-3, 1e-2)


def build(ctx):
    """(engine, make_weights, reference module, its Shape)."""
    import jax.numpy as jnp
    from apex_tpu.serving import ServingEngine
    from apex_tpu.serving.model import AfmoeConfig

    model = model_of(ctx.config)
    dtype = jnp.dtype(model["dtype"])
    ref = importlib.import_module("reference." + ctx.config["reference"])
    shape = ref.model_shape(model)
    cfg = AfmoeConfig(
        vocab_size=shape.vocab, hidden_size=shape.hidden,
        num_heads=shape.heads, num_kv_heads=shape.kv_heads,
        head_dim=shape.head_dim, layer_types=shape.layer_types,
        num_dense_layers=shape.dense_layers, intermediate_size=shape.ffn,
        moe_intermediate_size=shape.expert_ffn,
        num_experts=shape.router_width, experts_held=shape.held,
        top_k=shape.top_k, route_scale=shape.route_scale,
        sliding_window=shape.window, rope_theta=shape.theta,
        rms_norm_eps=shape.eps, dtype=dtype)
    layout = ref.param_layout(model)
    make = lambda: weights.make(layout, ctx.seed, dtype)
    eng = ServingEngine(cfg, make(), **ctx.config["builder"])
    return eng, make, ref, shape


def position_gaps(ctx, ref, shape, params, sample, cast_name="exact"):
    """Per served position of ``sample``, as three vectors: the gap of
    the served token below the reference's best logit, the gap of the
    lower precision's first token, and the reference's least routing
    margin there."""
    import jax.numpy as jnp

    builder = ctx.config["builder"]
    n_max = int(ctx.mix["max_new"].get("hi") or
                max(ctx.mix["max_new"]["values"]))
    lengths = sorted({builder["prefill_budget"] + n_max,
                      builder["max_pages_per_request"]
                      * builder["page_size"]})
    gaps, lows, margins = [], [], []
    for req in sample:
        seq = req.prompt + req.generated
        s = next(n for n in lengths if n >= len(seq))
        tokens = np.zeros((s,), np.int32)
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


def widest(gaps, margins, margin_min: float):
    """(the widest of ``gaps`` at positions whose routing margin is at
    least ``margin_min``, the share of positions left out)."""
    keep = margins >= margin_min
    widest_kept = float(np.max(gaps[keep])) if keep.any() else 0.0
    return widest_kept, (1.0 - float(keep.mean()) if len(keep) else 0.0)


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
        drain_s = drain(eng) if ctx.mix["loop"] == "open" else 0.0
        tracer.stop()
        recompiles = guard.recompiles
    tokens_out = sum(in_window.values())
    lat = latencies(offered, t0 + window_s, eng.clock())
    failed = sum(1 for r in offered if r.finish_reason in
                 ("rejected", "timeout", "failed"))
    if ctx.mix["loop"] == "open":
        failed += sum(1 for r in offered if r.finish_reason is None)
        e2e = {"tpot_p95_ms": harness.percentile(lat["tpot_ms"], 95)}
    else:
        e2e = {"serve_tokens_per_s": tokens_out / window_s}
    wpool = eng.cache.window_pool
    result = harness.Result(
        attempted=len(offered), failed=failed, end_to_end=e2e,
        window_start=t_wall, window_s=window_s,
        memory_peak_bytes=harness.memory_peak_bytes(devices), checks=[],
        counters={
            "tokens_out": tokens_out, "engine_steps": steps_in,
            "decode_steps": decode_in, "drain_s": drain_s,
            "requests_finished": sum(
                1 for r in offered if r.finish_reason in ("length", "eos")),
            "preemptions": sum(r.preemptions for r in offered),
            "recompiles_in_window": recompiles,
            "max_batch": eng.max_batch, "prefill_row": eng.prefill_budget,
            "chunk": eng.chunk_size, "page_size": eng.cache.page_size,
            "full_pages": eng.cache.num_pages,
            "window_pages": wpool.num_pages,
            "window_pages_per_request": wpool.max_pages_per_request,
            **work_done(offered, in_window), **lat})
    if ctx.trace:
        result.trace, result.trace_window_ns, result.trace_window_s = \
            tracer.reduce()
        result.counters["traced"] = {
            "decode_steps": decode_at_close - traced_from[1],
            **work_done(offered, in_window, since=traced_from[0])}

    sample = sample_finished(offered, ctx.seed, SAMPLE_REQUESTS)
    n_sample = len(sample)
    del eng
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


def readings(ctx, control: bool, positions: bool = False) -> dict:
    """``control.py``'s ``serve_readings`` for this kind: the program's
    gap over one window and, with ``control``, the gap of the reference
    computed with fp8 operands in the program's place, for each of
    ``MARGINS`` as ``route_margin_min``; with ``positions`` also every
    compared position's gap(s) and margin, to choose a margin from."""
    eng, make_weights, ref, shape = build(ctx)
    eng.warmup()
    offered, _, window_s, _, _ = window(ctx, eng, harness.Tracer(False),
                                        ctx.seconds)
    if ctx.mix["loop"] == "open":
        drain(eng)
    sample = sample_finished(offered, ctx.seed, SAMPLE_REQUESTS)
    tokens = sum(len(r.generated) for r in offered)
    del eng
    harness.free_device_memory()
    params = make_weights()
    gaps, lows, margins = position_gaps(
        ctx, ref, shape, params, sample,
        cast_name="fp8" if control else "exact")
    out = {"compared": len(gaps), "sample": len(sample),
           "sample_lens": [r.seq_len for r in sample],
           "tokens_per_s": tokens / window_s,
           "mean": {"program": float(np.mean(gaps)),
                    **({"fp8": float(np.mean(lows))} if control else {})},
           "by_margin_min": {}}
    for margin_min in MARGINS:
        gap, left_out = widest(gaps, margins, margin_min)
        row = {"program": gap, "left_out": left_out}
        if control:
            row["fp8"] = widest(lows, margins, margin_min)[0]
        out["by_margin_min"][str(margin_min)] = row
    if positions:
        out["positions"] = {
            "gap": gaps.tolist(), "margin": margins.tolist(),
            **({"fp8_gap": lows.tolist()} if control else {})}
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
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="also write the rows, with every position, here")
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    _, _, ctx = run_py.open_cell(args.workload, seed=seeds[0],
                                 seconds=args.seconds)
    rows = []
    for seed in seeds:
        ctx.seed = seed
        t0 = time.perf_counter()
        row = {"seed": seed,
               **readings(ctx, bool(args.control), bool(args.out)),
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print("control: " + json.dumps(
            {k: v for k, v in row.items() if k != "positions"}), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

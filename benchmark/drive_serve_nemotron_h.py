#!/usr/bin/env python3
"""The window driver of ``kind: serve_nemotron_h`` configurations: a
``nemotron_h`` decoder with a latent expert layer (Nemotron 3 Super:
Mamba-2 layers with eight B/C groups, a NoPE grouped-query layer, and
expert layers of squared-ReLU experts in a latent beside a shared
expert, one mixer a layer) served through the same ``ServingEngine`` as
the other five models as one chip of four that share each layer, its
requests owning a slot of recurrent state beside their pages.

The window, the drain, the sample and the bookkeeping are
``drive_serve``'s, imported; what this kind brings is its own ``build``
and its comparison, which joins two others'.  Top-22 of 512 is
discrete, so the served logits are compared as ``drive_serve_lfm2_moe``
compares them (``position_gaps``: at every served position of a seeded
sample of finished requests, how far the served token's logit lies
below the reference's best; positions whose routing is decided by less
than ``limits["route_margin_min"]`` in some expert layer are left out
of the widest gap, the share left out is compared, and the MEAN gap is
compared over every served position).  And, because these requests own
a recurrent state whose precision the logits barely show, the state
itself, as ``drive_serve_granite_hybrid`` compares it
(``sample_states``, ``state_gaps``: the first layer's state of the two
running requests that have taken the most tokens against the
reference's after the same tokens).  The program's choices are never
forced on the reference.

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
from drive_serve_afmoe import MARGINS, widest  # noqa: E402
from drive_serve_granite_hybrid import sample_states, state_gaps  # noqa: E402
from drive_serve_lfm2_moe import position_gaps  # noqa: E402
from flops_nemotron_h import model_of  # noqa: E402

SAMPLE_REQUESTS = 6
CONTROLS = ("fp8", "state_bf16")


def build(ctx):
    """(engine, make_weights, reference module, its Shape)."""
    import jax.numpy as jnp
    from apex_tpu.serving import NemotronHConfig, ServingEngine

    model = model_of(ctx.config)
    dtype = jnp.dtype(model["dtype"])
    ref = importlib.import_module("reference." + ctx.config["reference"])
    s = ref.model_shape(model)
    cfg = NemotronHConfig.from_published(
        model, dtype=dtype, state_dtype=jnp.dtype(model["ssm_state_dtype"]))
    layout = ref.param_layout(model)
    make = lambda: ref.finish(weights.make(layout, ctx.seed, dtype))
    eng = ServingEngine(cfg, make(), **ctx.config["builder"])
    return eng, make, ref, s


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
    gaps, _, margins = position_gaps(ctx, ref, shape, params, sample)
    drift, _ = state_gaps(ctx, ref, shape, params, states)
    gap, left_out = widest(gaps, margins, ctx.limits["route_margin_min"])
    result.counters["served_tokens_compared"] = len(gaps)
    result.counters["state_tokens_taken"] = [len(t) for t, _ in states]
    nan = float("nan")
    result.checks = [
        harness.Check("served_logit_gap", gap if n_sample else nan,
                      ctx.limits["served_logit_gap"]),
        harness.Check("served_logit_gap_mean",
                      float(np.mean(gaps)) if len(gaps) else nan,
                      ctx.limits["served_logit_gap_mean"]),
        harness.Check("route_left_out_share", left_out,
                      ctx.limits["route_left_out_share"]),
        harness.Check("first_layer_state_gap",
                      max(drift) if drift else nan,
                      ctx.limits["first_layer_state_gap"]),
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
    states = sample_states(eng)
    tokens = sum(len(r.generated) for r in offered)
    preemptions = sum(r.preemptions for r in offered)
    del eng
    harness.free_device_memory()
    params = make_weights()
    gaps, _, margins = position_gaps(ctx, ref, shape, params, sample)
    drift, _ = state_gaps(ctx, ref, shape, params, states)
    out = {"compared": len(gaps), "sample": len(sample),
           "sample_lens": [r.seq_len for r in sample],
           "state_tokens_taken": [len(t) for t, _ in states],
           "tokens_per_s": tokens / window_s, "preemptions": preemptions,
           "mean": {"program": float(np.mean(gaps))},
           "state": {"program": max(drift)}, "by_margin_min": {}}
    lows = {}
    if control:
        for name in CONTROLS:
            _, lows[name], _ = position_gaps(ctx, ref, shape, params, sample,
                                             cast_name=name)
            out["mean"][name] = float(np.mean(lows[name]))
            out["state"][name] = max(state_gaps(
                ctx, ref, shape, params, states, cast_name=name)[1])
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

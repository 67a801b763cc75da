#!/usr/bin/env python3
"""Read, on the chip, what the limits of a cell are set from.

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 \\
        [--seconds <s>] [--control 1] [--out <file>]

For every seed, in ONE process (set-up is most of a run): the program's
numbers against the plain reference (the lower readings) and, with
``--control 1``, the control's against the reference (the upper
readings): the reference put in the program's place and computed in
fp8, and for a training cell the fault that leaves half of the batch
out.  A serving cell's window is ``--seconds`` long at the cell's own
load (long enough to finish the mix's longest requests).  ``run.py``
never runs this; PERF.md's limits come from its output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402
import run as run_py  # noqa: E402


def train_readings(ctx, control: bool) -> dict:
    import drive_train
    from apex_tpu.transformer import parallel_state

    fs, make_weights, ref = drive_train.build(ctx)
    batches = drive_train.make_batches(ctx, fs, drive_train.CHECKED_STEPS)
    params = make_weights()
    params, opt_state, got = drive_train.first_steps(
        ctx, fs, params, make_weights, batches, ref)
    del params, opt_state, fs
    parallel_state.destroy_model_parallel()
    harness.free_device_memory()
    want = drive_train.reference_readings(ctx, ref, make_weights, batches)
    open_limits = {"loss_gap": 0, "grad_norm_gap": 0, "delta_norm_gap": 0}
    gaps = lambda g: {c.name: c.value
                      for c in drive_train.compare(g, want, open_limits)}
    out = {"program": gaps(got), "losses": got["losses"],
           "worst_leaves": {
               "grad": drive_train.worst_leaf_gap(
                   got["grad_sq"], want["grad_sq"])[1],
               "delta": drive_train.worst_leaf_gap(
                   got["delta_sq"], want["delta_sq"],
                   keep=drive_train.moving_columns(want["grad_sq"]))[1]}}
    if control:
        harness.free_device_memory()
        out["fp8"] = gaps(drive_train.reference_readings(
            ctx, ref, make_weights, batches, cast_name="fp8"))
        harness.free_device_memory()
        out["half_batch"] = gaps(drive_train.reference_readings(
            ctx, ref, make_weights, batches, half_batch=True))
    return out


def serve_readings(ctx, control: bool) -> dict:
    import drive_serve

    eng, make_weights, ref, _ = drive_serve.build(ctx)
    eng.warmup()
    tracer = harness.Tracer(False)
    offered, _, window_s, _, _ = drive_serve.window(
        ctx, eng, tracer, ctx.seconds)
    if ctx.mix["loop"] == "open":
        drive_serve.drain(eng)
    sample = drive_serve.sample_finished(offered, ctx.seed)
    tokens = sum(len(r.generated) for r in offered)
    del eng
    harness.free_device_memory()
    params = make_weights()
    gap, low, compared = drive_serve.widest_gap(
        ctx, ref, params, sample, cast_name="fp8" if control else "exact")
    out = {"program": {"served_logit_gap": gap}, "compared": compared,
           "sample": len(sample), "tokens_per_s": tokens / window_s}
    if control:
        out["fp8"] = {"served_logit_gap": low}
    del params
    harness.free_device_memory()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    _, _, ctx = run_py.open_cell(args.workload, seed=seeds[0],
                                 seconds=args.seconds)
    read = (train_readings if ctx.config["kind"] == "train"
            else serve_readings)
    rows = []
    for seed in seeds:
        ctx.seed = seed
        t0 = time.perf_counter()
        row = {"seed": seed, **read(ctx, bool(args.control)),
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print("control.py: " + json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

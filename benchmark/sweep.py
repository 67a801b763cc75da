#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell, once, on the chip.

    python3 benchmark/sweep.py --workload <name> --rates 1.5,2,2.5,3 \\
        --seconds 30 [--seed <n>]

One process, one engine: for each rate the cell's mix is offered at that
rate for ``--seconds``; what is printed per rate is the requests offered
and finished, the queue left waiting at the close (a backlog that grows
over the window is past the knee), tokens per second, and the tails.
The rate written into the mix file is four fifths of the highest rate
whose backlog does not grow.  ``run.py`` never runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402
import run as run_py  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 101)
    args = ap.parse_args(argv)
    _, _, ctx = run_py.open_cell(args.workload, seed=args.seed,
                                 seconds=args.seconds)
    mix = ctx.mix

    import drive_serve

    eng, *_ = drive_serve.build(ctx)
    eng.warmup()
    rid_base = 0
    for rate in (float(r) for r in args.rates.split(",")):
        ctx.mix = dict(mix, rate=rate)
        ctx.seed += 1
        offered, t0, window_s, late, _ = drive_serve.window(
            ctx, eng, harness.Tracer(False), args.seconds,
            rid_base=rid_base)
        rid_base += len(offered) + 1
        waiting = len(eng.sched.waiting)
        running = len(eng.sched.running)
        tokens = sum(len(r.generated) for r in offered)
        drain_s = drive_serve.drain(eng)
        lat = drive_serve.latencies(offered, t0 + window_s, eng.clock())
        print("sweep.py: " + json.dumps({
            "rate": rate, "offered": len(offered),
            "finished_at_close": sum(
                1 for r in offered if r.finish_t is not None
                and r.finish_t <= t0 + window_s),
            "waiting_at_close": waiting, "running_at_close": running,
            "tokens_per_s": tokens / window_s, "drain_s": drain_s,
            "ttft_p50_ms": harness.percentile(lat["ttft_ms"], 50),
            "ttft_p95_ms": harness.percentile(lat["ttft_ms"], 95),
            "tpot_p50_ms": harness.percentile(lat["tpot_ms"], 50),
            "tpot_p95_ms": harness.percentile(lat["tpot_ms"], 95),
            "queue_wait_p95_ms": harness.percentile(
                lat["queue_wait_ms"], 95),
            "generator_late_ms_max": float(max(late, default=0)) * 1e3,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The window driver of ``kind: serve_deepseek_v2`` configurations: a
``deepseek_v2`` decoder (DeepSeek-V2: latent attention over a latent
page, a group-limited softmax router over the held experts) served
through the same ``ServingEngine`` as the GPT and Trinity cells, with
prefix sharing on.

The window, the drain, the sample and the bookkeeping are
``drive_serve``'s, the comparison by routing margin
``drive_serve_afmoe``'s, imported.  What this kind brings:

* its own ``build``;
* ``preload``: before the window opens, so inside ``setup_s``, the
  mix's ``shared_prefix.count`` documents are read off the same seeded
  generator the window will use, each submitted as a request of its own
  (one new token), and the engine stepped until idle; the prefix index
  must then hold that many entries.  The documents' caches are built
  during set-up and measured while they are asked about;
* work counted from what was computed: a prompt's tokens from its
  ``shared`` count on (``prompt_shared`` beside ``prompt_lens``), and
  for the least bytes of the decode steps, per step and document, how
  many rows read the same shared pages (``decode_shared_rows``, from
  the ring's ``engine.decode`` records).

As a script it is ``control.py`` for this kind: the program's readings
and, with ``--control 1``, the fp8 control's, for several seeds in one
process, each at a row of candidate margins.
"""

from __future__ import annotations

import collections
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
import traffic  # noqa: E402
import weights  # noqa: E402
from drive_serve import (drain, generated_counts, latencies,  # noqa: E402
                         sample_finished, window, work_done)
from drive_serve_afmoe import MARGINS, position_gaps, widest  # noqa: E402
from flops_deepseek_v2 import model_of  # noqa: E402
from metrics import phase_ring  # noqa: E402

SAMPLE_REQUESTS = 4
#: request ids of the preload, clear of the window's (0, 1, ...)
PRELOAD_RID = 1 << 30


def build(ctx):
    """(engine, make_weights, reference module, its Shape)."""
    import jax.numpy as jnp
    from apex_tpu.serving import DeepseekV2Config, ServingEngine

    model = model_of(ctx.config)
    dtype = jnp.dtype(model["dtype"])
    ref = importlib.import_module("reference." + ctx.config["reference"])
    s = ref.model_shape(model)
    factor, original, beta_fast, beta_slow, mscale, mscale_all = s.yarn
    cfg = DeepseekV2Config(
        vocab_size=s.vocab, hidden_size=s.hidden, num_heads=s.heads,
        q_lora_rank=s.q_rank, kv_lora_rank=s.kv_rank,
        qk_nope_head_dim=s.nope, qk_rope_head_dim=s.rope,
        v_head_dim=s.v_dim, num_layers=s.layers,
        first_k_dense_replace=s.dense_layers, intermediate_size=s.ffn,
        moe_intermediate_size=s.expert_ffn,
        n_routed_experts=s.router_width, experts_held=s.held,
        top_k=s.top_k, n_group=s.n_group, topk_group=s.topk_group,
        routed_scaling_factor=s.route_scale, n_shared_experts=s.n_shared,
        rope_theta=s.theta, rope_factor=factor, rope_original_max=original,
        rope_beta_fast=beta_fast, rope_beta_slow=beta_slow,
        rope_mscale=mscale, rope_mscale_all_dim=mscale_all,
        rms_norm_eps=s.eps, dtype=dtype)
    layout = ref.param_layout(model)
    make = lambda: weights.make(layout, ctx.seed, dtype)
    eng = ServingEngine(cfg, make(), **ctx.config["builder"])
    return eng, make, ref, s


def documents(ctx, vocab_size: int) -> list:
    """The mix's shared documents, read off the seeded generator the
    window will use (a second instance of it: the same seed gives the
    same requests), in the order they first appear."""
    shared = ctx.mix["shared_prefix"]
    found = collections.OrderedDict()
    for offered in traffic.requests(ctx.mix, ctx.seed, vocab_size):
        found.setdefault(tuple(offered.prompt[:shared["length"]]), None)
        if len(found) == shared["count"] or offered.index > 4096:
            return [list(doc) for doc in found]


def preload(ctx, eng) -> float:
    """Build the documents' caches: one request a document, one token
    out, stepped to idle.  Returns the seconds it took."""
    from apex_tpu.serving import Request

    t0 = time.perf_counter()
    docs = documents(ctx, eng.cfg.vocab_size)
    for i, doc in enumerate(docs):
        eng.submit_request(Request(rid=PRELOAD_RID + i, prompt=doc,
                                   max_new_tokens=1, arrival_t=eng.clock()))
    eng.run(raise_on_stall=False)
    want = ctx.mix["shared_prefix"]["count"]
    if len(docs) != want or len(eng.prefix_index) != want:
        raise RuntimeError(
            f"preload: {len(docs)} documents, {len(eng.prefix_index)} "
            f"entries in the prefix index, {want} wanted")
    return time.perf_counter() - t0


def shared_work(offered, counts: dict, since: dict = None) -> dict:
    """``work_done`` and, beside each prompt's length, the tokens of it
    that rode in on shared pages (no work)."""
    work = work_done(offered, counts, since)
    since = since or {}
    work["prompt_shared"] = [
        r.prefix_tokens for r in offered
        if since.get(r.rid, 0) < 1 <= counts.get(r.rid, 0)]
    return work


def shared_rows(offered, doc_len: int, t0_ns: float, t1_ns: float) -> list:
    """Per decode step between the two times and document, how many of
    the step's rows read that document's shared pages (rows that hit
    the index), from the ring's ``engine.decode`` records."""
    # seeded random documents: the first tokens tell them apart
    doc_of = {r.rid: tuple(r.prompt[:min(64, doc_len)]) for r in offered
              if r.prefix_tokens >= doc_len}
    out = []
    for rec in phase_ring.ring():
        if rec.name != "engine.decode" or rec.t_start_ns < t0_ns \
                or rec.t_end_ns > t1_ns:
            continue
        out.extend(collections.Counter(
            doc_of[rid] for rid in rec.attrs["rids"]
            if rid in doc_of).values())
    return out


def run(ctx) -> harness.Result:
    import jax
    from apex_tpu.analysis import hot_path_guard

    eng, make_weights, ref, shape = build(ctx)
    eng.warmup()
    preload_s = preload(ctx, eng)
    devices = jax.devices()[:ctx.config["chips"]]
    tracer = harness.Tracer(ctx.trace, ctx.seconds)
    steps0, decode0 = eng.steps, eng.decode_steps
    doc_len = ctx.mix["shared_prefix"]["length"]

    with hot_path_guard("serve window", transfers=None,
                        tripwire=False) as guard:
        t_wall = time.perf_counter()
        offered, t0, window_s, late, traced_from = window(
            ctx, eng, tracer, ctx.seconds)
        t_close_ns = time.perf_counter_ns()
        in_window = generated_counts(offered)
        decode_at_close = eng.decode_steps
        steps_in = eng.steps - steps0
        decode_in = eng.decode_steps - decode0
        tracer.stop()
        recompiles = guard.recompiles
    tokens_out = sum(in_window.values())
    lat = latencies(offered, t0 + window_s, eng.clock())
    failed = sum(1 for r in offered if r.finish_reason in
                 ("rejected", "timeout", "failed"))
    result = harness.Result(
        attempted=len(offered), failed=failed,
        end_to_end={"serve_tokens_per_s": tokens_out / window_s},
        window_start=t_wall, window_s=window_s,
        memory_peak_bytes=harness.memory_peak_bytes(devices), checks=[],
        counters={
            "tokens_out": tokens_out, "engine_steps": steps_in,
            "decode_steps": decode_in, "preload_s": preload_s,
            "requests_finished": sum(
                1 for r in offered if r.finish_reason in ("length", "eos")),
            "prefix_hits": sum(1 for r in offered if r.prefix_hit),
            "prefix_entries": len(eng.prefix_index),
            "preemptions": sum(r.preemptions for r in offered),
            "recompiles_in_window": recompiles,
            "max_batch": eng.max_batch, "prefill_row": eng.prefill_budget,
            "chunk": eng.chunk_size, "page_size": eng.cache.page_size,
            "pages": eng.cache.num_pages, "pages_used": eng.cache.pages_used,
            "shared_len": doc_len,
            "decode_shared_rows": shared_rows(
                offered, doc_len, t_wall * 1e9, t_close_ns),
            **shared_work(offered, in_window), **lat})
    if ctx.trace:
        result.trace, result.trace_window_ns, result.trace_window_s = \
            tracer.reduce()
        result.counters["traced"] = {
            "decode_steps": decode_at_close - traced_from[1],
            "decode_shared_rows": shared_rows(
                offered, doc_len, tracer.t_start * 1e9, t_close_ns),
            **shared_work(offered, in_window, since=traced_from[0])}

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
    """As ``drive_serve_afmoe.readings``: the program's gap over one
    window (after the preload) and, with ``control``, the gap of the
    reference computed with fp8 operands in the program's place, for
    each of ``MARGINS`` as ``route_margin_min``."""
    eng, make_weights, ref, shape = build(ctx)
    eng.warmup()
    preload(ctx, eng)
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

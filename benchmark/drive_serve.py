"""The window driver of ``kind: serve`` configurations.

Set-up builds the ``ServingEngine`` around the benchmark's seeded
weights and warms it (``warmup()``).  The window is the loop of
``ServingEngine.serve()`` (submit what is due, ``step()`` while not
idle, sleep through gaps), copied here with one change: it stops
offering at the window's end and counts what was produced inside it.
A backlog loop keeps ``queue_depth`` requests waiting instead of
following a schedule.  An open loop is drained after the close (no new
arrivals) so that every request due in the window gets its first token
and its finish time; those waits count in its latencies.

After the close, once the peak is read and the engine freed, the plain
reference runs once over a seeded sample of the finished requests (the
longest among them) with their served tokens, and the widest gap by
which a served token's logit lies below the reference's best is
compared.  The traffic is greedy, which is what makes that valid.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

import harness
import traffic
import weights

SAMPLE_REQUESTS = 6


def to_program(params: dict) -> dict:
    """The reference's stacked layout -> the program's list of layers."""
    import jax

    layers = params["layers"]
    n = jax.tree_util.tree_leaves(layers)[0].shape[0]
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = [jax.tree_util.tree_map(lambda a, i=i: a[i], layers)
                     for i in range(n)]
    return out


def build(ctx):
    """(engine, make_weights, reference module, ServingModelConfig)."""
    import jax.numpy as jnp
    from apex_tpu.serving import ServingEngine, ServingModelConfig

    model = ctx.config["model"]
    dtype = jnp.dtype(model["dtype"])
    scfg = ServingModelConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_layers=model["num_layers"],
        max_position=model["max_position_embeddings"],
        mlp_ratio=model["ffn_hidden_size"] // model["hidden_size"],
        dtype=dtype)
    ref = importlib.import_module("reference." + ctx.config["reference"])
    layout = ref.param_layout(model)
    make = lambda: weights.make(layout, ctx.seed, dtype)
    stacked = make()
    params = to_program(stacked)
    del stacked
    eng = ServingEngine(scfg, params, tp=int(ctx.config.get("mesh") or 1),
                        **ctx.config["builder"])
    return eng, make, ref, scfg


def _submit(eng, offered, arrival_t, rid_base=0):
    from apex_tpu.serving import Request

    return eng.submit_request(Request(
        rid=rid_base + offered.index, prompt=offered.prompt,
        max_new_tokens=offered.max_new, arrival_t=arrival_t))


def window(ctx, eng, tracer, seconds: float, rid_base: int = 0):
    """Drive ``eng`` for ``seconds``.  Returns (requests offered, t0,
    window_s, lateness of the generator per request, what had been
    generated and decoded when the traced part began).  ``rid_base``
    keeps request ids apart when one engine serves several windows."""
    mix = ctx.mix
    source = traffic.requests(mix, ctx.seed, eng.cfg.vocab_size)
    backlog = mix["loop"] == "backlog"
    depth = int(mix.get("queue_depth", 64))
    clock = eng.clock
    offered, late = [], []
    pending = next(source)
    traced_from = None
    t0 = now = clock()
    while True:
        if tracer.start_if_due(now - t0):
            traced_from = (generated_counts(offered), eng.decode_steps)
        now = clock()
        if now - t0 >= seconds:
            break
        with tracer.span("submit"):
            if backlog:
                while len(eng.sched.waiting) < depth:
                    offered.append(_submit(eng, pending, now, rid_base))
                    pending = next(source)
            else:
                while t0 + pending.due_s <= now:
                    due = t0 + pending.due_s
                    offered.append(_submit(eng, pending, due, rid_base))
                    late.append(now - due)
                    pending = next(source)
        if not eng.sched.idle:
            with tracer.span("engine_step"):
                eng.step()
        else:
            gap = t0 + pending.due_s - now
            with tracer.span("sleep"):
                time.sleep(max(0.0, min(gap, 0.05, t0 + seconds - now)))
    window_s = clock() - t0
    return offered, t0, window_s, late, traced_from


def drain(eng) -> float:
    """No new arrivals: step until every request offered has finished.
    Returns the seconds it took."""
    t0 = time.perf_counter()
    eng.run(raise_on_stall=False)
    return time.perf_counter() - t0


def sample_finished(offered, seed: int, n: int = SAMPLE_REQUESTS):
    """A seeded sample of the finished requests, the longest in it."""
    done = [r for r in offered if r.finish_reason in ("length", "eos")
            and r.generated]
    if not done:
        return []
    longest = max(done, key=lambda r: r.seq_len)
    rest = [r for r in done if r is not longest]
    rng = np.random.RandomState(seed % (2 ** 32))
    picked = [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [longest] + picked


def widest_gap(ctx, ref, params, sample, cast_name="exact"):
    """(widest gap of a served token below the reference's best,
    widest gap of the lower precision's first token, served tokens
    compared)."""
    import jax.numpy as jnp

    model = ctx.config["model"]
    s_max = model["max_position_embeddings"]
    n_max = int(ctx.mix["max_new"].get("hi") or
                max(ctx.mix["max_new"]["values"]))
    served_gap = low_gap = 0.0
    compared = 0
    for req in sample:
        seq = (req.prompt + req.generated)[:s_max]
        tokens = np.zeros((s_max,), np.int32)
        tokens[:len(seq)] = seq
        n = len(req.generated)
        served = np.zeros((n_max,), np.int32)
        served[:n] = req.generated
        # position first predicts the first served token; the n_max
        # rows from there lie inside the sequence because the engine
        # admits only prompt + max_new <= max_position
        first = len(req.prompt) - 1
        best, chosen, low = ref.served_gaps(
            params, jnp.asarray(tokens), np.int32(first), np.int32(n),
            jnp.asarray(served), heads=model["num_attention_heads"],
            cast_name=cast_name)
        best, chosen, low = (np.asarray(a)[:n] for a in (best, chosen, low))
        served_gap = max(served_gap, float(np.max(best - chosen)))
        low_gap = max(low_gap, float(np.max(best - low)))
        compared += n
    return served_gap, low_gap, compared


def latencies(offered, t_close: float, t_end: float) -> dict:
    """Per-request times in ms, on the engine's clock, of every request
    due in the window (``t_close``).  A request that was refused,
    failed or got no token by ``t_end`` (after the drain) counts as the
    worst there is: its whole wait until ``t_end``."""
    ttft, tpot, queue_wait = [], [], []
    for r in offered:
        if r.arrival_t > t_close:
            continue
        if r.first_token_t is None or r.finish_reason == "rejected":
            ttft.append((t_end - r.arrival_t) * 1e3)
            continue
        ttft.append((r.first_token_t - r.arrival_t) * 1e3)
        if r.admit_t is not None:
            queue_wait.append((r.admit_t - r.arrival_t) * 1e3)
        n = len(r.generated)
        if r.finish_t is not None and n > 1:
            tpot.append((r.finish_t - r.first_token_t) / (n - 1) * 1e3)
    return {"ttft_ms": ttft, "tpot_ms": tpot, "queue_wait_ms": queue_wait}


def generated_counts(offered) -> dict:
    return {r.rid: len(r.generated) for r in offered}


def work_done(offered, counts: dict, since: dict = None) -> dict:
    """Real prompt tokens prefilled and tokens decoded between the
    snapshots ``since`` and ``counts`` (:func:`generated_counts`), as
    lists of lengths for ``flops.py``: a prompt counts once, when its
    first token comes (a re-prefill after a preemption is not useful
    work); every generated token after a request's first was one
    decode row whose context, itself included, is the prompt plus the
    tokens generated before it plus one."""
    prompts, contexts = [], []
    since = since or {}
    for r in offered:
        n, n0 = counts.get(r.rid, 0), since.get(r.rid, 0)
        p = len(r.prompt)
        if n0 < 1 <= n:
            prompts.append(p)
        contexts.extend(p + i for i in range(max(n0, 1), n))
    return {"prompt_lens": prompts, "decode_kv_lens": contexts}


def run(ctx) -> harness.Result:
    import jax
    from apex_tpu.analysis import hot_path_guard

    eng, make_weights, ref, _ = build(ctx)
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
    if ctx.mix["loop"] == "open":
        failed += sum(1 for r in offered if r.finish_reason is None)
        e2e = {"tpot_p95_ms": harness.percentile(lat["tpot_ms"], 95)}
    else:
        e2e = {"serve_tokens_per_s": tokens_out / window_s}
    work = work_done(offered, in_window)
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
            "generator_late_ms_max": float(max(late, default=0.0)) * 1e3,
            "recompiles_in_window": recompiles,
            "max_batch": eng.max_batch,
            "prefill_row": eng.prefill_budget,
            "page_size": eng.cache.page_size, **work, **lat})
    if ctx.trace:
        result.trace, result.trace_window_ns, result.trace_window_s = \
            tracer.reduce()
        result.counters["traced"] = {
            "decode_steps": decode_at_close - traced_from[1],
            **work_done(offered, in_window, since=traced_from[0])}

    sample = sample_finished(offered, ctx.seed)
    n_sample = len(sample)
    del eng
    harness.free_device_memory()
    params = make_weights()
    gap, _, compared = widest_gap(ctx, ref, params, sample)
    result.counters["served_tokens_compared"] = compared
    result.checks = [
        harness.Check("served_logit_gap", gap if n_sample else float("nan"),
                      ctx.limits["served_logit_gap"]),
        harness.Check("recompiles_in_window", float(recompiles), 0.0),
    ]
    return result

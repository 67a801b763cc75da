"""The latent paged decode attention's share of its roofline: the
larger of the least time to read every distinct cached token's latent
vector once a layer and the least time to score it (278,528 FLOP a
cached token a row a layer: the FLOP term binds), for the traced decode
rows (``flops_deepseek_v2.py``), over the trace time of the
``flash_decode_latent`` kernels inside the decode executable, found by
the name the program gives them."""
import re

import flops_deepseek_v2 as flops
import trace_reduce

KERNEL = re.compile(r"^%flash_decode_latent(\.\d+)?$")


def is_latent_kernel(name: str) -> bool:
    return trace_reduce.is_pallas(name) \
        and bool(KERNEL.match(trace_reduce.op_head(name)))


def read(result, ctx):
    traced = result.counters.get("traced", {})
    kv_lens = traced.get("decode_kv_lens")
    if not kv_lens or "decode_shared_rows" not in traced:
        return None
    t0, t1 = result.trace_window_ns
    calls = [x for run in trace_reduce.ops_within(
        result.trace.devices[0], ctx.config["executables"]["decode"],
        is_latent_kernel)
        for x in run if x[1] >= t0 - 2e6 and x[1] + x[2] <= t1 + 2e6]
    if not calls:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    chips = ctx.config["chips"]
    distinct = flops.distinct_tokens(
        kv_lens, traced["decode_shared_rows"],
        result.counters["shared_len"])
    least = max(
        flops.latent_attention_bytes(m, distinct, itemsize=2) / chips
        / ctx.peak["hbm_bytes_per_s"],
        sum(flops.decode_attention_flops(m, k) for k in kv_lens) / chips
        / ctx.peak["bf16_flops_per_s"])
    return 100.0 * least / (sum(dur for _, _, dur in calls) / 1e9)

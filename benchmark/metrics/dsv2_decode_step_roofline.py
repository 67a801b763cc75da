"""The DeepSeek-V2 decode executable's share of its roofline: the
larger of the least time to read what the traced decode steps must read
(``flops_deepseek_v2.py``: every weight a step touches once, and the
latent vector of every DISTINCT cached token its rows read, once a
layer: a page ten rows share is one page) and the least time to compute
them (absorbed latent attention is compute-bound where rows share
pages), over the device time of those runs.  One bound for the whole
step, so that parts which overlap cannot read over 100%."""
import flops_deepseek_v2 as flops
import trace_reduce


def read(result, ctx):
    runs = trace_reduce.runs_between(
        result.trace, ctx.config["executables"]["decode"],
        result.trace_window_ns)
    traced = result.counters.get("traced", {})
    kv_lens = traced.get("decode_kv_lens")
    if not runs or not kv_lens or "decode_shared_rows" not in traced:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    chips = ctx.config["chips"]
    distinct = flops.distinct_tokens(
        kv_lens, traced["decode_shared_rows"],
        result.counters["shared_len"])
    nbytes = flops.decode_steps_bytes(m, len(runs), len(kv_lens), distinct,
                                      itemsize=2)
    work = sum(flops.decode_flops(m, k) for k in kv_lens)
    least = max(nbytes / chips / ctx.peak["hbm_bytes_per_s"],
                work / chips / ctx.peak["bf16_flops_per_s"])
    return 100.0 * least / (sum(dur for _, _, dur in runs) / 1e9)

"""The in-place state update's share of its roofline: the least time to
move what the ``ssm_decode_update`` calls of the traced decode steps
must move (``flops_granite_hybrid.py``: each real row's float32 state
in and out a state-space layer, and the vectors the kernel takes and
gives; the bytes bind, at 0.75 FLOP a byte), over the trace time of
those kernels inside the decode executable, found by the name the
program gives them.  The bytes counted go into the run's counters
(``ssm_decode_update_bytes``)."""
import re

import flops_granite_hybrid as flops
import trace_reduce

KERNEL = re.compile(r"^%ssm_decode_update(\.\d+)?$")


def is_update_kernel(name: str) -> bool:
    return trace_reduce.is_pallas(name) \
        and bool(KERNEL.match(trace_reduce.op_head(name)))


def read(result, ctx):
    traced = result.counters.get("traced", {})
    kv_lens = traced.get("decode_kv_lens")
    if not kv_lens or "state_slots" not in result.counters:
        return None
    t0, t1 = result.trace_window_ns
    calls = [x for run in trace_reduce.ops_within(
        result.trace.devices[0], ctx.config["executables"]["decode"],
        is_update_kernel)
        for x in run if x[1] >= t0 - 2e6 and x[1] + x[2] <= t1 + 2e6]
    if not calls:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    chips = ctx.config["chips"]
    # every traced decode row went through one call a state-space layer
    rows = len(kv_lens) * m.ssm_layers
    nbytes = flops.ssm_update_call_bytes(m, rows)
    result.counters["ssm_decode_update_bytes"] = nbytes
    least = max(nbytes / chips / ctx.peak["hbm_bytes_per_s"],
                flops.ssm_update_call_flops(m, rows) / chips
                / ctx.peak["bf16_flops_per_s"])
    return 100.0 * least / (sum(dur for _, _, dur in calls) / 1e9)

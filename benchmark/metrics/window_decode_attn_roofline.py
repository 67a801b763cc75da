"""The windowed paged decode attention's share of its roofline: least
time to read each traced decode row's K and V under the window, once a
window layer (``flops_afmoe.py``) over the trace time of the
``flash_decode_window`` kernels inside the decode executable, found by
the name the program gives them."""
import re

import flops_afmoe
import trace_reduce

KERNEL = re.compile(r"^%flash_decode_window(\.\d+)?$")


def is_window_kernel(name: str) -> bool:
    return trace_reduce.is_pallas(name) \
        and bool(KERNEL.match(trace_reduce.op_head(name)))


def read(result, ctx):
    traced = result.counters["traced"]
    if not traced["decode_kv_lens"]:
        return None
    t0, t1 = result.trace_window_ns
    calls = [x for run in trace_reduce.ops_within(
        result.trace.devices[0], ctx.config["executables"]["decode"],
        is_window_kernel)
        for x in run if x[1] >= t0 - 2e6 and x[1] + x[2] <= t1 + 2e6]
    if not calls:
        return None
    m = flops_afmoe.model_shape(flops_afmoe.model_of(ctx.config))
    nbytes = flops_afmoe.window_attention_bytes(
        m, traced["decode_kv_lens"], itemsize=2) / ctx.config["chips"]
    least = nbytes / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (sum(dur for _, _, dur in calls) / 1e9)

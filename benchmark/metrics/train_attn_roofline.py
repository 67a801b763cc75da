"""The packed-QKV attention kernels' share of their roofline in the
train step: least time of every forward and backward call (``flops.py``:
causal, compute-bound at d=128) over the trace time of those calls.

The kernels carry no name in the program yet, so they are found by
shape: Pallas calls (``tpu_custom_call``) inside the step's executable
that read the packed ``[batch, seq, 3 * hidden]`` projection and write
``[batch, seq, hidden]`` (forward) or write ``[batch, seq, 3 * hidden]``
(backward)."""
import flops
import trace_reduce


def read(result, ctx):
    s = result.counters["seq"]
    m = flops.model_shape(ctx.config["model"])
    dev = result.trace.devices[0]
    step = ctx.config["executables"]["step"]
    calls = [x for run in trace_reduce.ops_within(
        dev, step, trace_reduce.is_pallas) for x in run]
    fwd_t = bwd_t = 0.0
    n_fwd = n_bwd = 0
    shape = None
    for name, _, dur in calls:
        res = [d for _, d in trace_reduce.result_shapes(name)]
        ops = [d for _, d in trace_reduce.operand_shapes(name)]
        packed = [d for d in res + ops
                  if len(d) == 3 and d[1] == s
                  and d[2] % (3 * m.head_dim) == 0]
        if not packed:
            continue
        wide = packed[0]
        if any(d == wide for d in res):
            bwd_t += dur
            n_bwd += 1
        elif any(d == (wide[0], wide[1], wide[2] // 3) for d in res):
            fwd_t += dur
            n_fwd += 1
        else:
            continue
        shape = wide
    if not n_fwd or not n_bwd:
        return None
    rows, heads = shape[0], shape[2] // 3 // m.head_dim
    least = 0.0
    for n, backward in ((n_fwd, False), (n_bwd, True)):
        work = flops.attention_call(
            q_lens=[s] * rows, kv_lens=[s] * rows, heads=heads,
            head_dim=m.head_dim, itemsize=2, causal=True, backward=backward)
        least += n * flops.roofline_seconds(work, ctx.peak)["seconds"]
    return 100.0 * least / ((fwd_t + bwd_t) / 1e9)

"""Whole train step's share of the chips' bf16 peak: forward plus
backward FLOPs per token (``flops.py``, no recomputation) x tokens per
second of this run's window / chips / peak."""
import flops


def read(result, ctx):
    c = result.counters
    per_token = flops.train_flops_per_token(
        flops.model_shape(ctx.config["model"]), c["seq"])
    rate = c["steps"] * c["tokens_per_step"] / result.window_s
    return 100.0 * per_token * rate / c["chips"] / ctx.peak["bf16_flops_per_s"]

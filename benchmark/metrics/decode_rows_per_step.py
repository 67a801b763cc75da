"""Rows a decode step carried, on average over the window: tokens
decoded (every token after a request's first) / decode steps."""


def read(result, ctx):
    c = result.counters
    if not c["decode_steps"]:
        return None
    return len(c["decode_kv_lens"]) / c["decode_steps"]

"""Device time of the MLPs in one decode step: self time of
``jit__decode``'s instructions under ``moe_router``, ``moe_experts``,
``moe_shared`` or the dense layer's ``mlp``, mean over the traced runs
(``scope_time.py``)."""
from metrics import scope_time


def read(result, ctx):
    return scope_time.read_group(result, ctx, "decode",
                                 scope_time.DSV2_DECODE, "experts")

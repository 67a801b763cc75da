"""Device time of the short-convolution mixers in one decode step: self
time of ``jit__decode``'s instructions under ``conv_in_proj``,
``short_conv`` or ``conv_out_proj`` (the input projection and the
``B * x`` gate, the convolution against the slot's tail and the tail's
write back, the ``C`` gate and the output projection), mean over the
traced runs (``scope_time.py``)."""
from metrics import scope_time

CONV = (("conv", frozenset({"conv_in_proj", "short_conv",
                            "conv_out_proj"})),)


def read(result, ctx):
    if "state_slots" not in result.counters:
        return None
    return scope_time.read_group(result, ctx, "decode", CONV, "conv")

"""Device time of one train step that no scope of the program names:
self time of the step executable's instructions under none of
``fwd_bwd``, ``zero_pack``, ``zero_update``, ``zero_unpack``, mean over
the traced steps (``scope_time.py``).  What the three named phases
leave over, so that they add up to the step."""
from metrics import scope_time


def read(result, ctx):
    return scope_time.read_group(result, ctx, "step", scope_time.TRAIN_STEP,
                                 scope_time.REST)

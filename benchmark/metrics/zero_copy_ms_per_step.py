"""Device time of the ZeRO optimizer's copies in one train step: the
gradients into the flat buffer (scope ``zero_pack``) and the updated
buffer back into the parameters' leaves (``zero_unpack``), self time,
mean over the traced steps (``scope_time.py``)."""
from metrics import scope_time


def read(result, ctx):
    return scope_time.read_group(result, ctx, "step", scope_time.TRAIN_STEP,
                                 "zero_copy")

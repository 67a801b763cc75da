"""Device time of the ZeRO optimizer's update in one train step: self
time of the step executable's instructions under the scope
``zero_update`` (``distributed_fused.py``), mean over the traced steps,
through the program's scope map (``scope_time.py``)."""
from metrics import scope_time


def read(result, ctx):
    return scope_time.read_group(result, ctx, "step", scope_time.TRAIN_STEP,
                                 "zero_update")

"""apex_tpu.contrib.optimizers — ZeRO-style sharded optimizers
(reference apex/contrib/optimizers/)."""

from apex_tpu.contrib.optimizers.distributed_fused import (  # noqa: F401
    DistributedFusedAdam,
    DistributedFusedLAMB,
    DistributedShardedOptimizer,
    ShardedOptState,
    live_zero_state,
    reshard_zero_state,
    stacked_zero_state,
)

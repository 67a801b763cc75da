"""Gradient-bucket planner for the overlap-aware ZeRO step (ISSUE 15).

Reference lineage: DDP gradient bucketing (apex/parallel/distributed.py
— close a bucket when the next parameter would push it past the
``bucket_bytes`` cap, so backward can ship finished buckets while later
layers are still differentiating) and DistributedFusedAdam's chunked
reduce-scatter pipeline (contrib/optimizers/distributed_fused_adam.py:
316-362 — the flat grad buffer moves in fixed-size chunks, each chunk's
collective overlapping the next chunk's compute).

TPU mapping.  There are no grad hooks to drive per-bucket issue from —
the whole step is one XLA program — so the bucket plan is *structural*:
the monolithic ``psum_scatter``/``all_gather`` pair of the serialized
ZeRO step (contrib/optimizers/distributed_fused.py) is split into one
reduce-scatter + all-gather **per bucket**, and XLA's latency-hiding
scheduler interleaves the smaller collectives with backward/optimizer
compute instead of queueing one buffer-sized transfer behind all of it.
The ``python -m apex_tpu.analysis hlo`` contract pins the resulting
per-bucket inventory; the exposed-collective wall is not measured
(no cell of the benchmark spans chips yet).

Layout contract (the part that must NOT leak into checkpoints).  The
canonical ZeRO ownership is the C-order contract of
:mod:`apex_tpu.multi_tensor.flat`: rank ``r`` of a ``world``-way shard
owns the contiguous slice ``flat[r*S : (r+1)*S]`` with
``S = schema.total // world``.  A bucket here is a **span of the
per-rank shard** ``[lo, hi) ⊂ [0, S)`` — equivalently the column block
``flat.reshape(world, S)[:, lo:hi]`` of the canonical buffer.
Reduce-scattering that block (flattened rank-major) hands rank ``r``
exactly its canonical slice of the span, so the optimizer-state stack
stays in the canonical layout **for every bucket plan**: a format-4
checkpoint written under one plan restores bitwise under any other
(tests/L0/test_bucketed_zero.py pins the round trip).  The planner
still *thinks* in reference-DDP terms — leaves are walked in pack
order and a bucket closes at the cap — and each canonical boundary is
mapped onto the shard as ``offset // world`` rounded to the lane
width, so a bucket's shard span is its leaves' per-rank share.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from apex_tpu.multi_tensor.flat import FlatSchema

__all__ = ["BucketPlan", "DEFAULT_BUCKET_BYTES", "plan_buckets"]

#: Default bucket cap for the flagship step.  The reference DDP default
#: is 10 MB (apex/parallel/distributed.py ``message_size``); torch DDP
#: uses 25 MB.  32 MiB keeps the per-collective payload large enough to
#: stay bandwidth-bound on an ICI link while giving a 1.3B-param fp32
#: grad buffer (~5.3 GB) ~170 buckets of overlap opportunity.
DEFAULT_BUCKET_BYTES = 32 << 20

_LANE = 128  # TPU lane width; flat.py packs leaves at this alignment


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static, hashable bucket plan (safe to close over in jit).

    ``spans`` partition the per-rank shard ``[0, shard)`` in order;
    bucket ``b`` covers canonical elements ``r*shard + [lo, hi)`` on
    every rank ``r`` (see module docstring for the layout contract).
    """

    spans: Tuple[Tuple[int, int], ...]
    shard: int           # per-rank shard length S = total // world
    world: int
    bucket_bytes: Optional[int]  # the cap that produced the plan

    @property
    def num_buckets(self) -> int:
        return len(self.spans)

    def span_elements(self, b: int) -> int:
        lo, hi = self.spans[b]
        return hi - lo

    def collective_elements(self, b: int) -> int:
        """Elements moved by bucket ``b``'s reduce-scatter (and its
        all-gather): the whole column block, ``world`` shard spans."""
        return self.span_elements(b) * self.world

    def validate(self) -> None:
        pos = 0
        for lo, hi in self.spans:
            if lo != pos or hi <= lo:
                raise ValueError(
                    f"bucket spans must partition [0, {self.shard}) in "
                    f"order; got {self.spans}")
            pos = hi
        if pos != self.shard:
            raise ValueError(
                f"bucket spans cover [0, {pos}) but the shard is "
                f"[0, {self.shard})")


def plan_buckets(schema: FlatSchema, world: int, *,
                 bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
                 itemsize: int = 4,
                 span_align: int = _LANE) -> BucketPlan:
    """Partition ``schema``'s superblock into size-targeted buckets.

    Reference-DDP cap semantics over the canonical pack order: leaves
    accumulate into the current bucket until adding the next leaf's
    padded bytes would exceed ``bucket_bytes`` (a bucket always takes
    at least one leaf, so a single oversized leaf becomes its own
    bucket — ``bucket_bytes=1`` is the one-param-per-bucket edge).
    ``bucket_bytes=None`` produces the single-bucket plan, which is
    exactly the serialized ZeRO data path (one monolithic
    reduce-scatter + all-gather).

    Each canonical bucket boundary is then mapped to the per-rank
    shard as ``boundary // world`` rounded down to ``span_align``
    (default: the 128 lane width; the Pallas flat-Adam path wants
    ``8*128`` sublane rows), so tiny adjacent leaves may merge into
    one span (their per-rank share is below one alignment row) — the
    plan never has more than ``shard // span_align`` buckets.
    ``itemsize`` is the grad transport dtype's byte width (the
    reduce-scatter payload the cap governs).
    """
    world = int(world)
    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    span_align = int(span_align)
    if span_align < _LANE or span_align % _LANE:
        raise ValueError(
            f"span_align must be a multiple of the {_LANE} lane width, "
            f"got {span_align}")
    if schema.total % world:
        raise ValueError(
            f"schema.total={schema.total} does not divide world={world}"
            " — pack with make_schema(total_multiple_of=128*world)")
    shard = schema.total // world
    if shard % span_align:
        raise ValueError(
            f"per-rank shard {shard} is not aligned (multiple of "
            f"{span_align}); pack with make_schema(total_multiple_of="
            f"{span_align}*world)")
    if bucket_bytes is None:
        return BucketPlan(spans=((0, shard),), shard=shard, world=world,
                          bucket_bytes=None)
    bucket_bytes = int(bucket_bytes)
    if bucket_bytes < 1:
        raise ValueError(f"bucket_bytes must be >= 1, got {bucket_bytes}")

    # canonical bucket boundaries at padded-leaf granularity (DDP cap)
    boundaries = []  # canonical end offsets of closed buckets
    cur_bytes = 0
    n = schema.num_tensors
    for i in range(n):
        end = schema.offsets[i + 1] if i + 1 < n else schema.total
        padded = (end - schema.offsets[i]) * itemsize
        if cur_bytes and cur_bytes + padded > bucket_bytes:
            boundaries.append(schema.offsets[i])
            cur_bytes = 0
        cur_bytes += padded

    # map canonical boundaries onto the per-rank shard (lane-rounded);
    # dedupe collapsed spans, always close the final span at `shard`
    cuts = [0]
    for b in boundaries:
        x = b // world // span_align * span_align
        if x > cuts[-1] and x < shard:
            cuts.append(x)
    cuts.append(shard)
    spans = tuple((cuts[i], cuts[i + 1]) for i in range(len(cuts) - 1))
    plan = BucketPlan(spans=spans, shard=shard, world=world,
                      bucket_bytes=bucket_bytes)
    plan.validate()
    return plan

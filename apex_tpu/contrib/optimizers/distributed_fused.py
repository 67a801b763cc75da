"""ZeRO-style sharded optimizers over the mesh "data" axis.

TPU-native re-design of the reference's sharded distributed optimizers:

* ``DistributedFusedAdam`` v1-v3
  (reference apex/contrib/optimizers/distributed_fused_adam.py:9-636),
* ``DistributedFusedLAMB``
  (reference apex/contrib/optimizers/distributed_fused_lamb.py:10-975).

Reference architecture: the flat fp16 grad buffer is split into
blocks→chunks→shards (distributed_fused_lamb.py:364-434); per-block
reduce-scatters overlap with backward via grad hooks (:316-362); each rank
runs the optimizer on its shard; updated param shards are all-gathered
(optionally e5m2-compressed).

TPU mapping — the communication pattern survives, the machinery dissolves:

* flat buffer        → one packed superblock (:mod:`apex_tpu.multi_tensor.flat`),
  padded so its length divides the shard count;
* chunked reduce-scatter + hooks → a single ``lax.psum_scatter`` inside the
  jitted step (XLA's scheduler overlaps it with the backward);
* sharded Adam/LAMB step → the fused update on this rank's shard slice;
* allgather of updated shards → ``lax.all_gather(tiled=True)``, optionally
  through an e5m2 cast (same 8-bit-exponent format as the reference's
  compressed allgather);
* LAMB's global grad-norm prepass (fused_lamb.py:121-136) → shard-local
  square-sum + one extra psum term fused into the same step.

Must run inside a region binding ``axis_name`` (shard_map over the mesh).
Optimizer state lives ONLY for this rank's shard — memory per device is
``params + 2·params/N`` instead of ``3·params`` (the ZeRO claim).

Memory-fit knobs (r6, the GPT-1.3B flagship — ISSUE 2): at 1.3B params a
16 GB chip cannot hold fp32 p+g+m+v (21 GB), so the flat-buffer dtypes
are configurable the way the reference's are:

* ``scatter_dtype`` — the flat grad buffer / reduce-scatter transport
  (the reference reduce-scatters its fp16 flat grad buffer,
  distributed_fused_adam.py:316-362); ``None`` keeps fp32.
* ``gather_dtype`` — the updated-shard all_gather transport; ``None``
  keeps fp32.  With bf16 model params, gathering in bf16 halves both
  the transport and the full-parameter transient (the update math still
  runs fp32 inside the fused elementwise chain — only the *stored*
  buffers narrow).
* ``exp_avg_dtype`` — first-moment storage.  bf16 halves the momentum
  buffer (1.3 GB/10⁹ params); the variance stays fp32 (its dynamic
  range IS the adaptive step size — narrowing it changes the update far
  more than momentum rounding does).

All default to the r5 behavior (fp32 everywhere): existing callers and
the parity tests are unchanged.  The analytic fitting table behind the
choices is in ``transformer/testing/flagship.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.multi_tensor.flat import FlatSchema, flatten, make_schema, unflatten


def _pack(tree, schema: FlatSchema, dtype):
    """:func:`flatten` into a superblock that stays a buffer of its own
    (and :func:`_unpack`, the same for :func:`unflatten`'s input).

    The step is pack -> elementwise update -> unpack.  Left free, XLA
    moves the update's fp32 converts back through the pack and the
    unpack's per-leaf reshapes back through the update, so the update
    runs on full-size fp32 copies of the superblock, once per leaf
    width, each relaid from the flat tiling to a 2-D one.  Compiled for
    one v5e chip (jax 0.9.0, libtpu 0.0.34, PR 22) the six-layer
    flagship step then asks for 14.0 GB and 200 s of compilation
    against 5.9 GB and 7 s with the superblocks pinned, and the
    four-layer (2, 2, 1) bucketed step for 590 s against 12.  (At
    dp=4 the single-axis step's whole-buffer collectives happened to
    pin the buffers already; a world of one has no collectives and the
    bucketed step's are per slice.)"""
    with jax.named_scope("zero_pack"):
        return jax.lax.optimization_barrier(
            flatten(tree, schema, dtype=dtype)[0])


def _unpack(flat, schema: FlatSchema):
    with jax.named_scope("zero_unpack"):
        return unflatten(jax.lax.optimization_barrier(flat), schema)


class ShardedOptState(NamedTuple):
    """One rank's state as :meth:`DistributedShardedOptimizer.step`
    takes and returns it.  The same tuple holds a whole mesh's state
    outside the step, in one of two forms: LIVE (what the flagship
    train step carries: ``step`` a ``[*lead]`` stack, each moment 1-D
    ``[world * shard]``) and STACKED (the interchange form of sharded
    checkpoints and reshards: each moment ``[*lead, shard]``);
    :func:`stacked_zero_state` / :func:`live_zero_state` go between."""

    step: jnp.ndarray  # i32 scalar
    exp_avg: jnp.ndarray  # [shard] exp_avg_dtype (momentum)
    exp_avg_sq: jnp.ndarray  # [shard] f32 (2nd moment)


def _map_zero_states(fn, tree):
    """``fn`` over every :class:`ShardedOptState` in ``tree``, which
    may be one itself; other leaves pass through."""
    is_state = lambda x: isinstance(x, ShardedOptState)  # noqa: E731
    return jax.tree_util.tree_map(
        lambda x: fn(x) if is_state(x) else x, tree, is_leaf=is_state)


def stacked_zero_state(tree):
    """The INTERCHANGE view of every live :class:`ShardedOptState` in
    ``tree`` (a state itself, or a ``(params, opt_state)`` pair; other
    leaves pass through): host NumPy arrays, each moment as the
    ``[*lead, shard]`` stack of per-rank partitions, ``lead`` being the
    ``step`` counter's shape (``[n_shards]``, or ``[dp, pp, tp]`` on a
    three-axis mesh).

    The train step carries a moment as ONE 1-D array, the ranks' shards
    laid end to end in linearized rank order, so that a device's piece
    is ``[shard]``, the shape the update runs on (on the TPU a
    ``[1, shard]`` piece is tiled differently, and squeezing it cost a
    pass over the state a step: PERF.md, PR 38).  That array is byte
    for byte the C-order flattening of the stack, so this view is a
    host-side ``reshape``, made once a save or a mesh rebuild and never
    in a step.  Sharded checkpoints (``save_checkpoint(shard_axis= /
    shard_axes=)``), :func:`reshard_zero_state` and
    :func:`apex_tpu.multi_tensor.flat.reshard_stack` define a leaf by
    its leading stack axes: they take this view.
    :func:`live_zero_state` takes it back."""
    def view(state):
        step = np.asarray(jax.device_get(state.step))
        return ShardedOptState(step, *(
            np.asarray(jax.device_get(a)).reshape(*step.shape, -1)
            for a in (state.exp_avg, state.exp_avg_sq)))

    return _map_zero_states(view, tree)


def live_zero_state(tree, like=None):
    """Inverse of :func:`stacked_zero_state`: every stacked
    :class:`ShardedOptState` in ``tree`` back in the form the train
    step carries (moments 1-D, the counter as it was).  ``like`` — a
    live tree of the same structure (``fs.opt_state``, or the
    ``(params, opt_state)`` pair): each array is placed with the
    sharding of its counterpart there, every device receiving only its
    own slice; without it the arrays stay uncommitted and the step
    places them on its first call."""
    def live(state):
        return ShardedOptState(
            np.asarray(jax.device_get(state.step)),
            *(np.asarray(jax.device_get(a)).reshape(-1)
              for a in (state.exp_avg, state.exp_avg_sq)))

    out = _map_zero_states(live, tree)
    if like is None:
        return jax.tree_util.tree_map(jnp.asarray, out)
    # straight from the host to each device's own slice
    return jax.tree_util.tree_map(
        lambda a, ref: jax.device_put(a, ref.sharding), out, like)


def reshard_zero_state(opt_state: ShardedOptState, *,
                       n_shards: Optional[int] = None,
                       schema: FlatSchema,
                       lead_shape=None) -> ShardedOptState:
    """Re-partition a STACKED per-rank :class:`ShardedOptState` (leading
    stack axes on every leaf: the interchange form,
    :func:`stacked_zero_state` of what the flagship train step carries)
    onto a new topology — the in-memory half of the elastic
    cross-topology story (the on-disk half lives in
    ``checkpoint.restore_checkpoint``'s sharded-manifest reshard).  The
    result is stacked too; :func:`live_zero_state` hands it to the new
    topology's step.

    ``n_shards`` — single-axis form: the leading ``[old_n]`` stack
    re-partitions to ``[n_shards, total/n_shards]``.  ``lead_shape`` —
    multi-axis form (e.g. ``(dp, pp, tp)``): the flat leaves re-stack to
    ``[*lead_shape, total/prod(lead_shape)]``, linearizing the old stack
    axes in C order (the linearized-world ZeRO layout).  Either way the
    flat-buffer leaves (``exp_avg``/``exp_avg_sq``) concatenate in rank
    order to the logical superblock, then re-split against the TARGET
    ``schema`` (whose ``total`` is padded to ``128·world`` — per-leaf
    offsets are topology-invariant, only the tail padding moves, so
    growth zero-fills and shrinkage may drop only all-zero tail padding;
    dropping real state raises).  The broadcast ``step`` counter
    re-broadcasts coordinate 0.  Host-side numpy — this runs once per
    mesh rebuild, not per step; routes through
    :func:`apex_tpu.multi_tensor.flat.reshard_stack`, the same
    implementation the checkpoint reshard uses."""
    from apex_tpu.multi_tensor.flat import reshard_stack

    if lead_shape is None:
        if n_shards is None:
            raise ValueError("pass n_shards or lead_shape")
        lead_shape = (int(n_shards),)
    lead_shape = tuple(int(x) for x in lead_shape)
    world = int(np.prod(lead_shape))
    shard = schema.total // world
    old_step = np.asarray(jax.device_get(opt_state.step))
    n_lead_old = old_step.ndim  # step content is scalar per rank

    def _flat(leaf) -> jnp.ndarray:
        a = np.asarray(jax.device_get(leaf))
        out = reshard_stack(a, n_lead_old, (*lead_shape, shard),
                            label=f"opt shard stack ({old_step.shape}->"
                                  f"{lead_shape})")
        return jnp.asarray(out)

    return ShardedOptState(
        step=jnp.asarray(reshard_stack(old_step, n_lead_old, lead_shape,
                                       replicated=True,
                                       label="opt step counter")),
        exp_avg=_flat(opt_state.exp_avg),
        exp_avg_sq=_flat(opt_state.exp_avg_sq),
    )


@dataclasses.dataclass(frozen=True)
class DistributedShardedOptimizer:
    """Common psum_scatter → sharded-update → all_gather engine."""

    lr: float = 1e-3
    betas: Tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-8
    weight_decay: float = 0.0
    bias_correction: bool = True
    # multi-axis meshes: ``axis_name`` may be a TUPLE of mesh axes (the
    # linearized-world ZeRO layout — shards/collectives span the whole
    # dp×pp×tp block; the caller feeds REPLICATED global grads, which
    # the mesh-wide psum_scatter sums world-fold and ``grad_average``
    # divides back out — exact for power-of-two worlds)
    axis_name: Any = "data"
    grad_average: bool = True
    e5m2_allgather: bool = False  # reference distributed_fused_lamb.py:93
    # memory-fit knobs (see module docstring); None = fp32 (r5 behavior)
    scatter_dtype: Optional[Any] = None
    gather_dtype: Optional[Any] = None
    exp_avg_dtype: Any = jnp.float32

    # -- host-side setup -----------------------------------------------------

    def make_schema(self, params, n_shards: int) -> FlatSchema:
        """Pack layout whose total length divides ``n_shards``
        (the block/chunk/shard alignment of the reference, :364-434)."""
        return make_schema(params, align=128,
                           total_multiple_of=128 * n_shards)

    def init(self, params, schema: FlatSchema, n_shards: int) -> ShardedOptState:
        """Per-rank shard state (call inside shard_map, or once per rank)."""
        shard = schema.total // n_shards
        return ShardedOptState(
            step=jnp.zeros((), jnp.int32),
            exp_avg=jnp.zeros((shard,), self.exp_avg_dtype),
            exp_avg_sq=jnp.zeros((shard,), jnp.float32),
        )

    # -- the sharded step ----------------------------------------------------

    def _shard_update(self, p, g, state, lr):
        raise NotImplementedError

    def step(self, grads, state: ShardedOptState, params,
             schema: FlatSchema):
        """One ZeRO step; call inside shard_map binding ``axis_name``.

        Returns ``(new_params, new_state)`` with new_params identical
        (bitwise) on every rank of the axis.
        """
        world = jax.lax.psum(1, self.axis_name)
        rank = jax.lax.axis_index(self.axis_name)
        shard = schema.total // world

        flat_g = _pack(grads, schema, self.scatter_dtype or jnp.float32)
        # reduce-scatter: each rank receives the summed shard it owns
        # (in scatter_dtype — the reference's fp16 flat grad buffer);
        # the update math upcasts to fp32 inside the fused chain
        g_shard = jax.lax.psum_scatter(flat_g, self.axis_name,
                                       tiled=True).astype(jnp.float32)
        if self.grad_average:
            g_shard = g_shard / world

        # e5m2 delta transport needs the fp32 base regardless of
        # gather_dtype (the compressed delta is the transport narrowing)
        flat_dtype = (jnp.float32 if self.e5m2_allgather
                      else self.gather_dtype or jnp.float32)
        flat_p = _pack(params, schema, flat_dtype)
        p_shard = jax.lax.dynamic_slice_in_dim(
            flat_p, rank * shard, shard).astype(jnp.float32)

        with jax.named_scope("zero_update"):
            new_p_shard, new_state = self._shard_update(
                p_shard, g_shard, state, flat_g)

        if self.e5m2_allgather:
            # 8-bit-exponent compressed transport (reference e5m2_allgather):
            # ship the *delta* in e5m2 so the fp32 base is preserved
            delta = (new_p_shard - p_shard).astype(jnp.float8_e5m2)
            gathered = jax.lax.all_gather(delta, self.axis_name, axis=0,
                                          tiled=True).astype(jnp.float32)
            new_flat_p = flat_p + gathered
        else:
            new_flat_p = jax.lax.all_gather(
                new_p_shard.astype(flat_dtype), self.axis_name,
                axis=0, tiled=True)
        return _unpack(new_flat_p, schema), new_state

    def step_buckets(self, partial_grads, state: ShardedOptState, params,
                     schema: FlatSchema, plan):
        """Bucketed-overlap twin of :meth:`step` (ISSUE 15; reference
        DistributedFusedAdam's chunked reduce-scatter pipeline,
        distributed_fused_adam.py:316-362).  Call inside shard_map
        binding ``axis_name``.

        Two deliberate differences from :meth:`step`:

        * ``partial_grads`` are this device's UNSUMMED local grads —
          the grad of the device's *local* mean loss w.r.t. the full
          replicated master, taken inside the region.  The summing
          happens in the per-bucket reduce-scatter itself, which is
          the whole point: the per-leaf boundary all-reduces a
          replicated master grad costs (world × the grad bytes, fully
          serialized before the optimizer can start) never exist.
          Under the unreplicated-cotangent convention the mesh-sum of
          those partials is exactly ``world ×`` the grad of the
          data-mean loss — the same normalization :meth:`step` sees
          from ``world`` replicated copies — so ``grad_average``
          divides the same ``world`` back out (exact for power-of-two
          worlds; parity vs the serialized step is pinned bitwise in
          tests/L0/test_bucketed_zero.py).
        * the monolithic psum_scatter/all_gather pair becomes one
          reduce-scatter + all-gather per ``plan`` bucket.  A bucket is
          a span of the per-rank shard (a column block of the
          ``[world, shard]`` view — multi_tensor/buckets.py layout
          contract), so rank ``r`` receives exactly its canonical
          slice of every bucket and the returned state layout is
          IDENTICAL to :meth:`step`'s for every plan: bucket geometry
          cannot leak into the checkpoint/reshard contract.

        ``e5m2_allgather`` is not supported here (the delta transport
        needs the fp32 base resident across the whole gather — exactly
        the transient bucketing exists to retire); use :meth:`step`.
        """
        if self.e5m2_allgather:
            raise NotImplementedError(
                "e5m2_allgather is not supported by the bucketed step; "
                "use step() for the compressed-delta transport")
        world = jax.lax.psum(1, self.axis_name)
        rank = jax.lax.axis_index(self.axis_name)
        # axis sizes are static, so this catches a stale plan (e.g.
        # cached across an elastic mesh reshape) at trace time instead
        # of as an opaque XLA shape error inside the gather
        if plan.world != world or plan.shard != schema.total // world:
            raise ValueError(
                f"bucket plan (world={plan.world}, shard={plan.shard}) "
                f"does not match this axis: world={world}, shard="
                f"{schema.total // world} — re-plan after a mesh change")
        # hand-built plans are allowed (the registry builds one):
        # a permuted/gapped span set would reassemble the concat in
        # the wrong order with no shape error — refuse at trace time
        plan.validate()
        shard = plan.shard

        flat_g = _pack(partial_grads, schema,
                       self.scatter_dtype or jnp.float32)
        flat_dtype = self.gather_dtype or jnp.float32
        flat_p = _pack(params, schema, flat_dtype)
        # the canonical [world, shard] view: column block [:, lo:hi]
        # flattened rank-major is bucket b's reduce-scatter payload
        g_view = flat_g.reshape(plan.world, shard)

        new_m, new_v, new_cols = [], [], []
        for lo, hi in plan.spans:
            k = hi - lo
            g_b = jax.lax.psum_scatter(
                g_view[:, lo:hi].reshape(-1), self.axis_name,
                tiled=True).astype(jnp.float32)
            if self.grad_average:
                g_b = g_b / world
            p_b = jax.lax.dynamic_slice_in_dim(
                flat_p, rank * shard + lo, k).astype(jnp.float32)
            m_b = jax.lax.dynamic_slice_in_dim(state.exp_avg, lo, k)
            v_b = jax.lax.dynamic_slice_in_dim(state.exp_avg_sq, lo, k)
            # every bucket updates off the same pre-step counter;
            # _shard_update increments internally, so each bucket's
            # bias correction sees the identical step number
            sub = ShardedOptState(state.step, m_b, v_b)
            with jax.named_scope("zero_update"):
                new_p_b, sub = self._shard_update(p_b, g_b, sub, None)
            new_m.append(sub.exp_avg)
            new_v.append(sub.exp_avg_sq)
            gathered = jax.lax.all_gather(
                new_p_b.astype(flat_dtype), self.axis_name,
                axis=0, tiled=True)
            new_cols.append(gathered.reshape(plan.world, k))

        new_state = ShardedOptState(
            step=state.step + 1,
            exp_avg=jnp.concatenate(new_m),
            exp_avg_sq=jnp.concatenate(new_v))
        new_flat_p = jnp.concatenate(new_cols, axis=1).reshape(-1)
        return _unpack(new_flat_p, schema), new_state


@dataclasses.dataclass(frozen=True)
class DistributedFusedAdam(DistributedShardedOptimizer):
    """Sharded AdamW (reference distributed_fused_adam.py:9; the update math
    is multi_tensor_distopt_adam_kernel.cu's)."""

    adam_w_mode: bool = True

    def _shard_update(self, p, g, state, flat_g):
        del flat_g
        b1, b2 = self.betas
        step = state.step + 1
        if not self.adam_w_mode:
            # classic-Adam mode: L2-style decay folded into the gradient
            # before the moment updates (reference non-AdamW branch)
            g = g + self.weight_decay * p
        # moments compute in fp32 and store in exp_avg_dtype: the
        # rounding happens once per step on the stored value only
        m = b1 * state.exp_avg.astype(jnp.float32) + (1 - b1) * g
        v = b2 * state.exp_avg_sq + (1 - b2) * g * g
        if self.bias_correction:
            c1 = 1 - b1 ** step.astype(jnp.float32)
            c2 = 1 - b2 ** step.astype(jnp.float32)
        else:
            c1 = c2 = 1.0
        update = (m / c1) / (jnp.sqrt(v / c2) + self.eps)
        if self.adam_w_mode:
            update = update + self.weight_decay * p
        new_p = p - self.lr * update
        return new_p, ShardedOptState(step, m.astype(self.exp_avg_dtype), v)


@dataclasses.dataclass(frozen=True)
class DistributedFusedLAMB(DistributedShardedOptimizer):
    """Sharded LAMB (reference distributed_fused_lamb.py:10): global grad
    norm for clipping, per-shard trust ratio over the shard's param/update
    norms.

    Divergence note: the reference computes the trust ratio per *tensor*
    (multi_tensor_lamb_compute_update_term); sharded layout makes per-shard
    the natural granularity here.  Per-tensor trust ratios remain available
    via the unsharded :class:`apex_tpu.optimizers.FusedLAMB`.
    """

    max_grad_norm: float = 1.0
    weight_decay: float = 0.01

    def step_buckets(self, partial_grads, state, params, schema, plan):
        """LAMB's global grad-norm prepass needs the WHOLE grad before
        any shard can clip — under bucketing that norm would silently
        become per-bucket (a different optimizer).  Refuse rather than
        diverge; the bucketed flagship path is Adam's."""
        raise NotImplementedError(
            "DistributedFusedLAMB has a global grad-norm prepass that "
            "a per-bucket pipeline cannot honor; use step(), or "
            "DistributedFusedAdam for the bucketed path")

    def _shard_update(self, p, g, state, flat_g):
        b1, b2 = self.betas
        step = state.step + 1
        # global grad norm: shard-local square-sum, psum'd (the reference's
        # fused L2-norm prepass + allreduce, distributed_fused_lamb.py:592)
        local_sq = jnp.sum(g * g)
        global_norm = jnp.sqrt(jax.lax.psum(local_sq, self.axis_name))
        if self.max_grad_norm > 0:
            clip = jnp.maximum(1.0, global_norm / self.max_grad_norm)
            g = g / clip
        m = b1 * state.exp_avg.astype(jnp.float32) + (1 - b1) * g
        v = b2 * state.exp_avg_sq + (1 - b2) * g * g
        if self.bias_correction:
            c1 = 1 - b1 ** step.astype(jnp.float32)
            c2 = 1 - b2 ** step.astype(jnp.float32)
        else:
            c1 = c2 = 1.0
        update = (m / c1) / (jnp.sqrt(v / c2) + self.eps)
        update = update + self.weight_decay * p
        p_norm = jnp.linalg.norm(p)
        u_norm = jnp.linalg.norm(update)
        trust = jnp.where((p_norm > 0) & (u_norm > 0), p_norm / u_norm, 1.0)
        new_p = p - self.lr * trust * update
        return new_p, ShardedOptState(step, m.astype(self.exp_avg_dtype), v)

"""GPT-1.3B-class flagship: configuration + ZeRO-fit train step.

The benched flagship was pinned for five rounds to GPT-350M (h=1024,
16 heads → d=64), a shape whose head dim half-fills the MXU contraction
lanes and caps attention at the measured 54.9 TF dot floor (r5).  This
module stands up the shape the hardware likes — **h=2048,
16 heads → d=128, seq 2048** (~1.32 B params with the 51200 vocab) —
as a first-class configuration, plus the memory-fit machinery a 1.3B
model needs on a 16 GB chip.

Following ZeRO (Rajbhandari et al., 2020), the train step wires
:class:`apex_tpu.contrib.optimizers.DistributedFusedAdam` — psum_scatter
→ sharded update → all_gather — over the mesh "data" axis, so fp32
moments live once per shard group instead of once per replica.  The
same step runs unchanged from 1 chip (world=1: the collectives are
identity and the *dtype plan* does the fitting) to a v5e-16 pod slice
(world=N: state is N-way sharded as well).  Since ISSUE 15 the
mesh_shape=(dp, tp, pp) step defaults to the **bucketed-overlap**
data path — per-bucket reduce-scatter/all-gather over partial grads,
``step_buckets`` + :func:`apex_tpu.multi_tensor.plan_buckets` — see
:func:`build_flagship_train_step`'s ``bucket_bytes`` notes and
docs/performance.md "Overlap-aware ZeRO".

Fit plans — why a 15.75-GiB (16.9e9-byte) chip needs one (1.32 B
params; bytes in GB, world=1).  The table is ANALYTIC:

=============  ======  =====  =========  ==================  ========
plan           params  grads  m / v      optimizer-phase     under
                                         peak (see note)     16.9 GB?
=============  ======  =====  =========  ==================  ========
fp32           5.3     5.3    5.3 / 5.3  26.4 GB             no
bf16_fp32m     2.6     2.6    5.3 / 5.3  18.5 GB             no
bf16_fit       2.6     2.6    2.6 / 5.3  15.8 GB             yes
=============  ======  =====  =========  ==================  ========

Peak note: the ZeRO step packs grads and params into flat superblocks,
so the optimizer-phase live set is m + v + flat grads + 2× flat params
(old tree and grad tree freed by donation — ``donate=True`` below is
load-bearing, not an optimization).  :func:`flagship_state_bytes`
computes both columns.  The compiler does not reach the analytic peak:
on jax 0.9.0 / libtpu 0.0.34, XLA's memory report for the 24-layer
``bf16_fit`` step on one v5e chip asks for 24.6 GiB of the chip's
15.75, at batch 4 and at batch 1 alike (arguments 12.3, program 12.3),
and 13 layers at full width are the most it places (PR 22; PERF.md,
"Where the time goes").

``bf16_fit`` keeps the variance (the adaptive step size) fp32 and
narrows params/grads/momentum to bf16; the update math itself always
runs fp32 inside the fused elementwise chain (see
``contrib/optimizers/distributed_fused.py``).  Parity vs the unsharded
fp32 FusedAdam is asserted on the emulated mesh in
``tests/L0/test_flagship.py`` (max|dw| ≤ 1e-3 — ISSUE 2 acceptance).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.contrib.optimizers import DistributedFusedAdam, ShardedOptState
from apex_tpu.multi_tensor.buckets import DEFAULT_BUCKET_BYTES, plan_buckets
from apex_tpu.telemetry import scopes
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing.standalone_gpt import GPTConfig, GPTModel

__all__ = [
    "GPT1P3B_KW",
    "ZeroFitPlan",
    "FIT_PLANS",
    "gpt1p3b_config",
    "gpt_param_count",
    "flagship_state_bytes",
    "build_flagship_train_step",
    "flagship_elastic_build",
    "FlagshipSetup",
]


# The flagship shape (ISSUE 2): 16 heads at h=2048 give d=128 — full MXU
# contraction-lane fill, which d=64 leaves half empty (PERF.md:
# ``train_attn_roofline``).  Block 256: the packed-QKV kernels'
# whole-sequence working set at 3·128 lanes exceeds the VMEM budget at
# the 512 library default but fits at 256 (ops.attention._qkv_packed_block shrinks
# automatically; the config pins it so the routing is explicit).
GPT1P3B_KW = dict(
    num_layers=24,
    hidden_size=2048,
    num_attention_heads=16,
    vocab_size=51200,
    max_position_embeddings=2048,
    bf16=True,
    use_flash_attention=True,
    remat=True,
    remat_policy="attn_res",
    flash_block_q=256,
    flash_block_k=256,
)


def gpt1p3b_config(**overrides) -> GPTConfig:
    """The 1.3B flagship :class:`GPTConfig`; ``overrides`` for toy-depth
    test/trajectory variants (keep ``hidden_size / num_attention_heads
    = 128`` when shrinking, so the d=128 kernel routing stays the one
    under test)."""
    return GPTConfig(**{**GPT1P3B_KW, **overrides})


def gpt_param_count(cfg: GPTConfig) -> int:
    """Analytic parameter count of the standalone GPT (biases and
    layernorms included): per layer 12h² GEMM weights + 13h vectors,
    plus word/position embeddings and the final layernorm."""
    h, L = cfg.hidden_size, cfg.num_layers
    per_layer = 12 * h * h + 13 * h
    return (L * per_layer
            + (cfg.vocab_size + cfg.max_position_embeddings) * h
            + 2 * h)


@dataclasses.dataclass(frozen=True)
class ZeroFitPlan:
    """Storage dtypes for the ZeRO step (see module table)."""

    name: str
    param_dtype: Any
    exp_avg_dtype: Any
    scatter_dtype: Optional[Any]  # flat-grad / reduce-scatter transport
    gather_dtype: Optional[Any]   # updated-shard all_gather transport


FIT_PLANS = {
    # fp32 everything — the r5 350M construction; does NOT fit 1.3B on
    # one 16 GB chip (kept for parity tests and small models)
    "fp32": ZeroFitPlan("fp32", jnp.float32, jnp.float32, None, None),
    # bf16 params/transport, both moments fp32 — 15.8 GB of state+grads
    # at 1.3B: still over the single-chip budget, fits at world ≥ 2
    "bf16_fp32m": ZeroFitPlan("bf16_fp32m", jnp.bfloat16, jnp.float32,
                              jnp.bfloat16, jnp.bfloat16),
    # the single-chip 1.3B fit: bf16 momentum as well; variance stays
    # fp32 (it IS the adaptive step size — see distributed_fused.py)
    "bf16_fit": ZeroFitPlan("bf16_fit", jnp.bfloat16, jnp.bfloat16,
                            jnp.bfloat16, jnp.bfloat16),
}


def flagship_state_bytes(cfg: GPTConfig, plan: ZeroFitPlan,
                         n_shards: int = 1) -> dict:
    """Analytic persistent-state + grad bytes for the fitting table
    of the module docstring; activations/logits excluded."""
    n = gpt_param_count(cfg)
    it = lambda d: jnp.dtype(d).itemsize
    out = {
        "params": n * it(plan.param_dtype),
        "grads": n * it(plan.scatter_dtype or jnp.float32),
        "exp_avg": n * it(plan.exp_avg_dtype) // n_shards,
        "exp_avg_sq": n * 4 // n_shards,
    }
    out["total"] = sum(out.values())
    # optimizer-phase live set (module docstring "peak note"): with the
    # param and grad TREES donated/freed, the step holds moments + the
    # flat grad buffer + old and new flat param buffers at once
    flat_param = n * it(plan.gather_dtype or jnp.float32)
    out["step_peak"] = (out["exp_avg"] + out["exp_avg_sq"]
                        + out["grads"] + 2 * flat_param)
    return out


class FlagshipSetup(NamedTuple):
    """Everything the bench/tests need from one flagship construction."""

    step: Any          # jitted (params, opt_state, tokens, labels) -> …
    params: Any        # pytree in plan.param_dtype
    # the ZeRO state in its LIVE form, committed to the mesh: each
    # moment ONE 1-D array [world * shard], the ranks' shards laid end
    # to end in linearized rank order and sharded so that a device
    # holds exactly its [shard] (the shape the update runs on: nothing
    # is squeezed or re-laid out in the step); the step counter a
    # [n_shards] (3-D mesh: [dp, pp, tp]) stack of int32.  Sharded
    # saves and reshards take its stacked view, [*lead, shard]:
    # contrib.optimizers.stacked_zero_state / live_zero_state.
    opt_state: Any
    mesh: Any
    schema: Any
    opt: DistributedFusedAdam
    model: GPTModel
    plan: ZeroFitPlan
    # structure-prefix PartitionSpecs of the (params, STACKED view of
    # opt_state) tuple, NOT of the live arrays (those carry their own
    # placement, ``opt_state.exp_avg.sharding``: one dim over the
    # linearized axes): params replicated, every stacked opt_state leaf
    # led by the "data" axis — what a sharded save records and
    # partitions by (resilience.save_zero_checkpoint takes the view and
    # these specs).  On a 3-D mesh the spec leads with all three axes
    # and mesh_axes carries the {"data": dp, "pipeline": pp, "tensor":
    # tp} mapping a format-4 save (shard_axes=) wants.
    stacked_shardings: Any = None
    mesh_axes: Any = None
    # the ISSUE 15 bucketed-overlap plan the 3-D step compiled with
    # (None on the single-axis path and the legacy serialized control)
    bucket_plan: Any = None


def _jit_step(fn, mesh, in_specs, donate: bool):
    """``jax.jit`` of a train step with the donation it ships with,
    handed to :mod:`apex_tpu.telemetry.scopes` whenever it is TRACED:
    only then are the batch's shapes known, and a trace happens at
    warm-up, never in a step.  The entry holds shapes with the
    placements ``in_specs`` name, nothing of the state itself."""
    @functools.wraps(fn)
    def traced(*args):
        scopes.register(
            scopes.executable_name(step), step,
            jax.tree_util.tree_map(
                lambda spec, arg: jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype,
                        sharding=NamedSharding(mesh, spec)), arg),
                tuple(in_specs), args,
                is_leaf=lambda x: isinstance(x, P)))
        return fn(*args)

    step = jax.jit(traced, donate_argnums=(0, 1) if donate else ())
    return step


def _live_specs(axes) -> ShardedOptState:
    """PartitionSpecs of the LIVE ZeRO state over the mesh ``axes`` it
    is sharded on: each moment is one 1-D array, the ranks' shards
    laid end to end in linearized rank order, so a device's piece is
    ``[shard]``, the shape the update runs on; the ``step`` counter
    (four bytes a rank) keeps one stack axis a mesh axis, and its shape
    is what :func:`~apex_tpu.contrib.optimizers.stacked_zero_state`
    takes the lead shape from."""
    flat = P(tuple(axes))
    return ShardedOptState(step=P(*axes), exp_avg=flat, exp_avg_sq=flat)


def _enter(state: ShardedOptState) -> ShardedOptState:
    """This rank's state inside the ``shard_map`` body: the moments
    arrive as ``[shard]`` and pass through, the counter sheds its stack
    axes (:func:`_leave` puts them back)."""
    return state._replace(step=state.step[(0,) * state.step.ndim])


def _leave(state: ShardedOptState, n_lead: int) -> ShardedOptState:
    return state._replace(step=state.step[(None,) * n_lead])


def _place_state(mesh, params, opt, schema, lead_shape, specs):
    """(params, opt_state) committed to ``mesh`` with the shardings the
    step runs under: params replicated, the zero optimizer state built
    already in its live form (:func:`_live_specs`) and already sharded,
    each device materializing only its own slice.

    Left uncommitted, both would sit whole on the first device (at
    world=4 the state alone is the size of an unsharded one) and the
    step would copy them onto the mesh on every call, so its donation
    could not alias."""
    params = jax.device_put(params, NamedSharding(mesh, P()))

    def zeros():
        # the whole superblock's moments: every rank's shard, end to end
        whole = opt.init(params, schema, 1)
        return whole._replace(
            step=jnp.broadcast_to(whole.step, lead_shape))

    opt_state = jax.jit(zeros, out_shardings=jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs))()
    return params, opt_state


def build_flagship_train_step(
    cfg: GPTConfig,
    *,
    plan: str | ZeroFitPlan = "bf16_fit",
    lr: float = 1e-4,
    weight_decay: float = 0.0,
    devices: Optional[Sequence] = None,
    donate: bool = True,
    seed: int = 0,
    mesh_shape: Optional[Sequence[int]] = None,
    bucket_bytes: Any = "auto",
) -> FlagshipSetup:
    """One flagship construction: model + ZeRO-sharded FusedAdam over
    the "data" axis of a fresh ``parallel_state`` mesh spanning
    ``devices`` (default: all local devices — 1 on a single chip, 8 on
    the emulated CPU mesh).

    The returned ``step(params, opt_state, tokens, labels)`` expects the
    GLOBAL batch (sharded over "data" internally; batch must divide the
    data-parallel size) and returns ``(params, opt_state, loss)`` with
    params bitwise-replicated across ranks.  ``donate=True`` donates
    params and optimizer state — at 1.3B the old buffers ARE the fit
    margin.

    ``mesh_shape=(dp, tp, pp)`` — multi-axis form (ISSUE 6): the mesh
    carries all three ``parallel_state`` axes, tensor parallelism
    shards the *compute* (each device runs its tp-rank's slice of the
    replicated master params, taken with a traced ``dynamic_slice``
    inside the step), and ZeRO shards the optimizer state over the
    **linearized world** — every (d, p, t) coordinate owns one
    contiguous shard of the master flat buffer, so a moment is one
    ``[dp * pp * tp * shard]`` array with spec
    ``P(("data", "pipeline", "tensor"))`` (its stacked view
    ``[dp, pp, tp, shard]``, spec ``P("data", "pipeline", "tensor")``:
    :class:`FlagshipSetup`).  ``pp`` must be 1 for the
    *train step* (the pipeline schedules are separate:
    ``transformer.pipeline_parallel``; the checkpoint / reshard
    machinery handles pp > 1 states).  ``mesh_shape=None`` shards over
    "data" alone; both paths carry the state in the same form, and
    byte for byte where the single-axis ``[n_shards, shard]`` stack
    had it.

    ``bucket_bytes`` (3-D path only, ISSUE 15) selects the gradient
    data path:

    * ``"auto"`` (default) — the **bucketed-overlap ZeRO step**: the
      grad of the device-local mean loss is taken *inside* the
      shard_map region (per-device partial grads, no boundary
      all-reduces), and the flat buffer moves through one
      reduce-scatter + all-gather **per bucket**
      (:func:`apex_tpu.multi_tensor.plan_buckets` at
      :data:`~apex_tpu.multi_tensor.DEFAULT_BUCKET_BYTES`), so XLA's
      latency-hiding scheduler interleaves collectives with
      backward/optimizer compute instead of queueing one
      buffer-sized transfer per direction behind a wall of per-leaf
      grad all-reduces.  The mesh-sum of the partials is exactly
      ``world ×`` the data-mean grad — the same normalization the
      serialized path sees from ``world`` replicated copies — and
      the optimizer-state layout is canonical for every plan
      (buckets are per-rank shard spans; multi_tensor/buckets.py),
      so checkpoints reshard identically.  Parity vs the serialized
      control is pinned in tests/L0/test_bucketed_zero.py.
    * an ``int`` — same step at that bucket cap (a cap at or above
      the buffer size is the one-bucket edge: the serialized
      collective tail on the new data path).
    * ``None`` — the **legacy serialized control**: grads taken
      through the shard_map boundary (per-leaf all-reduces of the
      replicated master grad) feeding one monolithic mesh-wide
      ``psum_scatter``/``all_gather`` — kept as the contract-checker
      negative control and the pre-r15 construction.
    """
    if isinstance(plan, str):
        plan = FIT_PLANS[plan]
    devs = list(devices if devices is not None else jax.devices())
    if mesh_shape is not None:
        return _build_flagship_train_step_3d(
            cfg, plan=plan, lr=lr, weight_decay=weight_decay, devs=devs,
            donate=donate, seed=seed, mesh_shape=tuple(mesh_shape),
            bucket_bytes=bucket_bytes)
    if bucket_bytes != "auto":
        raise ValueError(
            "bucket_bytes applies to the mesh_shape=(dp, tp, pp) step; "
            "the single-axis path has one whole-buffer collective pair")
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(1, 1, devices=devs)
    n_shards = len(devs)

    model = GPTModel(cfg)
    params = model.shard_master(model.init_master(jax.random.PRNGKey(seed)),
                                0)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(plan.param_dtype), params)

    opt = DistributedFusedAdam(
        lr=lr, weight_decay=weight_decay,
        scatter_dtype=plan.scatter_dtype,
        gather_dtype=plan.gather_dtype,
        exp_avg_dtype=plan.exp_avg_dtype)
    schema = opt.make_schema(params, n_shards)
    specs = _live_specs((parallel_state.DATA_AXIS,))
    params, opt_state = _place_state(mesh, params, opt, schema,
                                     (n_shards,), specs)

    def inner(p, state, tokens, labels):
        state = _enter(state)

        def lossf(p):
            return jnp.mean(model.apply(p, tokens, labels=labels))

        # the step's phases by name in the device trace: fwd_bwd here,
        # zero_pack / zero_update / zero_unpack inside opt.step
        with jax.named_scope("fwd_bwd"):
            loss, grads = jax.value_and_grad(lossf)(p)
        new_p, new_state = opt.step(grads, state, p, schema)
        loss = jax.lax.pmean(loss, opt.axis_name)
        return new_p, _leave(new_state, 1), loss

    in_specs = (P(), specs, P("data"), P("data"))
    sharded = shard_map(
        inner, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), specs, P()), check_rep=False)
    step = _jit_step(sharded, mesh, in_specs, donate)
    return FlagshipSetup(step, params, opt_state, mesh, schema, opt,
                         model, plan, stacked_shardings=(P(), P("data")))


def _tp_slice_tables(master, local0):
    """Static per-leaf (dim, size) tables for the traced tp slice:
    compare master leaf shapes against tp-rank-0's ``shard_master``
    output — equal shape means replicated (sentinel dim -1); otherwise
    exactly one dim shrinks, and rank r's slice starts at ``r * size``
    along it (the contiguous-equal-chunk contract every
    ``tensor_parallel`` layer's ``shard_master`` follows)."""
    def _dim(m, l):
        if m.shape == l.shape:
            return -1
        if m.ndim != l.ndim:
            raise ValueError(
                f"shard_master changed rank: {m.shape} -> {l.shape}")
        diffs = [i for i, (a, b) in enumerate(zip(m.shape, l.shape))
                 if a != b]
        if len(diffs) != 1:
            raise ValueError(
                f"shard_master slices more than one dim: {m.shape} -> "
                f"{l.shape} — the traced tp slice cannot express this")
        return diffs[0]

    dims = jax.tree_util.tree_map(_dim, master, local0)
    sizes = jax.tree_util.tree_map(
        lambda l, d: int(l.shape[d]) if d >= 0 else 0, local0, dims)
    return dims, sizes


def _build_flagship_train_step_3d(cfg, *, plan, lr, weight_decay, devs,
                                  donate, seed, mesh_shape,
                                  bucket_bytes="auto"):
    """The mesh_shape=(dp, tp, pp) body of
    :func:`build_flagship_train_step` (see its docstring for the
    layout contract and the ``bucket_bytes`` data-path selector)."""
    dp, tp, pp = (int(x) for x in mesh_shape)
    if pp != 1:
        raise NotImplementedError(
            "the 3-D flagship train step supports pp=1 (pipeline "
            "schedules live in the dryrun legs); checkpoint/reshard "
            "machinery handles pp > 1 states")
    world = dp * tp * pp
    if world != len(devs):
        raise ValueError(
            f"mesh_shape {mesh_shape} needs {world} devices, got "
            f"{len(devs)}")
    if cfg.num_attention_heads % tp or cfg.hidden_size % tp \
            or cfg.vocab_size % tp:
        raise ValueError(
            f"tp={tp} must divide heads/hidden/vocab "
            f"({cfg.num_attention_heads}/{cfg.hidden_size}/"
            f"{cfg.vocab_size})")
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(tp, pp, devices=devs)

    cfg_tp = dataclasses.replace(cfg, tp_size=tp)
    model = GPTModel(cfg_tp)
    master = jax.tree_util.tree_map(
        lambda a: a.astype(plan.param_dtype),
        model.init_master(jax.random.PRNGKey(seed)))
    local0 = model.shard_master(master, 0)
    slice_dims, slice_sizes = _tp_slice_tables(master, local0)

    def _slice_tp(mp, t_idx):
        return jax.tree_util.tree_map(
            lambda m, d, n: m if d < 0 else jax.lax.dynamic_slice_in_dim(
                m, t_idx * n, n, axis=d),
            mp, slice_dims, slice_sizes)

    opt = DistributedFusedAdam(
        lr=lr, weight_decay=weight_decay,
        scatter_dtype=plan.scatter_dtype,
        gather_dtype=plan.gather_dtype,
        exp_avg_dtype=plan.exp_avg_dtype,
        axis_name=tuple(parallel_state.MESH_AXES))
    schema = opt.make_schema(master, world)
    spec3 = P(*parallel_state.MESH_AXES)
    specs = _live_specs(parallel_state.MESH_AXES)
    master, opt_state = _place_state(mesh, master, opt, schema,
                                     (dp, pp, tp), specs)
    mesh_axes = {parallel_state.DATA_AXIS: dp,
                 parallel_state.PIPELINE_AXIS: pp,
                 parallel_state.TENSOR_AXIS: tp}

    if bucket_bytes is not None:
        # -- the bucketed-overlap ZeRO step (ISSUE 15, the default) ----
        # The whole step is ONE shard_map region.  The grad of the
        # device-local mean loss is taken INSIDE it: under the
        # unreplicated-cotangent convention (check_rep=False transposes
        # ``psum`` to ``psum``) the per-device partial grads carry a
        # uniform ×tp from the model's tensor-parallel activation
        # reductions, so their mesh-sum is tp·dp = world × the
        # data-mean grad — exactly the normalization the serialized
        # path sees from ``world`` replicated copies, and
        # ``grad_average`` divides the same ``world`` back out.  What
        # this buys: the per-leaf boundary all-reduces of a replicated
        # master grad never exist (8.2× less all-reduce traffic at the
        # toy contracts geometry), and the grad sum happens in the
        # per-bucket reduce-scatters the latency-hiding scheduler can
        # interleave with backward/optimizer compute.  Collective
        # inventory + end-to-end donation are machine-checked against
        # hlo_contracts.json (`python -m apex_tpu.analysis hlo`).
        bb = DEFAULT_BUCKET_BYTES if bucket_bytes == "auto" \
            else int(bucket_bytes)
        bplan = plan_buckets(
            schema, world, bucket_bytes=bb,
            itemsize=jnp.dtype(plan.scatter_dtype or jnp.float32).itemsize)

        def _bucketed_zero_inner(mp, state, tokens, labels):
            state = _enter(state)
            t_idx = jax.lax.axis_index(parallel_state.TENSOR_AXIS)

            def local_loss(mp):
                return jnp.mean(model.apply(_slice_tp(mp, t_idx), tokens,
                                            labels=labels))

            with jax.named_scope("fwd_bwd"):
                loss, grads = jax.value_and_grad(local_loss)(mp)
            loss = jax.lax.pmean(loss, parallel_state.DATA_AXIS)
            new_p, new_state = opt.step_buckets(grads, state, mp, schema,
                                                bplan)
            return new_p, _leave(new_state, 3), loss

        in_specs = (P(), specs, P("data"), P("data"))
        sharded = shard_map(
            _bucketed_zero_inner, mesh=mesh, in_specs=in_specs,
            out_specs=(P(), specs, P()), check_rep=False)
        step = _jit_step(sharded, mesh, in_specs, donate)
        return FlagshipSetup(
            step, master, opt_state, mesh, schema, opt, model, plan,
            stacked_shardings=(P(), spec3), mesh_axes=mesh_axes,
            bucket_plan=bplan)

    # -- the legacy serialized control (bucket_bytes=None) -------------
    # The grad is taken OUTSIDE the shard_map.  Inside a
    # check_rep=False region jax transposes ``psum`` to ``psum``
    # (the unreplicated-cotangent convention), so differentiating
    # through the model's tensor-parallel reductions *inside* the
    # region scales cotangents by the axis size — loss comes out right
    # and every grad is ×tp (measured, exactly; the bucketed step
    # above RELIES on that uniform factor).  Differentiating through
    # the shard_map boundary instead uses its true adjoints end-to-end
    # — the convention tensor_parallel/mappings.py documents and
    # tests/L0/test_tensor_parallel.py's col→row grad-parity case
    # pins.  The outer grads arrive replicated (the global master
    # grad), so the opt step needs no data-average: the mesh-wide
    # psum_scatter sums world identical copies and grad_average
    # divides them back out (exact for power-of-two worlds).  The
    # price — per-leaf boundary all-reduces, then one monolithic
    # scatter/gather pair strictly after the whole backward — is the
    # serialized inventory the ratcheted hlo contract now REJECTS
    # (tests/L0/test_hlo_contracts.py keeps this path as the negative
    # control).
    def inner_fwd(mp, tokens, labels):
        t_idx = jax.lax.axis_index(parallel_state.TENSOR_AXIS)
        loss = jnp.mean(model.apply(_slice_tp(mp, t_idx), tokens,
                                    labels=labels))
        return jax.lax.pmean(loss, parallel_state.DATA_AXIS)

    loss_fn = shard_map(
        inner_fwd, mesh=mesh,
        in_specs=(P(), P("data"), P("data")), out_specs=P(),
        check_rep=False)

    def inner_opt(grads, state, mp):
        new_p, new_state = opt.step(grads, _enter(state), mp, schema)
        return new_p, _leave(new_state, 3)

    opt_sharded = shard_map(
        inner_opt, mesh=mesh,
        in_specs=(P(), specs, P()), out_specs=(P(), specs),
        check_rep=False)

    def train_step(mp, state, tokens, labels):
        with jax.named_scope("fwd_bwd"):
            loss, grads = jax.value_and_grad(loss_fn)(mp, tokens, labels)
        new_p, new_state = opt_sharded(grads, state, mp)
        return new_p, new_state, loss

    step = _jit_step(train_step, mesh,
                     (P(), specs, P("data"), P("data")), donate)
    return FlagshipSetup(
        step, master, opt_state, mesh, schema, opt, model, plan,
        stacked_shardings=(P(), spec3), mesh_axes=mesh_axes)


def flagship_elastic_build(cfg: GPTConfig, *, plan: str | ZeroFitPlan
                           = "bf16_fit", lr: float = 1e-4,
                           seed: int = 0, donate: bool = False,
                           on_loss=None, bucket_bytes="auto"):
    """``build(devices)`` factory for
    :func:`apex_tpu.resilience.run_elastic_training`: each call stands up
    the ZeRO flagship step on exactly ``devices`` (a fresh mesh whose
    "data" axis spans them) and adapts it to the resilient-loop contract
    — ``state`` is the ``(params, opt_state)`` tuple with the ZeRO
    state LIVE (:class:`FlagshipSetup`: moments 1-D over
    ``len(devices)`` shards; it doubles as the cross-topology restore
    target, a saved stack restoring into it by its C-order
    flattening), ``shardings`` is ``FlagshipSetup.stacked_shardings``
    (the specs of its stacked view, which the loop's sharded saves
    take: ``save_zero_checkpoint``), and
    ``step_fn(state, (tokens, labels))`` returns ``(state, None)``.
    ``on_loss(step_loss)`` taps the per-step loss for trajectory
    assertions.

    ``build(devices, mesh_shape=(dp, tp, pp))`` — the multi-axis form
    the 3-D elastic harness calls: the step builds over the full
    dp×tp×pp ``parallel_state`` mesh and the moments span
    ``dp * pp * tp`` shards, stacked ``[dp, pp, tp, shard]`` in a save
    (see :func:`build_flagship_train_step`'s ``mesh_shape`` notes)."""

    def build(devices, mesh_shape=None):
        fs = build_flagship_train_step(
            cfg, plan=plan, lr=lr, devices=list(devices), seed=seed,
            donate=donate, mesh_shape=mesh_shape,
            bucket_bytes=bucket_bytes if mesh_shape is not None
            else "auto")

        def step_fn(state, batch):
            p, s = state
            tokens, labels = batch
            p, s, loss = fs.step(p, s, tokens, labels)
            if on_loss is not None:
                on_loss(float(loss))
            return (p, s), None

        return step_fn, (fs.params, fs.opt_state), fs.stacked_shardings

    return build

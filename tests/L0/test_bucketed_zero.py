"""Bucketed-overlap ZeRO tests (ISSUE 15 tentpole).

Three claims, each pinned:

1. **Planner** — ``plan_buckets`` follows reference-DDP cap semantics
   over the canonical pack order and always produces a partition of
   the per-rank shard, for every cap including the one-bucket and
   one-param-per-bucket edges.
2. **Parity** — the bucketed flagship step's loss trajectory AND
   parameters are fp32-bitwise identical across the whole
   ``bucket_bytes`` sweep (the one-bucket edge IS the serialized
   collective tail on the new data path), and match the legacy
   serialized control (grad-through-the-boundary + monolithic
   scatter/gather) bitwise on losses — the partial-grad
   reduce-scatter sums the same summands the boundary all-reduces
   did.
3. **Layout** — bucket geometry never leaks into the optimizer-state
   layout: a state trained under one plan resumes bitwise under any
   other, and a format-4 checkpoint round-trips across topologies
   regardless of the plan on either side (the C-order reshard
   contract is plan-invariant).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import checkpoint as ckpt
from apex_tpu.analysis.hlo import collective_inventory
from apex_tpu.contrib.optimizers import (
    DistributedFusedLAMB,
)
from apex_tpu.multi_tensor import (
    DEFAULT_BUCKET_BYTES,
    BucketPlan,
    make_schema,
    plan_buckets,
)
from apex_tpu.resilience import save_zero_checkpoint
from apex_tpu.transformer.testing import (
    build_flagship_train_step,
    gpt1p3b_config,
)

N_DEV = 8

TOY_KW = dict(num_layers=2, hidden_size=256, num_attention_heads=2,
              vocab_size=256, max_position_embeddings=64)


def _batch(cfg, b=8, seed=1):
    k = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(k, (b, cfg.max_position_embeddings), 0,
                                cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=-1)


def _run(fs, tokens, labels, steps=3):
    p, s = fs.params, fs.opt_state
    losses = []
    for _ in range(steps):
        p, s, loss = fs.step(p, s, tokens, labels)
        losses.append(float(loss))
    return p, s, losses


def _leaves32(tree):
    return [np.asarray(a, np.float32)
            for a in jax.tree_util.tree_leaves(tree)]


def _assert_trees_bitwise(a, b, what=""):
    for x, y in zip(_leaves32(a), _leaves32(b)):
        np.testing.assert_array_equal(x, y, err_msg=what)


# ------------------------------------------------------------- planner


def _toy_schema(world=8):
    tree = {"a": jnp.zeros((700,)), "b": jnp.zeros((64, 64)),
            "c": jnp.zeros((5,)), "d": jnp.zeros((3000,)),
            "e": jnp.zeros((129,))}
    return make_schema(tree, align=128, total_multiple_of=128 * world)


def test_plan_buckets_partitions_the_shard():
    schema = _toy_schema()
    for bb in (None, 1, 4096, 1 << 20, DEFAULT_BUCKET_BYTES):
        plan = plan_buckets(schema, 8, bucket_bytes=bb)
        plan.validate()  # spans partition [0, shard) in order
        assert plan.shard == schema.total // 8
        assert plan.world == 8
        assert all((hi - lo) % 1 == 0 and lo % 128 == 0
                   for lo, hi in plan.spans[:-1])
        # per-collective payload covers all ranks of the span
        assert sum(plan.collective_elements(b)
                   for b in range(plan.num_buckets)) == schema.total


def test_plan_buckets_ddp_cap_semantics():
    """Reference-DDP cap: leaves accumulate until the next leaf would
    exceed the cap; a bucket always takes at least one leaf (an
    oversized leaf becomes its own bucket)."""
    schema = _toy_schema(world=1)
    # cap of one leaf's bytes: every leaf closes a bucket -> canonical
    # boundaries at every leaf offset (world=1: spans ARE canonical)
    plan = plan_buckets(schema, 1, bucket_bytes=1)
    cut_points = {lo for lo, _ in plan.spans}
    assert cut_points == set(schema.offsets), (plan.spans, schema.offsets)
    assert plan.num_buckets == schema.num_tensors
    # a cap far above the buffer: one bucket (the serialized edge)
    plan1 = plan_buckets(schema, 1, bucket_bytes=schema.total * 4 + 1)
    assert plan1.num_buckets == 1
    assert plan1.spans == ((0, schema.total),)
    # None is the explicit serialized single-bucket plan
    plan_none = plan_buckets(schema, 1, bucket_bytes=None)
    assert plan_none.spans == plan1.spans
    assert plan_none.bucket_bytes is None


def test_plan_buckets_cap_is_monotone():
    """Shrinking the cap never produces fewer buckets."""
    schema = _toy_schema()
    prev = None
    for bb in (1 << 24, 1 << 16, 1 << 12, 1 << 8, 1):
        n = plan_buckets(schema, 8, bucket_bytes=bb).num_buckets
        if prev is not None:
            assert n >= prev, (bb, n, prev)
        prev = n
    assert prev >= 2  # the tiny cap really buckets at this geometry


def test_plan_buckets_validation():
    schema = _toy_schema()
    with pytest.raises(ValueError, match="world must be >= 1"):
        plan_buckets(schema, 0)
    with pytest.raises(ValueError, match="does not divide world"):
        plan_buckets(schema, 7)
    with pytest.raises(ValueError, match="bucket_bytes must be >= 1"):
        plan_buckets(schema, 8, bucket_bytes=0)
    with pytest.raises(ValueError, match="span_align"):
        plan_buckets(schema, 8, span_align=64)
    with pytest.raises(ValueError, match="spans must partition"):
        BucketPlan(spans=((0, 128), (256, 512)), shard=512, world=1,
                   bucket_bytes=None).validate()
    with pytest.raises(ValueError, match=r"cover \[0, 256\)"):
        BucketPlan(spans=((0, 256),), shard=512, world=1,
                   bucket_bytes=None).validate()


def test_plan_buckets_span_align_rounds_to_sublane_rows():
    """span_align=8*128 (the Pallas flat-Adam requirement) still
    partitions exactly; every interior cut is sublane-row aligned.
    The buffer must be packed to the same multiple (the FlatFusedAdam
    1024-element contract)."""
    tree = {"a": jnp.zeros((700,)), "b": jnp.zeros((64, 64)),
            "d": jnp.zeros((3000,))}
    schema = make_schema(tree, align=128, total_multiple_of=8 * 128)
    with pytest.raises(ValueError, match="not aligned"):
        plan_buckets(_toy_schema(world=1), 1, span_align=8 * 128)
    plan = plan_buckets(schema, 1, bucket_bytes=1, span_align=8 * 128)
    plan.validate()
    assert all(lo % (8 * 128) == 0 for lo, _ in plan.spans)


# ---------------------------------------------------- flagship parity


@pytest.fixture(scope="module")
def sweep_runs():
    """One 3-step trajectory per data path at the fp32 plan (grad noise
    removed, so any bucketing error shows as a bit flip): the legacy
    serialized control, the one-bucket edge, a mid cap, and the
    one-param-per-bucket edge.  Built once per module — five 8-device
    jit constructions are the dominant wall cost here."""
    cfg = gpt1p3b_config(bf16=False, **TOY_KW)
    tokens, labels = _batch(cfg)
    out = {}
    for name, bb in (("legacy", None), ("one_bucket", 1 << 30),
                     ("mid", 1 << 20), ("per_param", 1)):
        fs = build_flagship_train_step(
            cfg, plan="fp32", lr=1e-3, devices=jax.devices()[:N_DEV],
            donate=False, mesh_shape=(4, 2, 1), bucket_bytes=bb)
        p, s, losses = _run(fs, tokens, labels)
        out[name] = (p, s, losses, fs.bucket_plan)
    return out


def test_bucket_sweep_is_fp32_bitwise(sweep_runs):
    """THE parity acceptance (ISSUE 15): losses, params AND optimizer
    moments are fp32-bitwise identical across the bucket-size sweep —
    the one-bucket edge is the serialized collective tail, so
    'bucketed vs serialized' is exact, not approximate.  Elementwise
    Adam + identical per-element summation order in every
    reduce-scatter make this a strict invariant, not a tolerance."""
    ref_p, ref_s, ref_losses, ref_plan = sweep_runs["one_bucket"]
    assert ref_plan.num_buckets == 1
    for name in ("mid", "per_param"):
        p, s, losses, plan = sweep_runs[name]
        assert plan.num_buckets > 1, (name, plan)
        assert losses == ref_losses, (name, losses, ref_losses)
        _assert_trees_bitwise(p, ref_p, f"params {name} vs one_bucket")
        _assert_trees_bitwise(s, ref_s, f"opt state {name} vs one_bucket")
    # the edges really are edges
    assert sweep_runs["per_param"][3].num_buckets \
        > sweep_runs["mid"][3].num_buckets


def test_bucketed_matches_legacy_serialized_step(sweep_runs):
    """The new data path (partial grads summed IN the per-bucket
    reduce-scatters) reproduces the legacy control (per-leaf boundary
    all-reduces + monolithic scatter/gather) bitwise on the fp32 loss
    trajectory: same summands, same per-element reduction — only the
    collective *structure* changed.  Params carry reduction-order dust
    at the 1e-5 level (the boundary all-reduce and the reduce-scatter
    are different XLA reductions), bounded well under the 1e-3
    ISSUE 2 parity bar."""
    _, _, legacy_losses, _ = sweep_runs["legacy"]
    p, _, losses, _ = sweep_runs["one_bucket"]
    assert losses == legacy_losses, (losses, legacy_losses)
    legacy_p = sweep_runs["legacy"][0]
    maxdw = max(float(np.max(np.abs(a - b)))
                for a, b in zip(_leaves32(p), _leaves32(legacy_p)))
    assert maxdw <= 1e-4, maxdw


@pytest.mark.slow  # two extra 8-device bf16 constructions (~25 s)
def test_bucketed_matches_legacy_bf16_fit_bitwise():
    """At the real bf16_fit plan the 1e-5 reduction-order dust vanishes
    below bf16 resolution: params and losses match the legacy
    serialized step BITWISE (measured 0 ulp)."""
    cfg = gpt1p3b_config(**TOY_KW)
    tokens, labels = _batch(cfg)
    runs = {}
    for name, bb in (("legacy", None), ("bucketed", 1 << 20)):
        fs = build_flagship_train_step(
            cfg, plan="bf16_fit", lr=1e-3, devices=jax.devices()[:N_DEV],
            donate=False, mesh_shape=(4, 2, 1), bucket_bytes=bb)
        runs[name] = _run(fs, tokens, labels)
    assert runs["bucketed"][2] == runs["legacy"][2]
    _assert_trees_bitwise(runs["bucketed"][0], runs["legacy"][0],
                          "bf16_fit params bucketed vs legacy")


# ------------------------------------------------- layout / checkpoint


def test_bucket_plan_does_not_leak_into_state_layout():
    """Cross-plan resume, same topology: 2 steps under plan A, then the
    (params, opt_state) snapshot feeds a step built with plan B for 2
    more — bitwise equal to 4 straight steps under EITHER plan.  The
    optimizer-state stack is canonical for every plan (buckets are
    per-rank shard spans), so swapping plans mid-run is a no-op."""
    cfg = gpt1p3b_config(bf16=False, **TOY_KW)
    tokens, labels = _batch(cfg)

    def build(bb):
        return build_flagship_train_step(
            cfg, plan="fp32", lr=1e-3, devices=jax.devices()[:N_DEV],
            donate=False, mesh_shape=(4, 2, 1), bucket_bytes=bb)

    fs_a, fs_b = build(1 << 30), build(1 << 18)
    assert fs_b.bucket_plan.num_buckets > fs_a.bucket_plan.num_buckets

    control_p, control_s, control_losses = _run(fs_a, tokens, labels,
                                                steps=4)
    p, s = fs_a.params, fs_a.opt_state
    mixed_losses = []
    for step_fn in (fs_a.step, fs_a.step, fs_b.step, fs_b.step):
        p, s, loss = step_fn(p, s, tokens, labels)
        mixed_losses.append(float(loss))
    assert mixed_losses == control_losses
    _assert_trees_bitwise(p, control_p, "cross-plan params")
    _assert_trees_bitwise(s, control_s, "cross-plan opt state")


@pytest.mark.slow  # three 8-device constructions + a format-4 round trip
def test_format4_round_trip_is_bucket_plan_invariant(tmp_path):
    """THE reshard-contract satellite: a format-4 checkpoint written
    from a bucketed (4,2,1) run restores BITWISE into a (2,2,1)
    4-device target built with a different bucket plan — the on-disk
    C-order contract never sees bucket geometry — and the resumed
    trajectory matches the uninterrupted source run at <= 1 bf16
    ulp (the elastic-recovery bar)."""
    cfg = gpt1p3b_config(**TOY_KW)
    tokens, labels = _batch(cfg)

    fs_src = build_flagship_train_step(
        cfg, plan="bf16_fit", lr=1e-3, devices=jax.devices()[:N_DEV],
        donate=False, mesh_shape=(4, 2, 1), bucket_bytes=1 << 18)
    p, s = fs_src.params, fs_src.opt_state
    losses = []
    p2 = s2 = None
    for _ in range(4):
        p, s, loss = fs_src.step(p, s, tokens, labels)
        losses.append(float(loss))
        if len(losses) == 2:
            p2, s2 = p, s
            # a format-4 save partitions the live state's stacked view
            save_zero_checkpoint(
                str(tmp_path / "c"), (p, s), step=2,
                shardings=fs_src.stacked_shardings,
                shard_axes=fs_src.mesh_axes)

    fs_dst = build_flagship_train_step(
        cfg, plan="bf16_fit", lr=1e-3, devices=jax.devices()[:4],
        donate=False, mesh_shape=(2, 2, 1), bucket_bytes=1 << 30)
    (rp, rs), step = ckpt.restore_checkpoint(
        str(tmp_path / "c"), (fs_dst.params, fs_dst.opt_state),
        verify=True)
    assert step == 2
    # restored moments == source moments under the C-order contract:
    # concat over the (2,2,1) stack == concat over the (4,2,1) stack
    # (the world-8 schema may pad a longer all-zero tail than the
    # world-4 schema keeps — the only legal size difference)
    for got, want in ((rs.exp_avg, s2.exp_avg),
                      (rs.exp_avg_sq, s2.exp_avg_sq)):
        got = np.asarray(got, np.float32).reshape(-1)
        want = np.asarray(want, np.float32).reshape(-1)
        np.testing.assert_array_equal(got, want[:got.size])
        assert np.all(want[got.size:] == 0)
    _assert_trees_bitwise(rp, p2, "restored params")

    def ulp(a, b):
        ba = np.asarray(a, jnp.bfloat16.dtype).view(np.uint16)
        bb = np.asarray(b, jnp.bfloat16.dtype).view(np.uint16)
        return int(np.abs(ba.astype(np.int64) - bb.astype(np.int64)).max())

    for want in losses[2:]:
        rp, rs, loss = fs_dst.step(rp, rs, tokens, labels)
        assert ulp(np.float32(loss), np.float32(want)) <= 1, (
            float(loss), want)


# ------------------------------------------------ optimizer-level API


def test_flat_adam_bucketed_plan_is_bitwise():
    """FlatFusedAdam's bucketed walk (one kernel launch per span) is
    bitwise the single-launch step — the single-device twin of the
    flagship pipeline, registered with the contract checker."""
    from apex_tpu.optimizers.flat import FlatFusedAdam

    n = 8 * 1024
    opt = FlatFusedAdam(lr=1e-3, weight_decay=0.01)
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.randn(n).astype(np.float32))
    g = jnp.asarray(rng.randn(n).astype(np.float32) * 0.1)
    state = opt.init(p)
    schema = make_schema({"w": jnp.zeros((n,))}, align=128)
    plan = plan_buckets(schema, 1, bucket_bytes=n, span_align=8 * 128)
    assert plan.num_buckets == 1  # one leaf -> DDP cap can't split it
    # a hand-built multi-span plan (the leaf-cap path can't split a
    # single giant leaf, which is exactly DDP semantics)
    plan4 = BucketPlan(spans=((0, 2048), (2048, 4096), (4096, n)),
                       shard=n, world=1, bucket_bytes=2048 * 4)
    p_ref, s_ref = opt.step(g, state, p)
    p_b, s_b = opt.step(g, state, p, plan=plan4)
    np.testing.assert_array_equal(np.asarray(p_ref), np.asarray(p_b))
    np.testing.assert_array_equal(np.asarray(s_ref.exp_avg),
                                  np.asarray(s_b.exp_avg))
    np.testing.assert_array_equal(np.asarray(s_ref.exp_avg_sq),
                                  np.asarray(s_b.exp_avg_sq))
    assert int(s_b.step) == 1


def test_flat_adam_bucketed_plan_validation():
    from apex_tpu.optimizers.flat import FlatFusedAdam

    n = 8 * 1024
    opt = FlatFusedAdam()
    p = jnp.zeros((n,), jnp.float32)
    state = opt.init(p)
    bad_world = BucketPlan(spans=((0, n // 2),), shard=n // 2, world=2,
                           bucket_bytes=None)
    with pytest.raises(ValueError, match="world=1 plan"):
        opt.step(p, state, p, plan=bad_world)
    misaligned = BucketPlan(spans=((0, 128), (128, n)), shard=n, world=1,
                            bucket_bytes=None)
    with pytest.raises(ValueError, match="sublane-row"):
        opt.step(p, state, p, plan=misaligned)


def test_lamb_refuses_bucketed_step():
    """LAMB's global grad-norm prepass cannot be honored per-bucket —
    the bucketed path must refuse loudly, not clip per-bucket."""
    opt = DistributedFusedLAMB()
    with pytest.raises(NotImplementedError, match="grad-norm prepass"):
        opt.step_buckets(None, None, None, None, None)


def test_e5m2_allgather_refuses_bucketed_step():
    from apex_tpu.contrib.optimizers import DistributedFusedAdam

    opt = DistributedFusedAdam(e5m2_allgather=True)
    with pytest.raises(NotImplementedError, match="e5m2"):
        opt.step_buckets(None, None, None, None, None)


def test_bucketed_step_records_its_plan():
    """FlagshipSetup carries the compiled plan, the compiled step has
    one reduce-scatter and one all-gather a bucket and its loss falls;
    the legacy control carries None."""
    cfg = gpt1p3b_config(bf16=False, **TOY_KW)
    fs = build_flagship_train_step(
        cfg, plan="fp32", lr=1e-3, devices=jax.devices()[:4],
        donate=False, mesh_shape=(2, 2, 1), bucket_bytes=1 << 18)
    assert fs.bucket_plan is not None
    assert fs.bucket_plan.world == 4
    nb = fs.bucket_plan.num_buckets
    assert nb > 1, "the toy cap must actually bucket the buffer"
    tokens, labels = _batch(cfg)
    inv = collective_inventory(fs.step.lower(
        fs.params, fs.opt_state, tokens, labels).compile().as_text())
    assert inv["reduce-scatter"]["count"] == nb, inv
    assert inv["all-gather"]["count"] == nb, inv
    _, _, losses = _run(fs, tokens, labels)
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    fs_legacy = build_flagship_train_step(
        cfg, plan="fp32", lr=1e-3, devices=jax.devices()[:4],
        donate=False, mesh_shape=(2, 2, 1), bucket_bytes=None)
    assert fs_legacy.bucket_plan is None
    with pytest.raises(ValueError, match="single-axis"):
        build_flagship_train_step(cfg, plan="fp32", bucket_bytes=1)

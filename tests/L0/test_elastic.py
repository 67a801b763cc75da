"""Elastic-mesh resilience tests (ISSUE 3 tentpole): sharded ZeRO
checkpoints, cross-topology restore, collective watchdog, device-loss
chaos — all on the emulated 8-device CPU mesh.

Markers: everything here is ``chaos_mesh`` (mesh-aware fault injection);
the flagship-model reshard/trajectory cases are additionally ``slow``
(multiple 8-device jit constructions) so tier-1 stays fast — see README
for both invocations.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import checkpoint as ckpt
from apex_tpu import resilience as res
from apex_tpu.resilience import chaos
from apex_tpu.transformer.testing import (
    flagship_elastic_build,
    gpt1p3b_config,
    run_resilient_training,
)

pytestmark = [pytest.mark.chaos, pytest.mark.chaos_mesh]

N_DEV = 8

# the gpt1p3b_toy_zero golden-trajectory cell's exact configuration
# (tests/L1/common/harness.py run_flagship_trajectory): d=128 head
# geometry at toy depth, ZeRO bf16_fit over the 8-device mesh
TOY_KW = dict(num_layers=2, hidden_size=256, num_attention_heads=2,
              vocab_size=512, max_position_embeddings=32)


def _toy_cfg():
    return gpt1p3b_config(**TOY_KW)


def _golden_batches(cfg, n, seed=0):
    """The EXACT batch stream of the golden cell (harness.py:196-200)."""
    out = []
    for i in range(n):
        k = jax.random.fold_in(jax.random.PRNGKey(seed + 300), i % 2)
        tokens = jax.random.randint(k, (8, cfg.max_position_embeddings),
                                    0, cfg.vocab_size)
        out.append((tokens, jnp.roll(tokens, -1, axis=-1)))
    return out


def _bf16_ulp_diff(a, b):
    """Max bit-distance between two bf16 arrays (0 = bitwise equal)."""
    ba = np.asarray(a, jnp.bfloat16.dtype).view(np.uint16).astype(np.int64)
    bb = np.asarray(b, jnp.bfloat16.dtype).view(np.uint16).astype(np.int64)
    return int(np.max(np.abs(ba - bb))) if ba.size else 0


def _assert_flat_parity(restored, source, *, bitwise: bool):
    """Restored flat-buffer leaf vs the source topology's: equal on the
    common prefix (bitwise, or ≤ 1 bf16 ulp), all-zero beyond it (the
    only size difference the reshard contract allows is schema tail
    padding)."""
    fa = np.asarray(restored, np.float32).reshape(-1)
    fb = np.asarray(source, np.float32).reshape(-1)
    n = min(fa.size, fb.size)
    assert np.all(fa[n:] == 0) and np.all(fb[n:] == 0)
    if bitwise:
        np.testing.assert_array_equal(fa[:n], fb[:n])
    else:
        assert _bf16_ulp_diff(fa[:n], fb[:n]) <= 1


# ---------------------------------------------------- sharded format


def _synthetic_state(n_shards=8, shard=32):
    """A flagship-shaped state without the model: replicated params,
    stacked per-rank opt partitions, broadcast step counter."""
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(16), jnp.float32)}
    opt = {
        "step": jnp.broadcast_to(jnp.asarray(5, jnp.int32), (n_shards,)),
        "exp_avg": jnp.asarray(rng.randn(n_shards, shard), jnp.float32),
        "exp_avg_sq": jnp.asarray(
            np.abs(rng.randn(n_shards, shard)), jnp.float32),
    }
    return (params, opt), (P(), P("data"))


def test_sharded_save_layout_and_manifest(chaos_ckpt_dir):
    """The sharded manifest contract (docs/resilience.md "Distributed
    resilience"): per-rank shard files, per-shard CRC32 digests, a
    topology record, replicated leaves stored once."""
    import json

    state, shardings = _synthetic_state()
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings, shard_axis="data")
    d = ckpt.step_dir(str(chaos_ckpt_dir), 1)
    names = sorted(os.listdir(d))
    assert "arrays.npz" in names  # the replicated params
    assert [ckpt.shard_file(r) in names for r in range(8)] == [True] * 8
    with open(os.path.join(d, "manifest.json")) as f:
        man = json.load(f)
    assert man["format"] == 3
    assert man["topology"] == {"shard_axis": "data", "n_shards": 8}
    opt_entries = {k: e for k, e in man["leaves"].items()
                   if e.get("shard_axis")}
    assert len(opt_entries) == 3
    for e in opt_entries.values():
        assert len(e["crc32_shards"]) == 8
    step_e = next(e for k, e in opt_entries.items() if "step" in k)
    assert step_e["replicated_shards"] is True
    assert ckpt.verify_checkpoint(str(chaos_ckpt_dir), 1) == 1


@pytest.mark.parametrize("m", [8, 4, 1])
def test_sharded_roundtrip_reshard_synthetic(chaos_ckpt_dir, m):
    """8→M reshard of the stacked flat-buffer layout: fp32 bitwise on
    the common prefix, broadcast step counter re-broadcast, growth
    zero-filled."""
    state, shardings = _synthetic_state(8, 32)  # logical 256
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=2,
                         shardings=shardings, shard_axis="data")
    shard = 256 // m
    target = ({"w": jnp.zeros(16, jnp.float32)},
              {"step": jnp.zeros((m,), jnp.int32),
               "exp_avg": jnp.zeros((m, shard), jnp.float32),
               "exp_avg_sq": jnp.zeros((m, shard), jnp.float32)})
    (p, o), step = res.restore_resilient(str(chaos_ckpt_dir), target)
    assert step == 2
    np.testing.assert_array_equal(np.asarray(p["w"]),
                                  np.asarray(state[0]["w"]))
    assert np.all(np.asarray(o["step"]) == 5) and o["step"].shape == (m,)
    for leaf in ("exp_avg", "exp_avg_sq"):
        _assert_flat_parity(o[leaf], state[1][leaf], bitwise=True)


def test_fresh_init_zero_state_reshards_by_concat(chaos_ckpt_dir):
    """A fresh ZeRO init's moments are all-zero, so every rank's
    partition is bitwise identical — that must NOT classify them as
    replicated-per-rank (only 1-D per-rank scalar stacks are): an 8→4
    reshard of step-0 state re-partitions by concat and succeeds."""
    import json

    state = ({"w": jnp.ones(8, jnp.float32)},
             {"step": jnp.zeros((8,), jnp.int32),
              "exp_avg": jnp.zeros((8, 16), jnp.float32),
              "exp_avg_sq": jnp.zeros((8, 16), jnp.float32)})
    shardings = (P(), P("data"))
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=0,
                         shardings=shardings, shard_axis="data")
    with open(os.path.join(ckpt.step_dir(str(chaos_ckpt_dir), 0),
                           "manifest.json")) as f:
        man = json.load(f)
    flags = {k: e["replicated_shards"] for k, e in man["leaves"].items()
             if e.get("shard_axis")}
    assert [v for k, v in sorted(flags.items()) if "step" in k] == [True]
    assert [v for k, v in sorted(flags.items()) if "exp" in k] == [False,
                                                                   False]
    target = ({"w": jnp.zeros(8, jnp.float32)},
              {"step": jnp.zeros((4,), jnp.int32),
               "exp_avg": jnp.zeros((4, 32), jnp.float32),
               "exp_avg_sq": jnp.zeros((4, 32), jnp.float32)})
    (_, o), _ = ckpt.restore_checkpoint(str(chaos_ckpt_dir), target)
    assert np.all(np.asarray(o["exp_avg"]) == 0)


def test_reshard_refuses_to_drop_real_state(chaos_ckpt_dir):
    """Shrinking beyond schema padding (non-zero tail) must raise, not
    silently truncate optimizer state."""
    state, shardings = _synthetic_state(8, 32)
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings, shard_axis="data")
    target = ({"w": jnp.zeros(16, jnp.float32)},
              {"step": jnp.zeros((4,), jnp.int32),
               "exp_avg": jnp.zeros((4, 32), jnp.float32),  # 128 < 256
               "exp_avg_sq": jnp.zeros((4, 32), jnp.float32)})
    with pytest.raises(ValueError, match="not all zero"):
        ckpt.restore_checkpoint(str(chaos_ckpt_dir), target)


def test_reshard_zero_state_in_memory():
    """The host-side reshard helper (contrib.optimizers) agrees with the
    checkpoint path: concat → re-split against the target schema."""
    from apex_tpu.contrib.optimizers import (
        DistributedFusedAdam, ShardedOptState, reshard_zero_state)

    params = {"w": jnp.asarray(np.random.RandomState(1).randn(300),
                               jnp.float32)}
    opt = DistributedFusedAdam()
    sch8 = opt.make_schema(params, 8)
    sch4 = opt.make_schema(params, 4)
    rng = np.random.RandomState(2)
    stacked = ShardedOptState(
        step=jnp.broadcast_to(jnp.asarray(3, jnp.int32), (8,)),
        exp_avg=jnp.asarray(rng.randn(8, sch8.total // 8), jnp.float32),
        exp_avg_sq=jnp.asarray(rng.randn(8, sch8.total // 8), jnp.float32))
    # zero the schema tail so an 8→4 shrink is legal (live state never
    # has non-zero padding; random fill does)
    def _zero_tail(a, raw):
        a = np.array(a).reshape(-1)  # writable copy
        a[raw:] = 0
        return jnp.asarray(a.reshape(8, -1))
    raw = sum(sch8.sizes)
    stacked = stacked._replace(exp_avg=_zero_tail(stacked.exp_avg, raw),
                               exp_avg_sq=_zero_tail(stacked.exp_avg_sq,
                                                     raw))
    out = reshard_zero_state(stacked, n_shards=4, schema=sch4)
    assert out.exp_avg.shape == (4, sch4.total // 4)
    assert np.all(np.asarray(out.step) == 3) and out.step.shape == (4,)
    for a, b in ((out.exp_avg, stacked.exp_avg),
                 (out.exp_avg_sq, stacked.exp_avg_sq)):
        _assert_flat_parity(a, b, bitwise=True)


@pytest.mark.parametrize("lead,new_lead", [((8,), (4,)),
                                           ((4, 1, 2), (2, 2, 1))])
def test_live_state_goes_through_the_interchange_form_and_back(lead,
                                                                new_lead):
    """ISSUE 38: the train step carries a moment as ONE 1-D array (the
    stack's C-order flattening); ``stacked_zero_state`` /
    ``live_zero_state`` are the two functions between that and the
    stacked interchange form.  A ``reshard_zero_state`` input in the
    stacked form (what the tree before ISSUE 38 carried) lands bitwise
    in the new state, on the new topology, and comes back."""
    from apex_tpu.contrib.optimizers import (
        DistributedFusedAdam, ShardedOptState, live_zero_state,
        reshard_zero_state, stacked_zero_state)

    params = {"w": jnp.asarray(np.random.RandomState(1).randn(300),
                               jnp.float32)}
    opt = DistributedFusedAdam(exp_avg_dtype=jnp.bfloat16)
    world, new_world = int(np.prod(lead)), int(np.prod(new_lead))
    sch, new_sch = (opt.make_schema(params, w) for w in (world, new_world))
    rng = np.random.RandomState(2)
    raw = sum(sch.sizes)

    def _moment(dtype):
        a = rng.randn(sch.total).astype(np.float32)
        a[raw:] = 0          # live state never has non-zero padding
        return jnp.asarray(a.reshape(*lead, -1), dtype)

    stacked = ShardedOptState(
        step=jnp.broadcast_to(jnp.asarray(3, jnp.int32), lead),
        exp_avg=_moment(jnp.bfloat16), exp_avg_sq=_moment(jnp.float32))

    live = live_zero_state(stacked)
    assert live.exp_avg.shape == live.exp_avg_sq.shape == (sch.total,)
    assert live.step.shape == lead
    back = stacked_zero_state(live)
    for got, want in zip(back, stacked):
        assert isinstance(got, np.ndarray)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(got, np.asarray(want))
    # a pair (params, state) goes through whole; other leaves untouched
    pair = stacked_zero_state((params, live))
    assert pair[0]["w"] is params["w"]
    assert pair[1].exp_avg.shape == (*lead, sch.total // world)
    # an already stacked state passes through
    assert stacked_zero_state(back).exp_avg.shape == back.exp_avg.shape

    # the old form in, the new topology's live state out, and back
    out = reshard_zero_state(
        stacked, schema=new_sch,
        **(dict(n_shards=new_lead[0]) if len(new_lead) == 1
           else dict(lead_shape=new_lead)))
    new_live = live_zero_state(out)
    assert new_live.exp_avg.shape == (new_sch.total,)
    assert new_live.step.shape == new_lead
    for got, want in ((new_live.exp_avg, live.exp_avg),
                      (new_live.exp_avg_sq, live.exp_avg_sq)):
        assert got.dtype == want.dtype
        _assert_flat_parity(got, want, bitwise=True)
    again = reshard_zero_state(
        stacked_zero_state(new_live), schema=sch,
        **(dict(n_shards=lead[0]) if len(lead) == 1
           else dict(lead_shape=lead)))
    for got, want in zip(again, stacked):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_live_zero_state_places_a_shard_a_device():
    """``like=``: every array lands with its live counterpart's
    sharding, a device holding exactly its ``[shard]``."""
    from jax.sharding import Mesh, NamedSharding
    from apex_tpu.contrib.optimizers import (
        ShardedOptState, live_zero_state)

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    put = lambda a: jax.device_put(a, NamedSharding(mesh, P("data")))
    like = ShardedOptState(put(jnp.zeros((4,), jnp.int32)),
                           put(jnp.zeros((4 * 128,), jnp.bfloat16)),
                           put(jnp.zeros((4 * 128,), jnp.float32)))
    stacked = ShardedOptState(
        np.full((4,), 7, np.int32),
        np.arange(512, dtype=np.float32).reshape(4, 128).astype(
            jnp.bfloat16),
        np.arange(512, dtype=np.float32).reshape(4, 128))
    live = live_zero_state(stacked, like=like)
    for got, ref, want in zip(live, like, stacked):
        assert got.sharding == ref.sharding
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want).reshape(ref.shape))
    assert live.exp_avg.sharding.shard_shape(live.exp_avg.shape) == (128,)


def test_largest_divisor_submesh():
    """Losing 2 of 8 devices must rebuild on 4 (6 does not divide the
    global batch of 8), the select_devices policy the verify demo and a
    real deployment use."""
    devs = list(range(8))
    assert res.largest_divisor_submesh(devs, 8) == devs
    assert res.largest_divisor_submesh(devs[:6], 8) == devs[:4]
    assert res.largest_divisor_submesh(devs[:3], 8) == devs[:2]
    assert res.largest_divisor_submesh(devs[:5], 7) == devs[:1]


# --------------------------------------------------------- watchdog


def test_watchdog_timeout_escalates_to_grace_handler(chaos_ckpt_dir):
    """A slow-collective step overruns the armed deadline: the watchdog
    logs the straggler diagnostic and escalates to the GracePeriodHandler
    save-and-exit path — the loop writes a final checkpoint and returns
    preempted with the watchdog's reason."""
    state = {"w": jnp.ones((4,))}
    # generous margins: under full-suite load a NORMAL step can take
    # hundreds of ms, and a deadline racing that fires at the wrong
    # step (observed flake at timeout=0.25/delay=0.6)
    slow = chaos.slow_collective(lambda s, b: ({"w": s["w"] + 1.0}, None),
                                 at_step=3, delay=2.5)
    h = res.GracePeriodHandler()
    with res.Watchdog(timeout=1.0, handler=h, poll_interval=0.02) as wd:
        result = run_resilient_training(
            slow, state, [None] * 6, ckpt_dir=str(chaos_ckpt_dir),
            save_every=2, handler=h, watchdog=wd)
        assert result.preempted
        assert result.stop_reason == "watchdog_timeout(step=2)"
        # the loop finished the straggling step, then saved and exited
        assert result.steps_run == 3
        assert result.last_saved_step == 3
        assert wd.expired and wd.fired_steps == [2]
        report = wd.last_report
        assert set(report["device_heartbeat_age_s"]) == {
            getattr(d, "id", d) for d in jax.devices()}
        pct = report["step_duration_percentiles"]
        assert set(pct) >= {"p50", "p90", "p99", "max"}
        assert pct["max"] < 2.5  # history holds the FAST steps only
    assert ckpt.latest_step(str(chaos_ckpt_dir)) == 3


def test_watchdog_without_handler_raises_at_next_arm():
    import time

    wd = res.Watchdog(timeout=0.08, poll_interval=0.01)
    try:
        with wd.step(0):
            time.sleep(0.25)
        with pytest.raises(res.WatchdogTimeout, match="step 0 overran"):
            with wd.step(1):
                pass
    finally:
        wd.close()


def test_watchdog_adaptive_timeout_unarmed_before_history():
    """The documented adaptive deadline (`lambda d: 10 * max(d[-20:])`)
    must not crash on the empty duration history of the first step — it
    stays unarmed until a step has completed."""
    with res.Watchdog(timeout=lambda d: 10 * max(d[-20:]),
                      poll_interval=0.01) as wd:
        with wd.step(0):  # no history yet: must arm as infinite, not raise
            pass
        assert wd._current_timeout() < float("inf")  # history exists now
        with wd.step(1):
            pass
    assert not wd.expired


def test_elastic_restore_below_start_step_raises(chaos_ckpt_dir):
    """A fallback restore landing BEFORE this run's start_step must
    raise: the caller does not hold those batches, and a negative
    batches slice would silently train on the wrong data."""
    state, shardings = _synthetic_state()
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings, shard_axis="data")

    def build(devs):
        def step_fn(s, batch):
            raise chaos.DeviceLossError(devs[-1:])
        return step_fn, _synthetic_state()[0], shardings

    with pytest.raises(RuntimeError, match="before this run's start_step"):
        res.run_elastic_training(build, jax.devices(), [None] * 2,
                                 ckpt_dir=str(chaos_ckpt_dir),
                                 start_step=5, max_restarts=2)


def test_watchdog_quiet_run_never_fires():
    h = res.GracePeriodHandler()
    with res.Watchdog(timeout=5.0, handler=h) as wd:
        for i in range(4):
            with wd.step(i):
                pass
    assert not wd.expired and not h.should_stop
    assert wd.step_percentiles()["n"] == 4


# ------------------------------------------- chaos: kill mid-async-save


def test_kill_mid_async_save_newest_intact_shard_set_wins(chaos_ckpt_dir):
    """THE sharded-chaos acceptance case: step 1 lands intact; the step-2
    ASYNC sharded save dies mid-shard-set (injected write_shard fault —
    the atomic commit never happens); step 3 lands but one of its shard
    files is then corrupted on disk.  restore_resilient must skip step 3
    (one bad shard condemns the whole set), never see a partial step 2,
    and land on step 1 — the newest INTACT shard set."""
    state, shardings = _synthetic_state()
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings, shard_axis="data")
    with chaos.FaultyStore(fail_events=("write_shard",),
                           fail_times=None) as store:
        ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=2,
                             shardings=shardings, shard_axis="data",
                             blocking=False)
        with pytest.raises(res.AsyncSaveError):
            res.wait_for_save()
    assert store.failures_injected >= 1
    # the killed save left no committed step_2 (tmp cleaned, not renamed)
    assert not os.path.isdir(ckpt.step_dir(str(chaos_ckpt_dir), 2))
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=3,
                         shardings=shardings, shard_axis="data")
    chaos.corrupt_shard(str(chaos_ckpt_dir), 3, rank=5)
    target, _ = _synthetic_state()
    with pytest.warns(res.CheckpointFallbackWarning) as record:
        restored, step = res.restore_resilient(str(chaos_ckpt_dir), target)
    assert step == 1
    assert any("step 3" in str(w.message) for w in record)
    np.testing.assert_array_equal(np.asarray(restored[1]["exp_avg"]),
                                  np.asarray(state[1]["exp_avg"]))


def test_corrupt_shard_names_failure_under_direct_verify(chaos_ckpt_dir):
    state, shardings = _synthetic_state()
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings, shard_axis="data")
    chaos.corrupt_shard(str(chaos_ckpt_dir), 1, rank=2)
    with pytest.raises(ckpt.CheckpointCorruptionError):
        ckpt.verify_checkpoint(str(chaos_ckpt_dir), 1)


# --------------------------------------- flagship reshard + device loss


def _flagship_state_flat(state):
    """(params, opt_state) → comparable pieces."""
    params, opt = state
    return params, opt


@pytest.mark.slow  # 4 flagship jit constructions on the 8-device mesh
@pytest.mark.parametrize("plan,bitwise", [("fp32", True),
                                          ("bf16_fit", False)])
def test_flagship_sharded_reshard_parity(tmp_path, plan, bitwise):
    """ISSUE 3 acceptance: 8→4→8 reshard of GPT-1.3B-toy ZeRO state
    matches the unsharded restore bitwise (fp32) / ≤ 1 bf16 ulp
    (bf16_fit); the direct 8→1 debug restore holds the same parity
    against the source topology."""
    cfg = _toy_cfg()
    build = flagship_elastic_build(cfg, plan=plan, lr=1e-3)
    batches = _golden_batches(cfg, 2)

    step_fn, state8, shardings = build(jax.devices()[:8])
    for b in batches:
        state8, _ = step_fn(state8, b)
    d_sharded = str(tmp_path / "sharded")
    d_plain = str(tmp_path / "plain")
    # the state is live (moments 1-D): a sharded save takes its stacked
    # view (save_zero_checkpoint does), an unsharded one the arrays
    res.save_zero_checkpoint(d_sharded, state8, step=2,
                             shardings=shardings)
    ckpt.save_checkpoint(d_plain, state8, step=2)

    # 8 -> 4
    _, state4_t, _ = build(jax.devices()[:4])
    state4, s = res.restore_zero_checkpoint(d_sharded, state4_t)
    assert s == 2
    assert state4[1].exp_avg.shape == state4_t[1].exp_avg.shape
    assert state4[1].exp_avg.ndim == 1
    for leaf_r, leaf_s in zip(state4[1][1:], state8[1][1:]):  # moments
        _assert_flat_parity(leaf_r, leaf_s, bitwise=bitwise)

    # 4 -> 8, against the unsharded restore of the same state
    d_mid = str(tmp_path / "mid")
    res.save_zero_checkpoint(d_mid, state4, step=2, shardings=shardings)
    _, state8_t, _ = build(jax.devices()[:8])
    state8_rt, _ = res.restore_zero_checkpoint(d_mid, state8_t)
    state8_direct, _ = ckpt.restore_checkpoint(d_plain, target=state8_t,
                                               verify=True)
    for a, b in zip(jax.tree_util.tree_leaves(state8_rt),
                    jax.tree_util.tree_leaves(state8_direct)):
        if bitwise:
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        else:
            assert _bf16_ulp_diff(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32)) <= 1

    # 8 -> 1: the single-chip debug restore
    _, state1_t, _ = build(jax.devices()[:1])
    state1, _ = res.restore_zero_checkpoint(d_sharded, state1_t)
    for leaf_r, leaf_s in zip(state1[1][1:], state8[1][1:]):
        _assert_flat_parity(leaf_r, leaf_s, bitwise=bitwise)


@pytest.mark.slow  # two flagship jit constructions + 7 train steps
def test_device_loss_resumes_on_submesh_with_golden_trajectory(tmp_path):
    """ISSUE 3 acceptance: a deterministic device-loss chaos run (4 of 8
    devices lost at step 3) rebuilds the ZeRO step on the surviving
    4-device submesh, resumes from the newest intact sharded checkpoint
    (step 2), and reproduces the ``gpt1p3b_toy_zero`` golden loss
    trajectory from the restored step."""
    from tests.L1.common.harness import load_baseline

    golden = load_baseline("gpt1p3b_toy_zero")
    assert golden is not None and len(golden) == 6

    cfg = _toy_cfg()
    losses = []
    build = flagship_elastic_build(cfg, plan="bf16_fit", lr=1e-3,
                                   on_loss=losses.append)
    dl = chaos.DeviceLoss(at_step=3, device_ids=jax.devices()[4:8])
    result = res.run_elastic_training(
        build, jax.devices()[:8], _golden_batches(cfg, 6),
        ckpt_dir=str(tmp_path / "ckpt"), save_every=1, on_step=dl.poll,
        max_restarts=2)
    assert result.restarts == 1
    assert len(result.devices) == 4
    assert result.lost_devices == [4, 5, 6, 7]
    assert result.step == 6

    # 7 losses: steps 1-3 on 8 devices, then the replayed step 3 and
    # steps 4-6 on the 4-device submesh after the step-2 restore
    assert len(losses) == 7
    # the 8-device prefix IS the golden run
    np.testing.assert_array_equal(losses[:3], golden[:3])
    # resumed-on-submesh steps reproduce the golden trajectory from the
    # restored step: bf16 compute quantizes away the reduction-order
    # difference of the shrunken data axis — ≤ 1 bf16 ulp, 0 in practice
    for got, want in zip(losses[3:], golden[2:]):
        assert _bf16_ulp_diff(np.float32(got), np.float32(want)) <= 1, (
            losses, golden)


# ----------------------------------------- multi-axis (3-D) resilience


def _synthetic_state_3d(lead=(4, 1, 2), shard=32, seed=0):
    """A 3-D-flagship-shaped state without the model: replicated params,
    opt partitions stacked ``[dp, pp, tp, shard]`` over the linearized
    world, broadcast step counter stacked per coordinate."""
    rng = np.random.RandomState(seed)
    params = {"w": jnp.asarray(rng.randn(16), jnp.float32)}
    opt = {
        "step": jnp.broadcast_to(jnp.asarray(5, jnp.int32), lead),
        "exp_avg": jnp.asarray(rng.randn(*lead, shard), jnp.float32),
        "exp_avg_sq": jnp.asarray(
            np.abs(rng.randn(*lead, shard)), jnp.float32),
    }
    shardings = (P(), P("data", "pipeline", "tensor"))
    axes = {"data": lead[0], "pipeline": lead[1], "tensor": lead[2]}
    return (params, opt), shardings, axes


def _target_3d(lead, shard):
    return ({"w": jnp.zeros(16, jnp.float32)},
            {"step": jnp.zeros(lead, jnp.int32),
             "exp_avg": jnp.zeros((*lead, shard), jnp.float32),
             "exp_avg_sq": jnp.zeros((*lead, shard), jnp.float32)})


def test_format4_manifest_and_shard_files(chaos_ckpt_dir):
    """The format-4 contract (docs/resilience.md "3D topologies"):
    shard files keyed by (d, p, t) mesh coordinates, per-coordinate
    CRC32 digests, a mesh_axes topology record, replicated leaves
    stored once."""
    import json

    state, shardings, axes = _synthetic_state_3d((4, 1, 2))
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings, shard_axes=axes)
    d = ckpt.step_dir(str(chaos_ckpt_dir), 1)
    names = sorted(os.listdir(d))
    assert "arrays.npz" in names  # the replicated params
    want = [ckpt.shard_file_coords((dd, 0, t))
            for dd in range(4) for t in range(2)]
    assert all(w in names for w in want)
    with open(os.path.join(d, "manifest.json")) as f:
        man = json.load(f)
    assert man["format"] == 4
    assert man["topology"]["mesh_axes"] == {
        "data": 4, "pipeline": 1, "tensor": 2}
    opt_entries = {k: e for k, e in man["leaves"].items()
                   if e.get("shard_axes")}
    assert len(opt_entries) == 3
    for e in opt_entries.values():
        assert e["shard_axes"] == ["data", "pipeline", "tensor"]
        assert len(e["crc32_shards"]) == 8  # one digest per coordinate
    step_e = next(e for k, e in opt_entries.items() if "step" in k)
    assert step_e["replicated_shards"] is True
    assert ckpt.verify_checkpoint(str(chaos_ckpt_dir), 1) == 1


def test_garbled_mesh_axes_manifest_is_corruption(chaos_ckpt_dir):
    """A valid-JSON manifest whose topology lost mesh_axes (bit rot /
    partial overwrite) must surface as CheckpointCorruptionError under
    verify — not a raw KeyError — so restore_resilient's fallback walk
    can condemn the step and move to an older intact checkpoint."""
    import json

    state, shardings, axes = _synthetic_state_3d((4, 1, 2))
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings, shard_axes=axes)
    mpath = os.path.join(ckpt.step_dir(str(chaos_ckpt_dir), 1),
                         "manifest.json")
    with open(mpath) as f:
        man = json.load(f)
    man["topology"]["mesh_axes_corrupt"] = man["topology"].pop("mesh_axes")
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(ckpt.CheckpointCorruptionError):
        ckpt.restore_checkpoint(str(chaos_ckpt_dir), state, verify=True)


def _schema_total(raw: int, world: int) -> int:
    """total_multiple_of = 128·world, as the real flat schema pads."""
    m = 128 * world
    return (raw + m - 1) // m * m


@pytest.mark.parametrize("src,dst", [
    ((4, 1, 2), (2, 2, 2)),
    ((2, 2, 2), (8, 1, 1)),
    ((8, 1, 1), (1, 1, 1)),
    ((4, 1, 2), (1, 1, 1)),
    ((1, 1, 1), (4, 2, 1)),
    ((2, 2, 2), (4, 2, 1)),
])
def test_format4_reshard_sweep_bitwise(chaos_ckpt_dir, src, dst):
    """Property-style (dp, pp, tp) reshape sweep: restored optimizer
    state is fp32-BITWISE equal to the source's logical flat buffer for
    any N→M reshape of the mesh, the broadcast counter re-broadcasts,
    and schema tail padding grows/trims exactly — modelled on the real
    flat schema (raw content + zeros to 128·world)."""
    raw = 1500
    rng = np.random.RandomState(7)
    buf = rng.randn(raw).astype(np.float32)
    world_s, world_d = int(np.prod(src)), int(np.prod(dst))
    total_s = _schema_total(raw, world_s)
    total_d = _schema_total(raw, world_d)

    def _stacked(lead, total):
        world = int(np.prod(lead))
        flat = np.zeros((total,), np.float32)
        flat[:raw] = buf
        return jnp.asarray(flat.reshape(*lead, total // world))

    state = ({"w": jnp.asarray(buf[:16])},
             {"step": jnp.broadcast_to(jnp.asarray(5, jnp.int32), src),
              "exp_avg": _stacked(src, total_s),
              "exp_avg_sq": _stacked(src, total_s)})
    shardings = (P(), P("data", "pipeline", "tensor"))
    axes = {"data": src[0], "pipeline": src[1], "tensor": src[2]}
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings, shard_axes=axes)
    target = _target_3d(dst, total_d // world_d)
    (p, o), step = res.restore_resilient(str(chaos_ckpt_dir), target)
    assert step == 1
    assert np.all(np.asarray(o["step"]) == 5)
    assert o["step"].shape == tuple(dst)
    for leaf in ("exp_avg", "exp_avg_sq"):
        got = np.asarray(o[leaf]).reshape(-1)
        np.testing.assert_array_equal(got[:raw], buf)  # fp32 bitwise
        assert np.all(got[raw:] == 0)


def test_format4_roundtrip_8_to_222_to_8(chaos_ckpt_dir):
    """The ISSUE 6 round-trip: (8,1,1) → (2,2,2) → (8,1,1) restores the
    optimizer state fp32-bitwise."""
    state, shardings, axes = _synthetic_state_3d((8, 1, 1), 32)
    d1 = str(chaos_ckpt_dir / "a")
    d2 = str(chaos_ckpt_dir / "b")
    ckpt.save_checkpoint(d1, state, step=1, shardings=shardings,
                         shard_axes=axes)
    mid, _ = res.restore_resilient(d1, _target_3d((2, 2, 2), 32))
    ckpt.save_checkpoint(d2, mid, step=1, shardings=shardings,
                         shard_axes={"data": 2, "pipeline": 2,
                                     "tensor": 2})
    (p, o), _ = res.restore_resilient(d2, _target_3d((8, 1, 1), 32))
    for leaf in ("exp_avg", "exp_avg_sq"):
        np.testing.assert_array_equal(np.asarray(o[leaf]),
                                      np.asarray(state[1][leaf]))
    assert np.all(np.asarray(o["step"]) == 5)


def test_format4_pp_stage_remap_of_layer_slices(chaos_ckpt_dir):
    """A pp-stacked layer-slice leaf ([pp, L/pp, h], spec leading with
    "pipeline") re-maps its layer slices exactly across a pp change —
    the C-order flatten contract makes stage boundaries land on layer
    boundaries."""
    rng = np.random.RandomState(3)
    layers = jnp.asarray(rng.randn(8, 16), jnp.float32)  # L=8 logical
    state = {"stages": layers.reshape(2, 4, 16)}         # pp=2
    shardings = {"stages": P("pipeline")}
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings,
                         shard_axes={"data": 1, "pipeline": 2,
                                     "tensor": 1})
    out, _ = ckpt.restore_checkpoint(
        str(chaos_ckpt_dir), {"stages": jnp.zeros((4, 2, 16))})
    np.testing.assert_array_equal(
        np.asarray(out["stages"]).reshape(8, 16), np.asarray(layers))
    # and down to the pp=1 debug restore
    out1, _ = ckpt.restore_checkpoint(
        str(chaos_ckpt_dir), {"stages": jnp.zeros((1, 8, 16))})
    np.testing.assert_array_equal(
        np.asarray(out1["stages"]).reshape(8, 16), np.asarray(layers))


def test_format3_restores_byte_identical_through_new_path(chaos_ckpt_dir):
    """Format-3 ("data"-axis) checkpoints keep restoring BYTE-identically
    through the format-4-capable path (ISSUE 6 acceptance), including
    into a 3-D-shaped target (the migration direction)."""
    state, shardings = _synthetic_state(8, 32)
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings, shard_axis="data")
    # byte-identical same-topology restore
    (p, o), _ = res.restore_resilient(str(chaos_ckpt_dir),
                                      _synthetic_state(8, 32)[0])
    for k in ("step", "exp_avg", "exp_avg_sq"):
        np.testing.assert_array_equal(np.asarray(o[k]),
                                      np.asarray(state[1][k]))
    # format-3 → 3-D target: the dp stack linearizes into the
    # (dp', pp', tp') world exactly (migration note, docs/resilience.md)
    target = _target_3d((2, 1, 2), 64)
    (_, o3), _ = res.restore_resilient(str(chaos_ckpt_dir), target)
    for k in ("exp_avg", "exp_avg_sq"):
        np.testing.assert_array_equal(
            np.asarray(o3[k]).reshape(-1),
            np.asarray(state[1][k]).reshape(-1))
    assert np.all(np.asarray(o3["step"]) == 5)


def test_best_surviving_submesh_policy():
    """Largest-divisor per axis, shrinking dp before tp before pp; dp
    additionally divides the global batch."""
    devs = list(range(8))
    # lose 2 of (4, 2, 1): dp shrinks 4→2, tp/pp untouched
    assert res.best_surviving_submesh(devs[:6], (4, 2, 1)) == (
        devs[:4], (2, 2, 1))
    # batch divisibility caps dp
    assert res.best_surviving_submesh(devs[:6], (4, 2, 1),
                                      batch_size=6) == (devs[:4],
                                                        (2, 2, 1))
    assert res.best_surviving_submesh(devs[:6], (4, 2, 1),
                                      batch_size=9) == (devs[:2],
                                                        (1, 2, 1))
    # tp shrinks only after dp is exhausted
    assert res.best_surviving_submesh(devs[:1], (4, 2, 1)) == (
        devs[:1], (1, 1, 1))
    assert res.best_surviving_submesh(devs[:3], (2, 4, 1)) == (
        devs[:2], (1, 2, 1))
    # pp survives while tp gives way: (1, 4, 2) on 7 survivors
    assert res.best_surviving_submesh(devs[:7], (1, 4, 2)) == (
        devs[:4], (1, 2, 2))


def test_watchdog_per_axis_attribution():
    """A stalled tp group shows up as the suspect tensor index: every
    device but the (t=1) column heartbeats; the report's axis_groups
    names tensor group 1 (and no data suspect, since every data row
    contains a stale device symmetrically... the stale column makes
    every data group contain exactly one stale device, so data ages tie
    and only the tensor axis diverges)."""
    import time as _time

    mesh_axes = {"data": 4, "tensor": 2}
    coords = {i: (i // 2, i % 2) for i in range(8)}
    wd = res.Watchdog(timeout=60.0, devices=list(range(8)),
                      mesh_axes=mesh_axes, device_coords=coords,
                      poll_interval=0.01)
    try:
        with wd.step(0):
            pass  # stamps everyone together
        _time.sleep(0.05)
        for d in range(8):
            if coords[d][1] != 1:  # tensor column 1 goes silent
                wd.beat(d)
        report = wd.report()
        ax = report["axis_groups"]
        assert ax["mesh_axes"] == mesh_axes
        assert ax["suspect"].get("tensor") == 1
        assert "data" not in ax["suspect"]  # ties implicate nothing
        g1 = ax["groups"]["tensor"]["1"]
        g0 = ax["groups"]["tensor"]["0"]
        assert g1["max_age_s"] > g0["max_age_s"]
        # a lost device dominates the attribution
        wd.mark_lost([7])
        ax2 = wd.axis_report()
        assert 7 in ax2["groups"]["tensor"]["1"]["lost"]
        assert ax2["suspect"]["tensor"] == 1
    finally:
        wd.close()


def test_watchdog_mesh_derives_axes():
    """Passing a jax Mesh derives mesh_axes + device coordinates."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 1, 2),
                ("data", "pipeline", "tensor"))
    with res.Watchdog(timeout=60.0, mesh=mesh) as wd:
        assert wd.mesh_axes == {"data": 4, "pipeline": 1, "tensor": 2}
        assert len(wd.device_coords) == 8
        with wd.step(0):
            pass
        assert wd.report()["axis_groups"]["mesh_axes"]["tensor"] == 2


def test_watchdog_never_beaten_group_ranks_stalest():
    """A live device with NO heartbeat yet is infinitely stale, not
    infinitely fresh: a group wedged before its first completed step
    must become the suspect, never the freshly-beaten healthy group
    (and its max_age_s stays None — no observation — so the report
    stays JSON-safe)."""
    import json as _json

    mesh_axes = {"data": 2, "tensor": 1}
    coords = {0: (0, 0), 1: (1, 0)}
    with res.Watchdog(timeout=60.0, devices=[0, 1], mesh_axes=mesh_axes,
                      device_coords=coords) as wd:
        wd.beat(0)  # data group 0 healthy; group 1 never heartbeat
        ax = wd.axis_report()
        assert ax["suspect"].get("data") == 1
        assert ax["groups"]["data"]["1"]["max_age_s"] is None
        _json.dumps(ax)


def test_kill_mid_async_save_3d_newest_intact_shard_set_wins(
        chaos_ckpt_dir):
    """The 3-D chaos acceptance case (ISSUE 6 satellite): step 1 lands
    intact; the step-2 ASYNC multi-axis save dies mid-shard-set; step 3
    lands but a TENSOR-leg coordinate's shard file is corrupted.
    restore_resilient must skip step 3 (one bad coordinate condemns the
    whole set), never see a partial step 2, and land on step 1."""
    state, shardings, axes = _synthetic_state_3d((2, 1, 2), 32)
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=1,
                         shardings=shardings, shard_axes=axes)
    with chaos.FaultyStore(fail_events=("write_shard",),
                           fail_times=None) as store:
        ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=2,
                             shardings=shardings, shard_axes=axes,
                             blocking=False)
        with pytest.raises(res.AsyncSaveError):
            res.wait_for_save()
    assert store.failures_injected >= 1
    assert not os.path.isdir(ckpt.step_dir(str(chaos_ckpt_dir), 2))
    ckpt.save_checkpoint(str(chaos_ckpt_dir), state, step=3,
                         shardings=shardings, shard_axes=axes)
    chaos.corrupt_shard(str(chaos_ckpt_dir), 3, (1, 0, 1))  # tp leg
    target = _synthetic_state_3d((2, 1, 2), 32)[0]
    with pytest.warns(res.CheckpointFallbackWarning) as record:
        restored, step = res.restore_resilient(str(chaos_ckpt_dir),
                                               target)
    assert step == 1
    assert any("step 3" in str(w.message) for w in record)
    np.testing.assert_array_equal(np.asarray(restored[1]["exp_avg"]),
                                  np.asarray(state[1]["exp_avg"]))


def test_reshard_tree_in_memory_multi_axis():
    """reshard_tree / reshard_zero_state(lead_shape=...) — the in-memory
    twins of the format-4 reshard — agree with the on-disk contract."""
    from apex_tpu.contrib.optimizers import (
        DistributedFusedAdam, ShardedOptState, reshard_zero_state)
    from apex_tpu.multi_tensor.flat import reshard_tree

    params = {"w": jnp.asarray(np.random.RandomState(1).randn(300),
                               jnp.float32)}
    opt = DistributedFusedAdam()
    sch8 = opt.make_schema(params, 8)
    sch4 = opt.make_schema(params, 4)
    rng = np.random.RandomState(2)
    raw = sum(sch8.sizes)

    def _zeroed(shape):
        a = rng.randn(int(np.prod(shape))).astype(np.float32)
        a[raw:] = 0
        return jnp.asarray(a.reshape(shape))

    stacked = ShardedOptState(
        step=jnp.broadcast_to(jnp.asarray(3, jnp.int32), (4, 1, 2)),
        exp_avg=_zeroed((4, 1, 2, sch8.total // 8)),
        exp_avg_sq=_zeroed((4, 1, 2, sch8.total // 8)))
    out = reshard_zero_state(stacked, lead_shape=(2, 2, 1), schema=sch4)
    assert out.exp_avg.shape == (2, 2, 1, sch4.total // 4)
    assert np.all(np.asarray(out.step) == 3)
    assert out.step.shape == (2, 2, 1)
    for a, b in ((out.exp_avg, stacked.exp_avg),
                 (out.exp_avg_sq, stacked.exp_avg_sq)):
        _assert_flat_parity(a, b, bitwise=True)
    # reshard_tree: same result through the spec-driven tree API
    spec = ShardedOptState(step=P("data", "pipeline", "tensor"),
                           exp_avg=P("data", "pipeline", "tensor"),
                           exp_avg_sq=P("data", "pipeline", "tensor"))
    out2 = reshard_tree(
        stacked, spec, spec,
        target=ShardedOptState(
            step=jnp.zeros((2, 2, 1), jnp.int32),
            exp_avg=jnp.zeros((2, 2, 1, sch4.total // 4)),
            exp_avg_sq=jnp.zeros((2, 2, 1, sch4.total // 4))),
        axes_from={"data": 4, "pipeline": 1, "tensor": 2},
        axes_to={"data": 2, "pipeline": 2, "tensor": 1})
    for a, b in zip(jax.tree_util.tree_leaves(out2),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.slow  # three flagship jit constructions + 13 train steps
def test_3d_device_loss_resumes_on_best_submesh_with_golden(tmp_path):
    """ISSUE 6 acceptance: an 8-device run sharded (dp=4, tp=2) loses a
    device at step 3 → elastic rebuild on the best surviving submesh
    (dp shrinks to 2, tp=2 survives) → restore from the multi-axis
    format-4 shard set → the resumed loss trajectory matches the
    pre-loss golden run (same topology, uninterrupted) at ≤ 1 bf16
    ulp."""
    cfg = _toy_cfg()
    batches = _golden_batches(cfg, 6)

    # the pre-loss golden: uninterrupted (4, 2, 1) run
    golden = []
    build_g = flagship_elastic_build(cfg, plan="bf16_fit", lr=1e-3,
                                     on_loss=golden.append)
    step_fn, state, _ = build_g(jax.devices()[:8], mesh_shape=(4, 2, 1))
    for b in batches:
        state, _ = step_fn(state, b)
    assert len(golden) == 6

    losses = []
    build = flagship_elastic_build(cfg, plan="bf16_fit", lr=1e-3,
                                   on_loss=losses.append)
    dl = chaos.DeviceLoss(at_step=3, device_ids=jax.devices()[4:6])
    result = res.run_elastic_training(
        build, jax.devices()[:8], batches,
        ckpt_dir=str(tmp_path / "ckpt"), save_every=1, on_step=dl.poll,
        max_restarts=2, mesh_shape=(4, 2, 1), batch_size=8)
    assert result.restarts == 1
    assert result.mesh_shape == (2, 2, 1)  # dp shrank, tp survived
    assert len(result.devices) == 4
    assert result.lost_devices == [4, 5]
    assert result.step == 6

    # the final checkpoint on disk is a format-4 multi-axis shard set
    import json

    with open(os.path.join(ckpt.step_dir(str(tmp_path / "ckpt"), 6),
                           "manifest.json")) as f:
        man = json.load(f)
    assert man["format"] == 4
    assert man["topology"]["mesh_axes"] == {"data": 2, "pipeline": 1,
                                            "tensor": 2}

    # 7 losses: steps 1-3 on (4,2,1), then the replayed step 3 and
    # steps 4-6 on the (2,2,1) submesh after the step-2 restore
    assert len(losses) == 7
    np.testing.assert_array_equal(losses[:3], golden[:3])
    for got, want in zip(losses[3:], golden[2:]):
        assert _bf16_ulp_diff(np.float32(got), np.float32(want)) <= 1, (
            losses, golden)

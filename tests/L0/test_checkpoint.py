"""Checkpoint/resume tests (SURVEY.md §5.4).

Reference coverage being matched: amp state round-trip
(tests/L0/run_amp/test_checkpointing.py), FP16_Optimizer master-weight
state_dicts (fp16_optimizer.py:209-271), plus the TPU-design extensions:
precision-portable fp32 storage and restore onto a different-size mesh.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu import checkpoint as ckpt
from apex_tpu.amp import scaler as scaler_lib
from apex_tpu.optimizers import FusedAdam


def _toy_params(key):
    k1, k2 = jax.random.split(key)
    return {
        "dense": {"w": jax.random.normal(k1, (8, 16), jnp.float32),
                  "b": jnp.zeros((16,), jnp.float32)},
        "out": {"w": jax.random.normal(k2, (16, 4), jnp.float32)},
    }


def _loss(params, x, y):
    h = jnp.tanh(x @ params["dense"]["w"] + params["dense"]["b"])
    logits = h @ params["out"]["w"]
    return jnp.mean((logits - y) ** 2)


def _make_step(opt, amp_state):
    @jax.jit
    def step(state, x, y):
        def scaled_loss(p):
            return amp_state.scaler.scale(_loss(p, x, y), state.scaler_state)

        grads = jax.grad(scaled_loss)(state.params)
        grads, finite = amp_state.scaler.unscale(grads, state.scaler_state)
        new_p, new_o = opt.step_if_finite(grads, state.opt_state, state.params, finite)
        return state.replace(
            step=state.step + 1,
            params=new_p,
            opt_state=new_o,
            scaler_state=amp_state.scaler.update(state.scaler_state, finite),
        )

    return step


def _train(n_steps, state, step_fn, key):
    for i in range(n_steps):
        k = jax.random.fold_in(key, i)
        x = jax.random.normal(k, (32, 8), jnp.float32)
        y = jax.random.normal(jax.random.fold_in(k, 1), (32, 4), jnp.float32)
        state = step_fn(state, x, y)
    return state


def test_round_trip_exact(tmp_path):
    params = _toy_params(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    amp_state = amp.initialize("O2")
    state = ckpt.TrainState.create(params, opt.init(params), amp_state.scaler.init())
    state = _train(3, state, _make_step(opt, amp_state), jax.random.PRNGKey(1))

    ckpt.save_checkpoint(str(tmp_path), state, step=int(state.step))
    restored, step = ckpt.restore_checkpoint(str(tmp_path), target=state)
    assert step == 3
    for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # NamedTuple / dataclass structure survives
    assert isinstance(restored, ckpt.TrainState)
    assert restored.scaler_state.loss_scale == state.scaler_state.loss_scale


def test_resume_continues_trajectory_bitwise(tmp_path):
    """3 steps + save/restore + 3 steps == 6 straight steps, bitwise.

    The trajectory-parity discipline of the reference L1 tier
    (tests/L1/common/compare.py:40-64) applied to resume.
    """
    params = _toy_params(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2, weight_decay=0.01)
    amp_state = amp.initialize("O2")
    step_fn = _make_step(opt, amp_state)
    key = jax.random.PRNGKey(7)

    s0 = ckpt.TrainState.create(params, opt.init(params), amp_state.scaler.init())
    straight = _train(6, s0, step_fn, key)

    half = _train(3, s0, step_fn, key)
    ckpt.save_checkpoint(str(tmp_path), half, step=3)
    resumed, _ = ckpt.restore_checkpoint(str(tmp_path), target=half)
    # continue with the same per-step data keys (fold_in i=3..5)
    for i in range(3, 6):
        k = jax.random.fold_in(key, i)
        x = jax.random.normal(k, (32, 8), jnp.float32)
        y = jax.random.normal(jax.random.fold_in(k, 1), (32, 4), jnp.float32)
        resumed = step_fn(resumed, x, y)

    for a, b in zip(jax.tree_util.tree_leaves(straight), jax.tree_util.tree_leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_precision_portable_fp32_on_disk(tmp_path):
    """bf16 leaves are stored fp32 (O2StateDictHook parity,
    _initialize.py:133-142) and restore to the target's dtype."""
    tree = {"w": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3) / 3.0,
            "b": jnp.ones((3,), jnp.float32)}
    ckpt.save_checkpoint(str(tmp_path), tree, step=0)

    import numpy as _np
    with _np.load(str(tmp_path) + "/step_0000000000/arrays.npz") as z:
        stored = {k: z[k].dtype for k in z.files}
    assert all(dt == _np.float32 for dt in stored.values())

    # restore into a bf16 target -> bf16; into an fp32 target -> fp32
    back, _ = ckpt.restore_checkpoint(str(tmp_path), target=tree)
    assert back["w"].dtype == jnp.bfloat16
    fp32_target = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)
    back32, _ = ckpt.restore_checkpoint(str(tmp_path), target=fp32_target)
    assert back32["w"].dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(back32["w"]), np.asarray(tree["w"], dtype=np.float32))


def test_restore_on_different_mesh_size(tmp_path):
    """Save under an 8-way dp mesh, restore onto a 4-way mesh — the
    restart-on-different-topology design of SURVEY §5.4 (impossible with the
    reference's per-rank torch.save)."""
    devs = jax.devices()
    assert len(devs) >= 8
    mesh8 = Mesh(np.array(devs[:8]), ("data",))
    specs = {"w": P("data", None), "b": P()}
    w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    b = jnp.ones((8,), jnp.float32)
    tree = {
        "w": jax.device_put(w, NamedSharding(mesh8, specs["w"])),
        "b": jax.device_put(b, NamedSharding(mesh8, specs["b"])),
    }
    ckpt.save_checkpoint(str(tmp_path), tree, step=10, shardings=specs)

    mesh4 = Mesh(np.array(devs[:4]), ("data",))
    restored, step = ckpt.restore_checkpoint(
        str(tmp_path), target=tree, mesh=mesh4, shardings=specs)
    assert step == 10
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
    assert restored["w"].sharding.mesh.shape["data"] == 4

    # manifest specs alone (no shardings arg) also work
    restored2, _ = ckpt.restore_checkpoint(str(tmp_path), target=tree, mesh=mesh4)
    assert restored2["w"].sharding.spec == P("data", None)


def test_latest_step_and_keep(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4):
        ckpt.save_checkpoint(str(tmp_path), tree, step=s, keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 4
    import os
    kept = sorted(n for n in os.listdir(tmp_path) if n.startswith("step_"))
    assert len(kept) == 2


def test_restore_without_target_nested_dict(tmp_path):
    tree = {"a": {"b": jnp.ones((2, 2)), "c": jnp.zeros((3,))}, "d": jnp.asarray(5)}
    ckpt.save_checkpoint(str(tmp_path), tree, step=0)
    out, _ = ckpt.restore_checkpoint(str(tmp_path))
    np.testing.assert_array_equal(np.asarray(out["a"]["b"]), np.ones((2, 2)))
    np.testing.assert_array_equal(np.asarray(out["d"]), 5)


def test_raw_half_storage_round_trips(tmp_path):
    """fp32_portable=False keeps bf16 bits exactly (stored as a uint16 view)."""
    tree = {"w": (jnp.arange(7, dtype=jnp.bfloat16) / 3.0)}
    ckpt.save_checkpoint(str(tmp_path), tree, step=0, fp32_portable=False)
    back, _ = ckpt.restore_checkpoint(str(tmp_path), target=tree)
    assert back["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(back["w"]).view(np.uint16), np.asarray(tree["w"]).view(np.uint16))


def test_latest_step_survives_crash_artifacts(tmp_path):
    import os
    tree = {"x": jnp.zeros((2,))}
    ckpt.save_checkpoint(str(tmp_path), tree, step=2)
    # a save that died mid-write: .tmp dir with a manifest + truncated marker
    os.makedirs(tmp_path / "step_0000000003.tmp")
    (tmp_path / "step_0000000003.tmp" / "manifest.json").write_text("{}")
    (tmp_path / "latest").write_text("")
    (tmp_path / "step_junk").mkdir()
    assert ckpt.latest_step(str(tmp_path)) == 2
    restored, step = ckpt.restore_checkpoint(str(tmp_path), target=tree)
    assert step == 2


def test_keep_never_deletes_just_written_rollback(tmp_path):
    """Rollback-resume: saving a LOWER step than what's on disk with keep=1
    must keep the new save, pruning by recency not step number."""
    import os
    tree = {"x": jnp.zeros((2,))}
    ckpt.save_checkpoint(str(tmp_path), tree, step=5)
    path = ckpt.save_checkpoint(str(tmp_path), tree, step=3, keep=1)
    assert os.path.exists(path)
    assert ckpt.latest_step(str(tmp_path)) == 3
    assert not os.path.exists(ckpt.step_dir(str(tmp_path), 5))


def test_prefix_shardings_broadcast(tmp_path):
    """A PartitionSpec given at a subtree root applies to every leaf under it
    (pjit in_shardings broadcast rule)."""
    import json
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:4]), ("data",))
    tree = {"params": {"w": jnp.zeros((8, 2)), "v": jnp.zeros((8,))}}
    ckpt.save_checkpoint(str(tmp_path), tree, step=0,
                         shardings={"params": P("data")})
    with open(str(tmp_path) + "/step_0000000000/manifest.json") as f:
        man = json.load(f)
    assert all(e["spec"] == ["data"] for e in man["leaves"].values())
    restored, _ = ckpt.restore_checkpoint(
        str(tmp_path), target=tree, mesh=mesh, shardings={"params": P("data")})
    assert restored["params"]["w"].sharding.spec == P("data")


def test_missing_leaf_errors(tmp_path):
    ckpt.save_checkpoint(str(tmp_path), {"x": jnp.zeros((2,))}, step=0)
    with pytest.raises(KeyError):
        ckpt.restore_checkpoint(str(tmp_path), target={"x": jnp.zeros((2,)),
                                                       "y": jnp.zeros((2,))})


def test_missing_leaf_error_lists_all_missing_keys(tmp_path):
    """A target/checkpoint mismatch names EVERY missing leaf plus what the
    checkpoint actually holds — not a bare KeyError on the first key."""
    ckpt.save_checkpoint(str(tmp_path), {"x": jnp.zeros((2,))}, step=0)
    target = {"x": jnp.zeros((2,)), "y": jnp.zeros((2,)), "z": jnp.zeros((3,))}
    with pytest.raises(KeyError) as ei:
        ckpt.restore_checkpoint(str(tmp_path), target=target)
    msg = ei.value.args[0]  # str(KeyError) repr-escapes the quoted keys
    assert "missing 2 leaves" in msg
    assert "['y']" in msg and "['z']" in msg
    assert "['x']" in msg  # ...and says what IS there


def test_malformed_step_names_ignored(tmp_path):
    """Scanning tolerates every crash/user artifact: tmp dirs, non-digit
    suffixes, int()-parseable-but-nonstandard names ("+3", "1_0"), and
    plain files named like steps."""
    import os
    tree = {"x": jnp.zeros((2,))}
    ckpt.save_checkpoint(str(tmp_path), tree, step=4)
    os.makedirs(tmp_path / "step_0000000009.tmp")
    (tmp_path / "step_0000000009.tmp" / "manifest.json").write_text("{}")
    for bad in ("step_+3", "step_1_0", "step_ 7", "step_junk", "step_",
                "step_³", "step_٣"):  # non-ASCII "digits"
        os.makedirs(tmp_path / bad)
        (tmp_path / bad / "manifest.json").write_text("{}")
    (tmp_path / "step_0000000012").write_text("a file, not a dir")
    (tmp_path / "latest").write_text("12")  # marker points at the junk file
    assert ckpt.latest_step(str(tmp_path)) == 4
    restored, step = ckpt.restore_checkpoint(str(tmp_path), target=tree)
    assert step == 4


def test_multi_checkpoint_corrupt_latest_falls_back(tmp_path):
    """Satellite acceptance: save steps N<M, corrupt M's arrays file —
    resilient restore falls back to N and reports the corruption."""
    from apex_tpu import resilience as res
    from apex_tpu.resilience import chaos

    ckpt.save_checkpoint(str(tmp_path), {"x": jnp.ones((4,)) * 1}, step=3)
    ckpt.save_checkpoint(str(tmp_path), {"x": jnp.ones((4,)) * 2}, step=8)
    chaos.corrupt_arrays(str(tmp_path), 8, mode="flip")
    # plain restore of the corrupt step with verify=True refuses
    with pytest.raises(ckpt.CheckpointCorruptionError):
        ckpt.restore_checkpoint(str(tmp_path), target={"x": jnp.zeros((4,))},
                                step=8, verify=True)
    with pytest.warns(res.CheckpointFallbackWarning):
        restored, step = res.restore_resilient(
            str(tmp_path), target={"x": jnp.zeros((4,))})
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.ones(4))


def test_packed_format_round_trip_exact(tmp_path):
    """format 2: one flat superblock file written via the native threaded
    pack (apex_C-parity host runtime) — bitwise equal restore, including
    bf16 leaves stored fp32-portable."""
    params = _toy_params(jax.random.PRNGKey(0))
    params["half"] = jnp.arange(7, dtype=jnp.bfloat16) / 3
    opt = FusedAdam(lr=1e-2)
    amp_state = amp.initialize("O2")
    state = ckpt.TrainState.create(params, opt.init(params),
                                   amp_state.scaler.init())

    ckpt.save_checkpoint(str(tmp_path / "p"), state, step=1, packed=True)
    import os
    d = ckpt.step_dir(str(tmp_path / "p"), 1)
    assert os.path.exists(os.path.join(d, "arrays.pack"))
    assert not os.path.exists(os.path.join(d, "arrays.npz"))

    restored, step = ckpt.restore_checkpoint(str(tmp_path / "p"), target=state)
    assert step == 1
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_matches_npz_content(tmp_path):
    params = _toy_params(jax.random.PRNGKey(2))
    opt = FusedAdam(lr=1e-2)
    amp_state = amp.initialize("O2")
    state = ckpt.TrainState.create(params, opt.init(params),
                                   amp_state.scaler.init())
    ckpt.save_checkpoint(str(tmp_path / "a"), state, step=5, packed=True)
    ckpt.save_checkpoint(str(tmp_path / "b"), state, step=5, packed=False)
    ra, _ = ckpt.restore_checkpoint(str(tmp_path / "a"), target=state)
    rb, _ = ckpt.restore_checkpoint(str(tmp_path / "b"), target=state)
    for a, b in zip(jax.tree_util.tree_leaves(ra),
                    jax.tree_util.tree_leaves(rb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_raw_half_bits(tmp_path):
    params = {"h": jnp.array([1.5, -2.25, 3.0], jnp.bfloat16)}
    ckpt.save_checkpoint(str(tmp_path), params, step=0, packed=True,
                         fp32_portable=False)
    restored, _ = ckpt.restore_checkpoint(str(tmp_path), target=params)
    np.testing.assert_array_equal(np.asarray(restored["h"]),
                                  np.asarray(params["h"]))


def test_restore_without_target_handles_odd_keys(tmp_path):
    """Dict keys containing quotes/brackets/dots survive target=None
    restore via the manifest's structured path components (ADVICE r2:
    keystr re-parsing mangled them)."""
    tree = {"a'b": {"c[0].d": jnp.ones((2,))}, "plain": jnp.zeros((1,))}
    ckpt.save_checkpoint(str(tmp_path), tree, step=0)
    out, _ = ckpt.restore_checkpoint(str(tmp_path))
    np.testing.assert_array_equal(np.asarray(out["a'b"]["c[0].d"]),
                                  np.ones((2,)))
    np.testing.assert_array_equal(np.asarray(out["plain"]), np.zeros((1,)))


def test_bracket_quote_keys_round_trip(tmp_path):
    """Leaves whose dict keys contain quotes/brackets survive save +
    restore (with and without target).  (jax keystr double-quotes such
    keys so these do NOT actually collide; the save-side '#N' rename is
    a defensive guard for any pytree whose keystrs do collide, and spec
    association is keyed by structured path so it is rename-immune.)"""
    tree = {"x": {"y": jnp.ones((2,)) * 3}, "x']['y": jnp.ones((2,)) * 7}
    ckpt.save_checkpoint(str(tmp_path), tree, step=0)
    back, _ = ckpt.restore_checkpoint(str(tmp_path), target=tree)
    np.testing.assert_array_equal(np.asarray(back["x"]["y"]), 3 * np.ones(2))
    np.testing.assert_array_equal(np.asarray(back["x']['y"]), 7 * np.ones(2))
    out, _ = ckpt.restore_checkpoint(str(tmp_path))
    np.testing.assert_array_equal(np.asarray(out["x"]["y"]), 3 * np.ones(2))
    np.testing.assert_array_equal(np.asarray(out["x']['y"]), 7 * np.ones(2))


# --------------------------- the ZeRO state's interchange form (ISSUE 38)


def _stacked_zero_state(lead, shard=256, seed=3):
    """A ZeRO state as the tree before ISSUE 38 carried and saved it:
    every leaf a stack over ``lead``, ``bf16_fit`` dtypes."""
    from apex_tpu.contrib.optimizers import ShardedOptState

    rng = np.random.RandomState(seed)
    return ShardedOptState(
        step=jnp.full(lead, 5, jnp.int32),
        exp_avg=jnp.asarray(rng.randn(*lead, shard), jnp.bfloat16),
        exp_avg_sq=jnp.asarray(rng.rand(*lead, shard), jnp.float32))


@pytest.mark.parametrize("lead,kw,spec", [
    ((8,), dict(shard_axis="data"), P("data")),
    ((4, 1, 2), dict(shard_axes={"data": 4, "pipeline": 1, "tensor": 2}),
     P("data", "pipeline", "tensor")),
], ids=["format3", "format4"])
def test_stacked_checkpoint_restores_bitwise_into_the_live_state(
        tmp_path, lead, kw, spec):
    """A sharded checkpoint written from the stacked form (the call the
    tree made before ISSUE 38, its manifest and shard files) restores
    bit for bit into the live state, whose moments are 1-D; the live
    state saved through ``save_zero_checkpoint`` writes the SAME
    manifest and shard digests; and that restores into a stacked
    target as before."""
    import json
    import os

    from apex_tpu.contrib.optimizers import (
        live_zero_state, stacked_zero_state)
    from apex_tpu.resilience import (
        restore_zero_checkpoint, save_zero_checkpoint)

    params = {"w": jnp.arange(12, dtype=jnp.bfloat16)}
    stacked = _stacked_zero_state(lead)
    shardings = (P(), spec)
    old, new = str(tmp_path / "old"), str(tmp_path / "new")
    ckpt.save_checkpoint(old, (params, stacked), step=2,
                         shardings=shardings, **kw)

    live_target = live_zero_state(
        jax.tree_util.tree_map(jnp.zeros_like, stacked))
    assert live_target.exp_avg.shape == (int(np.prod(lead)) * 256,)
    (rp, live), step = restore_zero_checkpoint(old, (params, live_target))
    assert step == 2
    np.testing.assert_array_equal(np.asarray(rp["w"], np.float32),
                                  np.asarray(params["w"], np.float32))
    for got, tgt, want in zip(live, live_target, stacked):
        assert got.shape == tgt.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want).reshape(tgt.shape))

    # the live state back out, through the view: the same checkpoint
    save_zero_checkpoint(new, (rp, live), step=2, shardings=shardings,
                         **({} if "shard_axis" in kw else kw))

    def manifest(d):
        with open(os.path.join(ckpt.step_dir(d, 2), "manifest.json")) as f:
            return json.load(f)

    a, b = manifest(old), manifest(new)
    assert a["leaves"] == b["leaves"] and a["format"] == b["format"]
    assert a["topology"] == b["topology"]
    assert sorted(os.listdir(ckpt.step_dir(old, 2))) == sorted(
        os.listdir(ckpt.step_dir(new, 2)))
    (_, back), _ = ckpt.restore_checkpoint(
        new, (params, jax.tree_util.tree_map(jnp.zeros_like, stacked)),
        verify=True)
    for got, want in zip(back, stacked):
        assert got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(stacked_zero_state(live), stacked):
        np.testing.assert_array_equal(got, np.asarray(want))


def test_stacked_checkpoint_reshards_into_a_smaller_live_state(tmp_path):
    """8 ranks' stacked save into a 4-rank live state: the stack's
    C-order flattening IS the live moment, whatever the shard count."""
    from apex_tpu.contrib.optimizers import live_zero_state
    from apex_tpu.resilience import restore_zero_checkpoint

    stacked = _stacked_zero_state((8,))
    ckpt.save_checkpoint(str(tmp_path), ({}, stacked), step=1,
                         shardings=(P(), P("data")), shard_axis="data")
    target = live_zero_state(jax.tree_util.tree_map(
        jnp.zeros_like, _stacked_zero_state((4,), shard=512)))
    (_, live), _ = restore_zero_checkpoint(str(tmp_path), ({}, target))
    assert live.step.shape == (4,) and np.all(np.asarray(live.step) == 5)
    for got, want in zip(live[1:], stacked[1:]):
        assert got.shape == (2048,)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want).reshape(-1))


def test_a_live_state_handed_to_a_sharded_save_is_refused(tmp_path):
    """Forgetting the view must not write a shard file a scalar: the
    save names the function that was skipped."""
    from apex_tpu.contrib.optimizers import live_zero_state

    live = live_zero_state(_stacked_zero_state((8,)))
    with pytest.raises(ValueError, match="stacked_zero_state"):
        ckpt.save_checkpoint(str(tmp_path), ({}, live), step=1,
                             shardings=(P(), P("data")), shard_axis="data")


@pytest.mark.parametrize("writer", [True, False])
def test_save_zero_checkpoint_takes_the_view_where_the_snapshot_is(
        tmp_path, monkeypatch, writer):
    """The view is a host transfer of the whole state: a process that
    does not write takes none, and the writer takes it only after the
    fence on an earlier async write (never two host copies at once)."""
    from apex_tpu.contrib import optimizers
    from apex_tpu.contrib.optimizers import live_zero_state
    from apex_tpu.resilience import async_checkpoint, save_zero_checkpoint

    calls = []
    view, fence = optimizers.stacked_zero_state, async_checkpoint.wait_for_save
    monkeypatch.setattr(optimizers, "stacked_zero_state",
                        lambda t: calls.append("view") or view(t))
    monkeypatch.setattr(async_checkpoint, "wait_for_save",
                        lambda *a: calls.append("fence") or fence(*a))
    monkeypatch.setattr(jax, "process_index", lambda: 0 if writer else 1)

    live = live_zero_state(_stacked_zero_state((8,)))
    out = save_zero_checkpoint(str(tmp_path), ({}, live), step=3,
                               shardings=(P(), P("data")))
    assert out == ckpt.step_dir(str(tmp_path), 3)
    if writer:
        assert calls[:2] == ["fence", "view"], calls
        assert ckpt.latest_step(str(tmp_path)) == 3
    else:
        assert calls == [] and ckpt.latest_step(str(tmp_path)) is None

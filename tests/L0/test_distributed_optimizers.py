"""ZeRO sharded-optimizer tests on the 8-device mesh + fp16_utils tier.

Mirrors reference tests: tests/L0/run_optimizers/test_dist_adam.py (sharded
vs unsharded parity), contrib DistributedFusedLAMB paths, fp16util tests
(tests/L0/run_fp16util/).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import fp16_utils, optimizers
from apex_tpu.contrib.optimizers import (
    DistributedFusedAdam,
    DistributedFusedLAMB,
)

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:N_DEV]), ("data",))


def _params(key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": jax.random.normal(k1, (33, 7)),  # deliberately unaligned sizes
        "w2": jax.random.normal(k2, (129,)),
        "b": jax.random.normal(k3, (5, 3)),
    }


class TestDistributedFusedAdam:
    def test_matches_unsharded_fused_adam(self, mesh):
        # reference test_dist_adam.py: sharded optimizer == unsharded Adam
        params = _params(jax.random.PRNGKey(0))
        grads = _params(jax.random.PRNGKey(1))

        dopt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01)
        schema = dopt.make_schema(params, N_DEV)

        def step_fn(p, g):
            state = dopt.init(p, schema, N_DEV)
            # per-device grads: same grads on every device, grad_average
            # divides the psum back to the original values
            new_p, _ = dopt.step(g, state, p, schema)
            return new_p

        out = shard_map(step_fn, mesh=mesh, in_specs=(P(), P()),
                        out_specs=P(), check_rep=False)(params, grads)

        ref_opt = optimizers.FusedAdam(lr=1e-2, weight_decay=0.01,
                                       adam_w_mode=True)
        ref_state = ref_opt.init(params)
        ref_p, _ = ref_opt.step(grads, ref_state, params)
        for k in params:
            np.testing.assert_allclose(out[k], ref_p[k], rtol=1e-5, atol=1e-6)

    def test_matches_unsharded_classic_adam_l2_decay(self, mesh):
        """adam_w_mode=False: L2 decay folds into the grad BEFORE the moment
        updates (reference AdamFunctor ADAM_MODE_1, multi_tensor_adam.cu)."""
        params = _params(jax.random.PRNGKey(2))
        grads = _params(jax.random.PRNGKey(3))

        dopt = DistributedFusedAdam(lr=1e-2, weight_decay=0.05,
                                    adam_w_mode=False)
        schema = dopt.make_schema(params, N_DEV)

        def step_fn(p, g):
            state = dopt.init(p, schema, N_DEV)
            new_p, _ = dopt.step(g, state, p, schema)
            return new_p

        out = shard_map(step_fn, mesh=mesh, in_specs=(P(), P()),
                        out_specs=P(), check_rep=False)(params, grads)

        ref_opt = optimizers.FusedAdam(lr=1e-2, weight_decay=0.05,
                                       adam_w_mode=False)
        ref_p, _ = ref_opt.step(grads, ref_opt.init(params), params)
        for k in params:
            np.testing.assert_allclose(out[k], ref_p[k], rtol=1e-5, atol=1e-6)

    def test_multi_step_convergence(self, mesh):
        params = _params(jax.random.PRNGKey(0))
        target = _params(jax.random.PRNGKey(7))
        dopt = DistributedFusedAdam(lr=5e-2)
        schema = dopt.make_schema(params, N_DEV)

        @jax.jit
        def train_step(p, state):
            def inner(p, state):
                # strip the leading per-device axis: each rank keeps ITS OWN
                # exp_avg/exp_avg_sq shard across steps (P("data") on both
                # specs), not a replicated copy of rank 0's
                state = jax.tree_util.tree_map(lambda a: a[0], state)
                grads = jax.tree_util.tree_map(lambda a, t: a - t, p, target)
                new_p, new_s = dopt.step(grads, state, p, schema)
                return new_p, jax.tree_util.tree_map(lambda a: a[None], new_s)
            return shard_map(inner, mesh=mesh, in_specs=(P(), P("data")),
                             out_specs=(P(), P("data")),
                             check_rep=False)(p, state)

        state0 = dopt.init(params, schema, N_DEV)
        state = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (N_DEV, *a.shape)), state0)

        def dist(p):
            return sum(float(jnp.sum((p[k] - target[k]) ** 2)) for k in p)

        # all 50 steps inside ONE dispatch: repeated host dispatches of the
        # 8-device CPU executable abort intermittently in the runtime's
        # collective thread pool (observed ~2/5 full-suite runs)
        @jax.jit
        def train_50(p, state):
            def body(carry, _):
                p, state = carry
                return train_step(p, state), None

            (p, state), _ = jax.lax.scan(body, (p, state), None, length=50)
            return p, state

        d0 = dist(params)
        p, state = train_50(params, state)
        assert dist(p) < d0 * 0.2

    @pytest.mark.slow  # heaviest dtype-plan parity case (ISSUE 6 wall-clock)
    def test_dtype_plan_close_to_fp32(self, mesh):
        """The r6 memory-fit knobs (bf16 scatter/gather transport + bf16
        momentum storage — the gpt1p3b bf16_fit plan): update math stays
        fp32 inside the fused chain, so one step agrees with the
        all-fp32 optimizer to bf16-rounding tolerance."""
        params = _params(jax.random.PRNGKey(0))
        grads = _params(jax.random.PRNGKey(1))
        dopt = DistributedFusedAdam(
            lr=1e-2, scatter_dtype=jnp.bfloat16,
            gather_dtype=jnp.bfloat16, exp_avg_dtype=jnp.bfloat16)
        schema = dopt.make_schema(params, N_DEV)

        def inner(p, g):
            state = dopt.init(p, schema, N_DEV)
            assert state.exp_avg.dtype == jnp.bfloat16
            new_p, new_s = dopt.step(g, state, p, schema)
            assert new_s.exp_avg.dtype == jnp.bfloat16
            assert new_s.exp_avg_sq.dtype == jnp.float32
            return new_p

        out = shard_map(inner, mesh=mesh, in_specs=(P(), P()),
                        out_specs=P(), check_rep=False)(params, grads)
        ref = DistributedFusedAdam(lr=1e-2)

        def ref_inner(p, g):
            state = ref.init(p, schema, N_DEV)
            new_p, _ = ref.step(g, state, p, schema)
            return new_p

        out_r = shard_map(ref_inner, mesh=mesh, in_specs=(P(), P()),
                          out_specs=P(), check_rep=False)(params, grads)
        for k in params:
            # gathering fp32 params through bf16 transport quantizes the
            # values themselves: the bound is ~2 bf16 ulps relative
            # (one from the gather, one from the update diff).  In the
            # real fit plan params are STORED bf16, so this rounding is
            # the storage format, not an extra loss.
            np.testing.assert_allclose(out[k], out_r[k], rtol=2e-2,
                                       atol=1e-3)

    @pytest.mark.slow  # 8-device e5m2 transport parity (ISSUE 2 CI satellite)
    def test_e5m2_allgather_close(self, mesh):
        params = _params(jax.random.PRNGKey(0))
        grads = _params(jax.random.PRNGKey(1))
        dopt = DistributedFusedAdam(lr=1e-2, e5m2_allgather=True)
        ref = DistributedFusedAdam(lr=1e-2, e5m2_allgather=False)
        schema = dopt.make_schema(params, N_DEV)

        def run(opt):
            def inner(p, g):
                state = opt.init(p, schema, N_DEV)
                new_p, _ = opt.step(g, state, p, schema)
                return new_p
            return shard_map(inner, mesh=mesh, in_specs=(P(), P()),
                             out_specs=P(), check_rep=False)(params, grads)

        out_c, out_r = run(dopt), run(ref)
        for k in params:
            # e5m2 has ~2 mantissa bits: deltas agree to ~25% relative,
            # and the fp32 base is exactly preserved
            np.testing.assert_allclose(out_c[k], out_r[k], rtol=0.3,
                                       atol=1e-3)


class TestDistributedFusedLAMB:
    # slow since PR 22: pays for test_tpu_lowering / test_chip_smoke in tier-1
    @pytest.mark.slow
    def test_step_moves_toward_target_with_clipping(self, mesh):
        params = _params(jax.random.PRNGKey(0))
        dopt = DistributedFusedLAMB(lr=1e-2, max_grad_norm=1.0)
        schema = dopt.make_schema(params, N_DEV)
        big_grads = jax.tree_util.tree_map(lambda a: a * 100.0, params)

        def inner(p, g):
            state = dopt.init(p, schema, N_DEV)
            new_p, _ = dopt.step(g, state, p, schema)
            return new_p

        out = shard_map(inner, mesh=mesh, in_specs=(P(), P()),
                        out_specs=P(), check_rep=False)(params, big_grads)
        # grad clipping must keep the update bounded despite x100 grads
        for k in params:
            delta = float(jnp.max(jnp.abs(out[k] - params[k])))
            assert delta < 0.1, (k, delta)
            assert delta > 0

    # slow since PR 22: pays for test_tpu_lowering / test_chip_smoke in tier-1
    @pytest.mark.slow
    def test_replicated_output_across_ranks(self, mesh):
        params = _params(jax.random.PRNGKey(0))
        grads = _params(jax.random.PRNGKey(1))
        dopt = DistributedFusedLAMB(lr=1e-3)
        schema = dopt.make_schema(params, N_DEV)

        def inner(p, g):
            p = jax.tree_util.tree_map(lambda a: a[0], p)
            g = jax.tree_util.tree_map(lambda a: a[0], g)
            state = dopt.init(p, schema, N_DEV)
            new_p, _ = dopt.step(g, state, p, schema)
            return jax.tree_util.tree_map(lambda a: a[None], new_p)

        # stack outputs per device and check bitwise equality
        out = shard_map(inner, mesh=mesh, in_specs=(P("data"), P("data")),
                        out_specs=P("data"), check_rep=False)(
            jax.tree_util.tree_map(lambda a: jnp.broadcast_to(
                a, (N_DEV, *a.shape)), params),
            jax.tree_util.tree_map(lambda a: jnp.broadcast_to(
                a, (N_DEV, *a.shape)), grads))
        for k in params:
            base = np.asarray(out[k]).reshape(N_DEV, -1)
            for r in range(1, N_DEV):
                np.testing.assert_array_equal(base[0], base[r])


class TestFP16Utils:
    def test_network_to_half_keeps_bn_fp32(self):
        tree = {"conv": {"w": jnp.ones((4, 4))},
                "bn1": {"weight": jnp.ones((4,))}}
        half = fp16_utils.network_to_half(tree)
        assert half["conv"]["w"].dtype == jnp.bfloat16
        assert half["bn1"]["weight"].dtype == jnp.float32

    def test_master_model_sync(self):
        model = {"w": jnp.ones((3,), jnp.bfloat16)}
        master = {"w": jnp.full((3,), 1.5, jnp.float32)}
        synced = fp16_utils.master_params_to_model_params(model, master)
        assert synced["w"].dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(synced["w"], np.float32), 1.5)

    def test_fp16_optimizer_end_to_end(self):
        opt = fp16_utils.FP16_Optimizer(optimizers.FusedSGD(lr=0.5),
                                        dynamic_loss_scale=True)
        params = {"w": jnp.array([2.0, -3.0])}
        opt.load_params(params)

        def loss_fn(p, x):
            return jnp.sum((p["w"] * x) ** 2)

        x = jnp.array([1.0, 1.0])
        l0 = float(loss_fn(opt.master_params, x))
        for _ in range(5):
            half = opt.model_params()
            grads, finite = opt.backward(loss_fn, opt.master_params, x)
            opt.step(grads, finite)
        assert float(loss_fn(opt.master_params, x)) < l0

    def test_fp16_optimizer_skips_on_overflow(self):
        opt = fp16_utils.FP16_Optimizer(optimizers.FusedSGD(lr=0.5))
        params = {"w": jnp.array([1.0])}
        opt.load_params(params)
        before = opt.master_params["w"]
        scale_before = float(opt.loss_scale)

        def inf_loss(p, x):
            return jnp.sum(p["w"] * jnp.inf)

        grads, finite = opt.backward(inf_loss, opt.master_params,
                                     jnp.ones(1))
        assert not bool(finite)
        opt.step(grads, finite)
        np.testing.assert_array_equal(opt.master_params["w"], before)
        assert float(opt.loss_scale) == scale_before / 2.0

    def test_state_dict_roundtrip(self):
        opt = fp16_utils.FP16_Optimizer(optimizers.FusedSGD(lr=0.1))
        opt.load_params({"w": jnp.ones((2,))})
        sd = opt.state_dict()
        opt2 = fp16_utils.FP16_Optimizer(optimizers.FusedSGD(lr=0.1))
        opt2.load_state_dict(sd)
        np.testing.assert_array_equal(opt2.master_params["w"],
                                      opt.master_params["w"])

    def test_clip_master_grads(self):
        opt = fp16_utils.FP16_Optimizer(optimizers.FusedSGD(lr=0.1))
        grads = {"w": jnp.array([30.0, 40.0])}  # norm 50
        clipped, norm = opt.clip_master_grads(grads, max_norm=5.0)
        np.testing.assert_allclose(norm, 50.0, rtol=1e-6)
        np.testing.assert_allclose(
            jnp.linalg.norm(clipped["w"]), 5.0, rtol=1e-5)

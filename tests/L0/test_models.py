"""Model-zoo tests (CPU, tiny shapes)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, optimizers
from apex_tpu.models import ResNet, ResNetConfig
from apex_tpu.ops import softmax_cross_entropy_loss


def _tiny_cfg(**kw):
    return ResNetConfig(block_sizes=(1, 1), width=8, num_classes=10, **kw)


class TestResNet:
    # slow since PR 22: pays for test_tpu_lowering / test_chip_smoke in tier-1
    @pytest.mark.slow
    def test_forward_shapes_and_state(self):
        model = ResNet(_tiny_cfg())
        params, state = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        logits, new_state = model.apply(params, state, x, training=True)
        assert logits.shape == (2, 10)
        # BN running stats must move in training mode
        assert not np.allclose(new_state["bn1"]["mean"], state["bn1"]["mean"])
        # eval mode keeps state
        logits_eval, eval_state = model.apply(params, new_state, x,
                                              training=False)
        np.testing.assert_array_equal(eval_state["bn1"]["mean"],
                                      new_state["bn1"]["mean"])

    def test_amp_o2_training_decreases_loss(self):
        # examples/imagenet in miniature: O2 + FusedLAMB + dynamic scale
        model = ResNet(_tiny_cfg())
        params, bn_state = model.init(jax.random.PRNGKey(0))
        amp_state = amp.initialize("O2")
        scaler = amp_state.scaler
        scale_state = scaler.init()
        opt = optimizers.FusedLAMB(lr=1e-2)
        opt_state = opt.init(params)

        def loss_fn(p, bn, x, y):
            logits, new_bn = model.apply(p, bn, x, training=True)
            return softmax_cross_entropy_loss(logits, y).mean(), new_bn

        grad_fn = amp.scaled_value_and_grad(loss_fn, scaler, has_aux=True)

        @jax.jit
        def train_step(params, bn, opt_state, scale_state, x, y):
            half = amp_state.cast_model(params)
            (loss, new_bn), grads, finite = grad_fn(scale_state, half, bn, x, y)
            new_params, new_opt = opt.step(grads, opt_state, params)
            params, opt_state = amp.skip_or_step(
                finite, (new_params, new_opt), (params, opt_state))
            scale_state = scaler.update(scale_state, finite)
            return params, new_bn, opt_state, scale_state, loss

        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3),
                              jnp.bfloat16)
        y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
        losses = []
        for _ in range(8):
            params, bn_state, opt_state, scale_state, loss = train_step(
                params, bn_state, opt_state, scale_state, x, y)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        assert all(np.isfinite(losses))

    def test_half_params_stay_half_master_fp32(self):
        model = ResNet(_tiny_cfg())
        params, _ = model.init(jax.random.PRNGKey(0))
        amp_state = amp.initialize("O2")
        half = amp_state.cast_model(params)
        assert half["conv1"]["w"].dtype == jnp.bfloat16
        assert half["bn1"]["weight"].dtype == jnp.float32  # keep_batchnorm_fp32
        assert params["conv1"]["w"].dtype == jnp.float32


class TestGraftEntry:
    def test_entry_compiles(self):
        import __graft_entry__ as ge
        fn, args = ge.entry()
        out = jax.jit(fn)(*args)
        assert out.shape[-1] == 1024

    @pytest.mark.slow  # 8-device multichip dryrun (ISSUE 2 CI satellite)
    def test_dryrun_multichip(self):
        import __graft_entry__ as ge
        ge.dryrun_multichip(8)

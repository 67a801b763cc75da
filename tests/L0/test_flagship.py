"""GPT-1.3B flagship machinery tests (ISSUE 2 tentpole).

The full 1.3B shape only runs on hardware (benchmark/, chip_smoke.py); here
the same construction — d=128 head geometry, ZeRO-sharded FusedAdam over the
mesh "data" axis, fit-plan dtypes — runs at toy width/depth on the
emulated 8-device mesh, with the acceptance parity check:
ZeRO-sharded step vs unsharded FusedAdam, max|dw| ≤ 1e-3.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu import optimizers
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import (
    FIT_PLANS,
    GPTModel,
    build_flagship_train_step,
    flagship_state_bytes,
    gpt1p3b_config,
    gpt_param_count,
)

N_DEV = 8

# toy depth/width, flagship head geometry: hidden/heads = 128 keeps the
# d=128 kernel routing (the thing the flagship exists to measure) while
# the model stays CPU-small
TOY_KW = dict(num_layers=2, hidden_size=256, num_attention_heads=2,
              vocab_size=256, max_position_embeddings=64)


def _batch(cfg, b=8, seed=1):
    k = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(k, (b, cfg.max_position_embeddings), 0,
                                cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=-1)


@pytest.fixture(scope="module")
def flagship_bf16_fit():
    """ONE bf16_fit flagship construction shared by every test in this
    module that steps the default toy config (ISSUE 6 wall-clock
    satellite: the 8-device jit construction is the dominant cost —
    build it once per module, not once per test).  donate=False so each
    test can step from the pristine (params, opt_state) snapshot."""
    cfg = gpt1p3b_config(**TOY_KW)
    return cfg, build_flagship_train_step(
        cfg, plan="bf16_fit", lr=1e-3, devices=jax.devices()[:N_DEV],
        donate=False)


def _unsharded_reference(cfg, plan, tokens, labels, steps, lr):
    """Plain (unsharded) FusedAdam trajectory of the identical model —
    the parity baseline the reference's test_dist_adam.py compares
    against.  Params in the same storage dtype as the ZeRO run so the
    comparison isolates the sharding machinery, not the fit plan."""
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        1, 1, devices=jax.devices()[:1])
    model = GPTModel(cfg)
    params = model.shard_master(
        model.init_master(jax.random.PRNGKey(0)), 0)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(plan.param_dtype), params)
    opt = optimizers.FusedAdam(lr=lr)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, s, t, l):
        def lossf(p):
            return shard_map(
                lambda p, t, l: jnp.mean(model.apply(p, t, labels=l)),
                mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
                check_rep=False)(p, t, l)

        loss, grads = jax.value_and_grad(lossf)(p)
        p, s = opt.step(grads, s, p)
        return p, s, loss

    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
    return params, float(loss)


@pytest.mark.parametrize("plan_name,compute_bf16,tol", [
    # fp32 plan at fp32 compute — the ISSUE 2 acceptance cell
    # (max|dw| ≤ 1e-3), measured ~1e-6: with grad noise removed, the
    # diff isolates the sharding machinery (psum_scatter reduction
    # order, flat-schema slicing, all_gather reassembly).  bf16 compute
    # would make the comparison vacuous: Adam's step-1 update is
    # ~sign(g)·lr, so bf16-level grad noise between the batch-split and
    # full-batch graphs flips signs of near-zero grads and saturates
    # max|dw| at 2·lr for ANY correct implementation.
    ("fp32", False, 1e-3),
    # the single-chip fit plan at the real bf16 compute: params are
    # STORED bf16 in both runs, so the floor is one bf16 ulp at the
    # largest param scale (layernorm weights ≈ 1.0 → ulp 2⁻⁸); two
    # ulps bound the two steps — slow tier (~16s; the fp32 cell keeps
    # the sharding-machinery parity in tier-1, ISSUE 12 wall trim)
    pytest.param("bf16_fit", True, 2 ** -7, marks=pytest.mark.slow),
])
def test_zero_step_parity_vs_unsharded(plan_name, compute_bf16, tol,
                                       flagship_bf16_fit):
    cfg = gpt1p3b_config(bf16=compute_bf16, **TOY_KW)
    plan = FIT_PLANS[plan_name]
    tokens, labels = _batch(cfg)

    if plan_name == "bf16_fit" and compute_bf16:
        # the default toy construction — reuse the module's shared build
        _, fs = flagship_bf16_fit
    else:
        fs = build_flagship_train_step(
            cfg, plan=plan_name, lr=1e-3, devices=jax.devices()[:N_DEV],
            donate=False)
    p, s = fs.params, fs.opt_state
    for _ in range(2):
        p, s, loss = fs.step(p, s, tokens, labels)
    assert np.isfinite(float(loss))

    ref_p, ref_loss = _unsharded_reference(cfg, plan, tokens, labels,
                                           steps=2, lr=1e-3)
    # compare on host: the two trees live on different device sets
    maxdw = max(
        float(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b, np.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(p),
                        jax.tree_util.tree_leaves(ref_p)))
    assert maxdw <= tol, (plan_name, maxdw)


def test_flagship_loss_decreases(flagship_bf16_fit):
    cfg, fs = flagship_bf16_fit
    tokens, labels = _batch(cfg)
    p, s = fs.params, fs.opt_state
    losses = []
    for _ in range(6):
        p, s, loss = fs.step(p, s, tokens, labels)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses


def test_param_count_matches_tree():
    cfg = gpt1p3b_config(**TOY_KW)
    model = GPTModel(cfg)
    params = model.shard_master(
        model.init_master(jax.random.PRNGKey(0)), 0)
    n = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    assert n == gpt_param_count(cfg)


def test_fit_plan_table_matches_module_docs():
    """The analytic fitting table of the flagship module: at the
    full 1.3B shape only bf16_fit's optimizer-phase peak fits a
    15.75-GiB chip at world=1; bf16_fp32m fits once sharded."""
    cfg = gpt1p3b_config()
    n = gpt_param_count(cfg)
    assert 1.25e9 < n < 1.40e9, n  # "1.3B-class"
    budget = 15.75 * 2 ** 30  # ≈16.9e9 bytes
    peaks = {name: flagship_state_bytes(cfg, plan)["step_peak"]
             for name, plan in FIT_PLANS.items()}
    assert peaks["fp32"] > peaks["bf16_fp32m"] > peaks["bf16_fit"]
    assert peaks["fp32"] > budget
    assert peaks["bf16_fp32m"] > budget  # the near-miss the docs name
    assert peaks["bf16_fit"] < budget
    # sharding shrinks the moment terms: fp32 moments fit at world ≥ 2
    sharded = flagship_state_bytes(cfg, FIT_PLANS["bf16_fp32m"],
                                   n_shards=8)
    assert sharded["step_peak"] < budget


def test_flagship_shape_engages_packed_attention(monkeypatch):
    """Tentpole (d): at the flagship geometry (s=2048, d=128, bf16,
    block 256) the packed-QKV gate must pass — the shape class the
    flagship exists for cannot silently fall to the generic kernels."""
    from apex_tpu.ops import attention as attn_mod

    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")
    cfg = gpt1p3b_config()
    hn = cfg.kv_channels
    assert hn == 128
    assert attn_mod._qkv_packed_ok(
        4, cfg.max_position_embeddings, cfg.num_attention_heads, hn,
        cfg.flash_block_q, True, 0.0, jnp.bfloat16)
    # and the generic-kernel backward (the attn_res recompute path for
    # masked variants) stays compilable at this shape via the grid
    # one-pass kernel
    q = jax.ShapeDtypeStruct((4 * 16, 2048, 128), jnp.bfloat16)
    assert attn_mod._pallas_bwd_ok(q, q, None, 512, 512)

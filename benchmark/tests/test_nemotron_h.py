"""The Nemotron-H cell's own files at a tiny size on the CPU: the
reference and ``flops_nemotron_h.py`` by hand-computed cases, the window
driver end to end with both controls, its comparison shown to fail, and
the readers it adds."""

import json
import os
import time

import numpy as np
import pytest

import flops_nemotron_h as flops
import harness
from reference import nemotron_h_serve as ref

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LIMITS = {"served_logit_gap": 1e-3, "served_logit_gap_mean": 1e-4,
          "route_margin_min": 0.0, "route_left_out_share": 0.0,
          "first_layer_state_gap": 1e-4}


def _load(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def _ctx(limits=LIMITS, seconds=2.0, seed=2 ** 31 + 42):
    return harness.Context(
        workload={"name": "test"}, config=_load("tiny-serve-nemotron-h"),
        mix=_load("tiny-backlog"), limits=dict(limits), peak=PEAK,
        seed=seed, seconds=seconds, trace=False,
        t_process=time.perf_counter())


# -- the yardstick ------------------------------------------------------------

def test_flops_counts_the_published_model_by_its_parts():
    config = harness.load_json("configs",
                               "nemotron-3-super-serve-ep4-l11.json")
    m = flops.model_shape(flops.model_of(config))
    assert (m.ssm_layers, m.expert_layers, m.attention_layers) == (5, 5, 1)
    assert (m.experts, m.held, m.top_k, m.groups) == (512, 128, 22, 8)
    assert round(flops.ssm_mixer_params(m) / 1e5) == 1096          # 109.6M
    assert round(flops.expert_layer_fixed_params(m) / 1e5) == 545  # 54.5M
    assert round(flops.expert_params(m) / 1e3) == 5505             # 5.505M
    assert round(flops.attention_mixer_params(m) / 1e5) == 357     # 35.7M
    assert 2 * m.vocab * m.hidden == 268_435_456                  # 268.4M
    assert round(flops.total_params(m) / 1e6) == 4648
    assert round(flops.total_params(m) * 2 / 1e9, 2) == 9.30
    assert flops.state_bytes(m) == 4 * 2 ** 20
    assert flops.tail_bytes(m) == 3 * 10240 * 2
    assert round(66 * flops.slot_bytes(m) / 1e9, 2) == 1.40
    assert flops.kv_token_bytes(m) == 1024
    # 64 rows touch 94% of the 640 held experts: 6.62 GB, 58% of 11.4
    touched = flops.experts_touched(m, 64)
    assert round(touched / 640, 2) == 0.94
    assert round(touched * flops.expert_params(m) * 2 / 1e9, 2) == 6.62
    step = flops.decode_steps_bytes(m, 1, touched, 64, 64 * 1100)
    assert 11.2e9 < step < 11.5e9
    # 2.05 GFLOP a token in the layers, 2.3 with the head
    assert round(flops.layer_flops_per_token(m) / 1e9, 2) == 2.05
    assert round(flops.decode_flops(m, 1) / 1e9, 1) == 2.3
    assert config["published"]["num_hidden_layers"] == 88
    assert set(config["assumed"]) >= {
        "rope", "split_order", "gated_norm", "router_input", "state",
        "expert_bias", "seeded_weights", "pool"}


def test_flops_by_hand():
    m = flops.NemotronHShape(
        layer_types=("mamba", "moe", "attention"), hidden=4, heads=2,
        kv_heads=1, head_dim=2, ssm_heads=2, ssm_head_dim=2, groups=2,
        state=3, taps=2, latent=2, expert_ffn=3, shared_ffn=5, experts=8,
        held=4, top_k=2, vocab=10)
    assert m.conv_dim == 4 + 2 * 2 * 3
    ssm = 4 * (4 + 16 + 2) + 4 * 4
    small = 3 * 16 + 4 + 3 * 2
    attn = 2 * 4 * 4 + 2 * 4 * 2
    fixed = 4 * 8 + 2 * 4 * 2 + 2 * 4 * 5
    expert = 2 * 2 * 3
    assert flops.ssm_mixer_params(m) == ssm
    assert flops.expert_layer_fixed_params(m) == fixed
    assert flops.total_params(m) == (ssm + small + attn + fixed
                                     + 4 * expert + 3 * 4 + 80 + 4)
    # a token: a pair lands here with probability 4/8, two pairs a token
    per_token = (2 * ssm + (6 * 4 * 3 + 4 * 4 + 2 * 2 * 16) + 2 * attn
                 + 2 * fixed + 2 * expert * 1.0)
    assert flops.layer_flops_per_token(m) == per_token
    assert flops.decode_flops(m, 5) == per_token + 80 + 4 * 2 * 2 * 5
    assert flops.state_bytes(m) == 4 * 3 * 4
    assert flops.tail_bytes(m) == 1 * 16 * 2
    assert flops.kv_token_bytes(m) == 2 * 1 * 2 * 2
    # 3 of the 4 held experts touched, 2 rows: the other 8 embedding
    # rows and one expert are not read
    w = (flops.total_params(m) - expert - 8 * 4) * 2
    assert flops.decode_weight_bytes(m, 3, 2) == w
    assert flops.decode_steps_bytes(m, 1, 3, 2, 11) == \
        w + 2 * 2 * (48 + 32) + 11 * 8
    assert flops.decode_experts_bytes(m, 3) == (3 * expert + fixed) * 2
    assert flops.ssm_update_call_bytes(m, 2) == 2 * (96 + 4 * (12 + 12))
    assert flops.experts_touched(m, 1000) == pytest.approx(4.0)


def test_reference_by_hand_a_mamba_layer_with_two_groups_and_an_expert_layer():
    """A Mamba layer of two heads in two groups, an expert layer of
    top-1 of 2 experts (both held) in a latent of 2, beside a shared
    expert: every step by hand, token by token."""
    import jax.numpy as jnp

    model = dict(
        hybrid_override_pattern="ME", num_hidden_layers=2, vocab_size=5,
        hidden_size=4, num_attention_heads=2, num_key_value_heads=1,
        head_dim=2, mamba_num_heads=2, mamba_head_dim=2, n_groups=2,
        ssm_state_size=2, conv_kernel=2, expand=1, moe_latent_size=2,
        moe_intermediate_size=3, moe_shared_expert_intermediate_size=2,
        n_routed_experts=2, num_experts_per_tok=1, routed_scaling_factor=2.0,
        norm_topk_prob=True, mlp_hidden_act="relu2", n_group=1,
        layer_norm_epsilon=0.0, tie_word_embeddings=False,
        use_conv_bias=True)
    shape = ref.model_shape(model)
    rng = np.random.RandomState(0)
    leaf = lambda *s: rng.randn(*s).astype(np.float32)
    ch = 4 + 2 * 2 * 2
    mamba = {"norm": leaf(4), "win": leaf(4, 4 + ch + 2),
             "conv_w": leaf(2, ch), "conv_b": leaf(ch),
             "dt_bias": leaf(2), "a_log": leaf(2) * 0.3, "d_skip": leaf(2),
             "gn": leaf(4), "wout": leaf(4, 4)}
    moe = {"router": leaf(4, 2), "expert_bias": np.float32([0.0, 0.0]),
           "latent_down": leaf(4, 2), "latent_up": leaf(2, 4),
           "experts": {"wu": leaf(2, 2, 3), "wd": leaf(2, 3, 2)},
           "shared": {"wu": leaf(4, 2), "wd": leaf(2, 4)}}
    params = {"embed": leaf(5, 4), "head": leaf(4, 5), "norm_f": leaf(4),
              "layers": [mamba, {"norm": leaf(4), "moe": moe}]}
    tokens = [3, 0, 4]
    got = np.asarray(ref.logits_all(
        params, jnp.asarray(tokens, jnp.int32), shape))

    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True)) * g
    sig = lambda x: 1 / (1 + np.exp(-x))
    silu = lambda x: x * sig(x)
    softplus = lambda x: np.log1p(np.exp(x))
    relu2 = lambda a, p: np.maximum(a @ p["wu"], 0) ** 2 @ p["wd"]
    x = params["embed"][tokens]
    u = rms(x, mamba["norm"])
    proj = u @ mamba["win"]
    z, xbc, dt = proj[:, :4], proj[:, 4:4 + ch], proj[:, 4 + ch:]
    prev = np.concatenate([np.zeros((1, ch), np.float32), xbc[:-1]])
    c = silu(mamba["conv_w"][0] * prev + mamba["conv_w"][1] * xbc
             + mamba["conv_b"])
    xs, bs, cs = c[:, :4].reshape(3, 2, 2), c[:, 4:8], c[:, 8:12]
    state = np.zeros((2, 2, 2))
    y = np.zeros((3, 2, 2))
    for t in range(3):
        for h in range(2):                        # head h reads group h
            b, cc = bs[t, 2 * h:2 * h + 2], cs[t, 2 * h:2 * h + 2]
            delta = softplus(dt[t, h] + mamba["dt_bias"][h])
            state[h] = (np.exp(-np.exp(mamba["a_log"][h]) * delta) * state[h]
                        + delta * np.outer(xs[t, h], b))
            y[t, h] = state[h] @ cc + mamba["d_skip"][h] * xs[t, h]
    q = y.reshape(3, 4) * silu(z)
    q = np.concatenate([rms(q[:, :2], mamba["gn"][:2]),
                        rms(q[:, 2:], mamba["gn"][2:])], -1)
    x = x + q @ mamba["wout"]
    u = rms(x, params["layers"][1]["norm"])
    score = sig(u @ moe["router"])
    sel = score.argmax(-1)
    lat = u @ moe["latent_down"]
    routed = np.stack([
        score[t, sel[t]] / (score[t, sel[t]] + 1e-20) * 2.0 * relu2(
            lat[t], {k: v[sel[t]] for k, v in moe["experts"].items()})
        for t in range(3)])
    x = x + routed @ moe["latent_up"] + relu2(u, moe["shared"])
    out = rms(x, params["norm_f"]) @ params["head"]
    np.testing.assert_allclose(got, out, rtol=2e-4, atol=2e-5)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "nemotron_h_serve.py")) as f:
        assert "apex_tpu" not in f.read()


# -- the window driver, end to end at a tiny size on the CPU ------------------

@pytest.fixture(scope="module")
def tiny_run():
    import drive_serve_nemotron_h as d
    from apex_tpu.telemetry import PHASE_RING

    PHASE_RING.clear()
    ctx = _ctx()
    result = d.run(ctx)
    return result, ctx, PHASE_RING.snapshot()


def test_driver_runs_and_is_correct(tiny_run):
    result, _, _ = tiny_run
    c = result.counters
    assert result.correct, result.checks
    assert result.failed == 0 and result.attempted > 0
    assert result.end_to_end["serve_tokens_per_s"] > 0
    assert c["served_tokens_compared"] > 0 and c["state_slots"] == 6
    assert max(c["prompt_lens"]) > 16                  # a chunked prompt
    assert c["state_tokens_taken"]
    assert {ch.name for ch in result.checks} == {
        "served_logit_gap", "served_logit_gap_mean", "route_left_out_share",
        "first_layer_state_gap", "recompiles_in_window"}


def test_both_controls_run_and_read_worse_than_the_program():
    import drive_serve_nemotron_h as d

    row = d.readings(_ctx(seconds=1.5), control=True)
    assert row["compared"] > 0
    assert row["mean"]["program"] < 1e-5
    assert row["mean"]["fp8"] > 1e-4
    assert row["state"]["program"] < 1e-5
    for name in d.CONTROLS:
        assert row["state"][name] > 1e-4, name


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    import drive_serve_nemotron_h as d

    real_build = d.build

    def build(ctx):
        eng, make_weights, module, shape = real_build(ctx)
        decode = eng._decode_fn

        def altered(*args):
            tok, *rest = decode(*args)
            return ((tok + 1) % shape.vocab, *rest)

        eng._decode_fn = altered
        return eng, make_weights, module, shape

    monkeypatch.setattr(d, "build", build)
    result = d.run(_ctx(seconds=1.0))
    assert not result.correct
    assert [c.name for c in result.checks if not c.ok] == [
        "served_logit_gap", "served_logit_gap_mean"]


# -- the readers this cell adds -----------------------------------------------

def _refill(records):
    from apex_tpu.telemetry import PHASE_RING

    PHASE_RING.clear()
    for r in records:
        PHASE_RING.record(r)


def _read(name, result, ctx):
    import run as run_py

    return run_py.read_layer_metric({"name": name}, result, ctx)


def test_ring_readers_on_a_tiny_run(tiny_run):
    result, ctx, records = tiny_run
    _refill(records)
    assert 0.0 < _read("nemotron_serve_mfu", result, ctx) < 1.0
    assert _read("decode_rows_per_step", result, ctx) > 1.0
    assert _read("nemotron_moe_load_max_over_mean", result, ctx) >= 1.0
    assert 0.0 < _read("moe_tile_fill_share", result, ctx) <= 100.0
    assert 0.0 < _read("state_slots_held_share", result, ctx) <= 100.0
    assert _read("backlog_host_turn_ms_per_step", result, ctx) > 0.0


def test_the_new_readers_find_nothing_in_another_kinds_run(tiny_run):
    import trace_reduce

    result, ctx, _ = tiny_run
    bare = harness.Result(
        attempted=1, failed=0, end_to_end={},
        window_start=result.window_start, window_s=result.window_s,
        memory_peak_bytes=0, checks=[],
        counters={"prompt_lens": [5], "decode_kv_lens": [6],
                  "state_slots": 6,
                  "traced": {"decode_kv_lens": [6], "prompt_lens": [5]}},
        trace=trace_reduce.Trace(
            [trace_reduce.DeviceTrace("/device:TPU:0", [], [])], []),
        trace_window_ns=(0.0, 1.0))
    other = harness.Context(
        workload={"name": "test"}, config=_load("tiny-serve-lfm2-moe"),
        mix=ctx.mix, limits={}, peak=PEAK, seed=1, seconds=1.0,
        trace=True, t_process=0.0)
    for name in ("nemotron_serve_mfu", "nemotron_decode_step_roofline",
                 "nemotron_decode_experts_roofline",
                 "nemotron_ssm_update_roofline", "nemotron_prefill_mfu",
                 "nemotron_moe_load_max_over_mean"):
        assert _read(name, bare, other) is None, name
        assert _read(name, bare, ctx) is None or name in (
            "nemotron_serve_mfu", "nemotron_moe_load_max_over_mean"), name


def test_trace_readers_on_a_made_up_trace(tiny_run):
    import trace_reduce

    result, ctx, records = tiny_run
    _refill(records)
    dev = trace_reduce.DeviceTrace(
        "/device:TPU:0",
        [("jit__decode(1)", 1e6, 1e6), ("jit__prefill(2)", 3e6, 2e6)], [])
    result.trace = trace_reduce.Trace([dev], [])
    result.trace_window_ns = (0.0, 1e7)
    result.counters["traced"] = {
        "decode_steps": 1, "decode_kv_lens": [50, 47, 55],
        "prompt_lens": [46, 52]}
    m = flops.model_shape(flops.model_of(ctx.config))
    from metrics import phase_ring
    touched = phase_ring.mean(
        d.attrs["moe_experts_touched"]
        for _, inside in phase_ring.steps(result)
        for d in inside.get("engine.decode", ())
        if "moe_experts_touched" in d.attrs)
    assert 1 <= touched <= 3 * 8
    nbytes = flops.decode_steps_bytes(m, 1, touched, 3, 152)
    step = max(nbytes / PEAK["hbm_bytes_per_s"],
               sum(flops.decode_flops(m, k) for k in (50, 47, 55))
               / PEAK["bf16_flops_per_s"])
    assert _read("nemotron_decode_step_roofline", result, ctx) == \
        pytest.approx(100 * step / 1e-3)
    assert result.counters["nemotron_decode_step_bytes"] == nbytes
    work = flops.prefill_flops(m, 46) + flops.prefill_flops(m, 52)
    assert _read("nemotron_prefill_mfu", result, ctx) == pytest.approx(
        100 * work / 2e-3 / PEAK["bf16_flops_per_s"])
    # no kernel of that name in the made-up trace
    assert _read("nemotron_ssm_update_roofline", result, ctx) is None

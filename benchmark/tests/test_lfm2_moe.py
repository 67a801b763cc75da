"""The LFM2-MoE cell's own files at a tiny size on the CPU: the reference
and ``flops_lfm2_moe.py`` by hand-computed cases, the window driver end
to end with both controls, its comparison shown to fail, and the
readers it adds."""

import json
import os
import time

import numpy as np
import pytest

import flops_lfm2_moe as flops
import harness
from reference import lfm2_moe_serve as ref

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LIMITS = {"served_logit_gap": 1e-3, "served_logit_gap_mean": 1e-4,
          "route_margin_min": 0.0, "route_left_out_share": 0.0}


def _load(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def _ctx(limits=LIMITS, seconds=2.0, seed=2 ** 31 + 39):
    return harness.Context(
        workload={"name": "test"}, config=_load("tiny-serve-lfm2-moe"),
        mix=_load("tiny-backlog"), limits=dict(limits), peak=PEAK,
        seed=seed, seconds=seconds, trace=False,
        t_process=time.perf_counter())


# -- the yardstick --------------------------------------------------------------

def test_flops_counts_the_published_model_as_the_issue_does():
    config = harness.load_json("configs", "lfm2-24b-a2b-serve-l10.json")
    m = flops.model_shape(flops.model_of(config))
    assert (m.conv_layers, m.attention_layers, m.expert_layers) == (8, 2, 8)
    assert (m.experts, m.held, m.top_k, m.head_dim) == (64, 64, 4, 64)
    assert flops.conv_mixer_params(m) == 16_783_360               # 16.78M
    assert flops.attention_mixer_params(m) == 10_485_760          # 10.49M
    assert flops.mlp_params(m, m.ffn) == 72_351_744               # 72.35M
    assert m.held * flops.expert_params(m) == 603_979_776         # 604.0M
    assert m.vocab * m.hidden == 134_217_728                      # 134.2M
    assert round(flops.total_params(m) / 1e6) == 5267
    assert round(flops.total_params(m) * 2 / 1e9, 2) == 10.53
    assert flops.tail_bytes(m) == 8192 and flops.slot_bytes(m) == 65536
    assert flops.kv_token_bytes(m) == 4096
    # 64 rows touch 98.4% of the 512 experts, 9.5 GB, 87-92% of a step
    touched = flops.experts_touched(m, 64)
    assert round(touched / 512, 3) == 0.984
    assert round(touched * flops.expert_params(m) * 2 / 1e9, 1) == 9.5
    step = flops.decode_steps_bytes(m, 1, touched, 64, 64 * 1100)
    assert 10.5e9 < step < 10.8e9
    assert round(flops.layer_flops_per_token(m) / 1e9, 2) == 1.21
    assert config["published"]["num_hidden_layers"] == 40
    assert set(config["assumed"]) >= {"tie_word_embeddings", "split_order",
                                      "expert_bias", "seeded_weights",
                                      "kv_layout", "pool"}


def test_flops_by_hand():
    m = flops.Lfm2MoeShape(
        layer_types=("conv", "full_attention", "conv"), dense_layers=1,
        hidden=4, heads=2, kv_heads=1, ffn=6, expert_ffn=3, experts=4,
        held=4, top_k=2, taps=3, vocab=10)
    conv = 3 * 16 + 3 * 4 + 16
    attn = 2 * 4 * 4 + 2 * 4 * 2
    assert flops.conv_mixer_params(m) == conv
    assert flops.attention_mixer_params(m) == attn
    expert = 3 * 4 * 3
    layer_params = 2 * conv + attn + 3 * 4 * 6 + 2 * (4 * expert + 16)
    assert flops.total_params(m) == layer_params + 40
    # a token: the GEMMs (2 of 4 experts), the gates and taps of 2 conv
    per_token = (2 * (2 * conv + attn) + 2 * (2 * 4 + 2 * 3 * 4)
                 + 2 * 3 * 4 * 6 + 2 * (2 * 16 + 2 * expert * 2))
    assert flops.layer_flops_per_token(m) == per_token
    # one attention layer, 2 heads of 2: 4 * 2 * 2 a pair
    assert flops.decode_flops(m, 5) == per_token + 2 * 4 * 10 + 16 * 5
    assert flops.prefill_flops(m, 4) == 4 * per_token + 16 * 10 + 80
    assert flops.tail_bytes(m) == 2 * 4 * 2
    assert flops.kv_token_bytes(m) == 2 * 1 * 2 * 2
    # 5 of the 8 experts touched: 3 untouched are not read
    w = (flops.total_params(m) - 3 * expert) * 2
    assert flops.decode_weight_bytes(m, 5) == w
    assert flops.decode_steps_bytes(m, 2, 5, 3, 11) == \
        2 * w + 3 * 2 * 2 * 16 + 11 * 8
    assert flops.decode_ffn_bytes(m, 5) == (5 * expert + 2 * 16 + 72) * 2
    assert flops.experts_touched(m, 1000) == pytest.approx(8.0)


def test_reference_by_hand_a_conv_layer_and_an_attention_layer_of_experts():
    """A dense convolution layer, then an attention layer with two heads
    over one K/V head of 2 and top-1 of 2 experts: every step by hand."""
    import jax.numpy as jnp

    model = dict(
        vocab_size=5, hidden_size=4, num_attention_heads=2,
        num_key_value_heads=1, layer_types=["conv", "full_attention"],
        num_hidden_layers=2, num_dense_layers=1, intermediate_size=3,
        moe_intermediate_size=2, num_experts=2, num_experts_per_tok=1,
        routed_scaling_factor=1.5, conv_L_cache=3, norm_eps=0.0,
        rope_theta=100.0)
    shape = ref.model_shape(model)
    rng = np.random.RandomState(0)
    leaf = lambda *s: rng.randn(*s).astype(np.float32)
    conv = {"g_op": leaf(4), "g_ffn": leaf(4), "w_in": leaf(4, 12),
            "conv_w": leaf(3, 4), "w_out": leaf(4, 4),
            "mlp": {"wg": leaf(4, 3), "wu": leaf(4, 3), "wd": leaf(3, 4)}}
    moe = {"router": leaf(4, 2), "expert_bias": np.float32([0.0, 0.0]),
           "experts": {"wg": leaf(2, 4, 2), "wu": leaf(2, 4, 2),
                       "wd": leaf(2, 2, 4)}}
    attn = {"g_op": leaf(4), "g_ffn": leaf(4), "wq": leaf(4, 4),
            "wk": leaf(4, 2), "wv": leaf(4, 2), "wo": leaf(4, 4),
            "gq": leaf(2), "gk": leaf(2), "moe": moe}
    params = {"embed": leaf(5, 4), "norm_f": leaf(4), "layers": [conv, attn]}
    tokens = [3, 0, 4, 1]
    got = np.asarray(ref.logits_all(
        params, jnp.asarray(tokens, jnp.int32), shape))

    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True)) * g
    sig = lambda x: 1 / (1 + np.exp(-x))
    silu = lambda x: x * sig(x)
    swiglu = lambda u, p: (silu(u @ p["wg"]) * (u @ p["wu"])) @ p["wd"]
    x = params["embed"][tokens]
    u = rms(x, conv["g_op"])
    b, c, xt = np.split(u @ conv["w_in"], 3, -1)
    v = np.concatenate([np.zeros((2, 4), np.float32), b * xt])
    w = conv["conv_w"]
    cv = np.stack([w[0] * v[t] + w[1] * v[t + 1] + w[2] * v[t + 2]
                   for t in range(4)])
    h = x + (c * cv) @ conv["w_out"]
    x = h + swiglu(rms(h, conv["g_ffn"]), conv["mlp"])

    def rope(z, p):                      # z [2]: one frequency, theta^0
        co, si = np.cos(p), np.sin(p)
        return np.array([z[0] * co - z[1] * si, z[1] * co + z[0] * si])

    u = rms(x, attn["g_op"])
    q = rms((u @ attn["wq"]).reshape(4, 2, 2), attn["gq"])
    k = rms(u @ attn["wk"], attn["gk"])
    vv = u @ attn["wv"]
    q = np.stack([[rope(q[p, n], p) for n in range(2)] for p in range(4)])
    k = np.stack([rope(k[p], p) for p in range(4)])
    a = np.zeros((4, 4), np.float32)
    for p in range(4):
        for n in range(2):
            s = np.array([q[p, n] @ k[j] for j in range(p + 1)]) / np.sqrt(2)
            e = np.exp(s - s.max())
            e /= e.sum()
            a[p, 2 * n:2 * n + 2] = sum(e[j] * vv[j] for j in range(p + 1))
    h = x + a @ attn["wo"]
    u = rms(h, attn["g_ffn"])
    score = sig(u @ moe["router"])
    sel = score.argmax(-1)
    y = np.stack([
        score[t, sel[t]] / (score[t, sel[t]] + 1e-6) * 1.5 * swiglu(
            u[t], {key: val[sel[t]] for key, val in moe["experts"].items()})
        for t in range(4)])
    out = rms(h + y, params["norm_f"]) @ params["embed"].T
    np.testing.assert_allclose(got, out, rtol=2e-4, atol=2e-5)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "lfm2_moe_serve.py")) as f:
        assert "apex_tpu" not in f.read()


# -- the window driver, end to end at a tiny size on the CPU -------------------

@pytest.fixture(scope="module")
def tiny_run():
    import drive_serve_lfm2_moe as d
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
    assert {ch.name for ch in result.checks} == {
        "served_logit_gap", "served_logit_gap_mean", "route_left_out_share",
        "recompiles_in_window"}


def test_both_controls_run_and_read_worse_than_the_program():
    import drive_serve_lfm2_moe as d

    row = d.readings(_ctx(seconds=1.5), control=True)
    assert row["compared"] > 0
    assert row["mean"]["program"] < 1e-5
    for name in d.CONTROLS:
        assert row["mean"][name] > 1e-4, name
    widest = row["by_margin_min"]["0.0"]
    assert widest["program"] < 1e-4 < widest["tail_reset"]


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    import drive_serve_lfm2_moe as d

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


# -- the readers this cell adds ------------------------------------------------

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
    assert 0.0 < _read("lfm2_serve_mfu", result, ctx) < 1.0
    assert _read("decode_rows_per_step", result, ctx) > 1.0
    assert _read("moe_load_max_over_mean", result, ctx) >= 1.0
    assert _read("backlog_host_turn_ms_per_step", result, ctx) > 0.0


def test_the_new_readers_find_nothing_in_another_kinds_run(tiny_run):
    import trace_reduce

    result, ctx, _ = tiny_run
    bare = harness.Result(
        attempted=1, failed=0, end_to_end={},
        window_start=result.window_start, window_s=result.window_s,
        memory_peak_bytes=0, checks=[],
        counters={"prompt_lens": [5], "decode_kv_lens": [6],
                  "traced": {"decode_kv_lens": [6], "prompt_lens": [5]}},
        trace=trace_reduce.Trace(
            [trace_reduce.DeviceTrace("/device:TPU:0", [], [])], []),
        trace_window_ns=(0.0, 1.0))
    for name in ("lfm2_serve_mfu", "lfm2_decode_step_roofline",
                 "lfm2_decode_experts_roofline",
                 "lfm2_short_conv_ms_per_step", "lfm2_prefill_mfu"):
        assert _read(name, bare, ctx) is None, name


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
    assert 1 <= touched <= 4 * 8
    nbytes = flops.decode_steps_bytes(m, 1, touched, 3, 152)
    step = max(nbytes / PEAK["hbm_bytes_per_s"],
               sum(flops.decode_flops(m, k) for k in (50, 47, 55))
               / PEAK["bf16_flops_per_s"])
    assert _read("lfm2_decode_step_roofline", result, ctx) == \
        pytest.approx(100 * step / 1e-3)
    assert result.counters["lfm2_decode_step_bytes"] == nbytes
    work = flops.prefill_flops(m, 46) + flops.prefill_flops(m, 52)
    assert _read("lfm2_prefill_mfu", result, ctx) == pytest.approx(
        100 * work / 2e-3 / PEAK["bf16_flops_per_s"])

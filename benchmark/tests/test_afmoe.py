"""The afmoe cell's own files at a tiny size on the CPU: the reference
and ``flops_afmoe.py`` by hand-computed cases, the window driver end to
end, its comparison shown to fail, and the six readers it adds."""

import json
import os
import time

import numpy as np
import pytest

import flops_afmoe
import harness
from reference import afmoe_serve as ref

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LIMITS = {"served_logit_gap": 1e-3, "served_logit_gap_mean": 1e-4,
          "route_margin_min": 0.0, "route_left_out_share": 0.0}


def _load(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def _ctx(limits=LIMITS, seconds=2.0, seed=2 ** 31 + 29):
    return harness.Context(
        workload={"name": "test"}, config=_load("tiny-serve-afmoe"),
        mix=_load("tiny-backlog-mixed"), limits=dict(limits), peak=PEAK,
        seed=seed, seconds=seconds, trace=False,
        t_process=time.perf_counter())


# -- the yardstick --------------------------------------------------------------

def test_flops_afmoe_counts_the_published_model_as_the_issue_does():
    config = harness.load_json("configs", "trinity-large-serve-ep8-l5.json")
    m = flops_afmoe.model_shape(flops_afmoe.model_of(config))
    assert (m.window_layers, m.full_layers, m.expert_layers) == (4, 1, 4)
    assert flops_afmoe.attention_params(m) == 62_914_560        # 62.9M
    assert flops_afmoe.mlp_params(m, m.ffn) == 113_246_208      # 113.2M
    assert flops_afmoe.mlp_params(m, m.expert_ffn) == 28_311_552
    assert round(flops_afmoe.held_params(m) / 1e9, 2) == 4.32
    assert flops_afmoe.kv_token_bytes(m, 2) == 4096
    assert flops_afmoe.routed_pairs_per_token(m) == 0.5


def test_flops_afmoe_by_hand():
    m = flops_afmoe.AfmoeShape(
        layer_types=("sliding_attention", "full_attention"), dense_layers=1,
        hidden=4, heads=2, kv_heads=1, head_dim=2, ffn=8, expert_ffn=3,
        router_width=8, held=2, top_k=2, window=3, vocab=10)
    # pairs: a window of 3 caps the causal count
    assert flops_afmoe.attention_pairs(4, 4) == 1 + 2 + 3 + 4
    assert flops_afmoe.attention_pairs(4, 4, 3) == 1 + 2 + 3 + 3
    assert flops_afmoe.attention_pairs(1, 7, 3) == 3
    assert flops_afmoe.attention_pairs(2, 5, 3) == 3 + 3
    assert flops_afmoe.attention_pairs(2, 3, 3) == 2 + 3
    assert flops_afmoe.attention_pairs(1, 2, 3) == 2
    # wq, wgate, wo [4, 4] and wk, wv [4, 2]
    assert flops_afmoe.attention_params(m) == 3 * 16 + 2 * 8
    # one token: two attentions, a dense MLP, and in the expert layer the
    # router, the shared expert and 2 * 2 / 8 of a routed pair
    per_token = 2 * (2 * 64 + 3 * 4 * 8 + 4 * 8 + 3 * 4 * 3 * 1.5)
    assert flops_afmoe.layer_matmul_flops_per_token(m) == per_token
    # decode at a context of 5: the full layer scores 5 keys, the window 3
    assert flops_afmoe.decode_flops(m, 5) == \
        per_token + 2 * 4 * 10 + 4 * 2 * 2 * (5 + 3)
    assert flops_afmoe.prefill_flops(m, 4) == \
        4 * per_token + 4 * 2 * 2 * (10 + 9) + 2 * 4 * 10
    # bytes: K and V of a token are 2 * 1 * 2 values
    assert flops_afmoe.window_attention_bytes(m, [2, 9], 2) == 8 * (2 + 3)
    assert flops_afmoe.full_attention_bytes(m, [2, 9], 2) == 8 * 11
    # one row chooses 2 of 8: each of the 2 held is chosen with p = 1/4
    assert flops_afmoe.experts_touched(m, 1) == pytest.approx(0.5)
    assert flops_afmoe.experts_touched(m, 1000) == pytest.approx(2.0)
    weights = (2 * 64 + 96 + 36 * (1 + 0.5) + 32 + 40 + 4) * 2
    assert flops_afmoe.decode_weight_bytes(m, 1, 2) == pytest.approx(weights)
    assert flops_afmoe.decode_steps_bytes(m, 2, [2, 9], 2) == pytest.approx(
        2 * weights + 8 * 5 + 8 * 11)


def test_reference_by_hand_one_sliding_layer_of_experts():
    """One sliding layer, window 2, two heads over one K/V head, top-1
    of 2 experts of which the second is held: every step by hand."""
    import jax.numpy as jnp

    model = dict(
        vocab_size=3, hidden_size=2, num_attention_heads=2,
        num_key_value_heads=1, head_dim=2, layer_types=["sliding_attention"],
        num_dense_layers=0, intermediate_size=2, moe_intermediate_size=1,
        num_experts=1, router_width=2, experts_held=[1, 2],
        num_experts_per_tok=1, route_scale=2.0, sliding_window=2,
        rope_theta=10000, rms_norm_eps=0.0)
    shape = ref.model_shape(model)
    rng = np.random.RandomState(0)
    leaf = lambda *s: rng.randn(*s).astype(np.float32)
    moe = {"router": leaf(2, 2), "expert_bias": np.float32([0.0, 5.0]),
           "experts": {"wg": leaf(1, 2, 1), "wu": leaf(1, 2, 1),
                       "wd": leaf(1, 1, 2)},
           "shared": {"wg": leaf(2, 1), "wu": leaf(2, 1), "wd": leaf(1, 2)}}
    layer = {"g1": leaf(2), "g2": leaf(2), "g3": leaf(2), "g4": leaf(2),
             "gq": leaf(2), "gk": leaf(2), "wq": leaf(2, 4), "wk": leaf(2, 2),
             "wv": leaf(2, 2), "wgate": leaf(2, 4), "wo": leaf(4, 2),
             "moe": moe}
    params = {"embed": leaf(3, 2), "head": leaf(2, 3), "norm_f": leaf(2),
              "layers": [layer]}
    tokens = [2, 0, 1]
    got = np.asarray(ref.logits_all(
        params, jnp.asarray(tokens, jnp.int32), shape))

    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True)) * g
    sig = lambda x: 1 / (1 + np.exp(-x))
    silu = lambda x: x * sig(x)

    def rope(x, p):                       # x [2]: one frequency, theta^0
        c, s = np.cos(p), np.sin(p)
        return np.array([x[0] * c - x[1] * s, x[1] * c + x[0] * s])

    x = params["embed"][tokens] * np.sqrt(2)
    u = rms(x, layer["g1"])
    q = (u @ layer["wq"]).reshape(3, 2, 2)
    k = (u @ layer["wk"]).reshape(3, 1, 2)
    v = (u @ layer["wv"]).reshape(3, 1, 2)
    q, k = rms(q, layer["gq"]), rms(k, layer["gk"])
    q = np.stack([[rope(q[p, n], p) for n in range(2)] for p in range(3)])
    k = np.stack([[rope(k[p, 0], p)] for p in range(3)])
    a = np.zeros((3, 4), np.float32)
    for p in range(3):
        keys = [j for j in range(3) if p - 2 < j <= p]   # itself and one back
        for n in range(2):
            s = np.array([q[p, n] @ k[j, 0] for j in keys]) / np.sqrt(2)
            w = np.exp(s - s.max())
            w /= w.sum()
            a[p, 2 * n:2 * n + 2] = sum(wi * v[j, 0] for wi, j in zip(w, keys))
    a = a * sig(u @ layer["wgate"])
    h = x + rms(a @ layer["wo"], layer["g2"])
    u = rms(h, layer["g3"])
    score = sig(u @ moe["router"])
    mlp = lambda p: (silu(u @ p["wg"]) * (u @ p["wu"])) @ p["wd"]
    # the bias of 5 puts expert 1 first everywhere; its weight is its own
    # score, renormalised over the one chosen (to 1) and scaled by 2
    assert ((score + moe["expert_bias"]).argmax(-1) == 1).all()
    e1 = {key: w[0] for key, w in moe["experts"].items()}
    y = mlp(moe["shared"]) + 2.0 * mlp(e1)
    out = rms(h + rms(y, layer["g4"]), params["norm_f"]) @ params["head"]
    np.testing.assert_allclose(got, out, rtol=2e-4, atol=2e-5)
    # held [0, 1) instead: expert 1 is absent and only the shared is left
    other = ref.model_shape({**model, "experts_held": [0, 1]})
    got0 = np.asarray(ref.logits_all(
        params, jnp.asarray(tokens, jnp.int32), other))
    out0 = rms(h + rms(mlp(moe["shared"]), layer["g4"]),
               params["norm_f"]) @ params["head"]
    np.testing.assert_allclose(got0, out0, rtol=2e-4, atol=2e-5)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "afmoe_serve.py")) as f:
        assert "apex_tpu" not in f.read()


# -- the window driver, end to end at a tiny size on the CPU -------------------

@pytest.fixture(scope="module")
def tiny_run():
    import drive_serve_afmoe
    from apex_tpu.telemetry import PHASE_RING

    PHASE_RING.clear()
    ctx = _ctx()
    result = drive_serve_afmoe.run(ctx)
    return result, ctx, PHASE_RING.snapshot()


def test_afmoe_driver_runs_and_is_correct(tiny_run):
    result, _, _ = tiny_run
    assert result.correct, result.checks
    assert result.failed == 0 and result.attempted > 0
    assert result.end_to_end["serve_tokens_per_s"] > 0
    assert result.counters["served_tokens_compared"] > 0
    assert result.counters["window_pages_per_request"] == 3 + 2 + 1
    assert max(result.counters["prompt_lens"]) > 16      # a chunked prompt
    assert {c.name for c in result.checks} == {
        "served_logit_gap", "served_logit_gap_mean", "route_left_out_share",
        "recompiles_in_window"}


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    import drive_serve_afmoe

    real_build = drive_serve_afmoe.build

    def build(ctx):
        eng, make_weights, module, shape = real_build(ctx)
        decode = eng._decode_fn

        def altered(*args):
            tok, *rest = decode(*args)
            return ((tok + 1) % shape.vocab, *rest)

        eng._decode_fn = altered
        return eng, make_weights, module, shape

    monkeypatch.setattr(drive_serve_afmoe, "build", build)
    result = drive_serve_afmoe.run(_ctx(seconds=1.0))
    assert not result.correct
    assert [c.name for c in result.checks if not c.ok] == [
        "served_logit_gap", "served_logit_gap_mean"]


def test_fp8_control_comes_out_not_correct_and_margins_leave_positions_out():
    import drive_serve_afmoe as d

    ctx = _ctx(seconds=1.0)
    eng, make_weights, module, shape = d.build(ctx)
    eng.warmup()
    offered, *_ = d.window(ctx, eng, harness.Tracer(False), 1.0)
    sample = d.sample_finished(offered, ctx.seed, d.SAMPLE_REQUESTS)
    gaps, lows, margins = d.position_gaps(
        ctx, module, shape, make_weights(), sample, cast_name="fp8")
    assert len(gaps) == sum(len(r.generated) for r in sample) > 0
    assert d.widest(gaps, margins, 0.0) == (pytest.approx(gaps.max()), 0.0)
    assert gaps.max() <= LIMITS["served_logit_gap"] < lows.max()
    cut = float(np.median(margins))
    widest_kept, left_out = d.widest(lows, margins, cut)
    assert 0.3 < left_out < 0.7 and widest_kept <= lows.max()


# -- the readers this cell adds ------------------------------------------------

RING_READERS = ("window_pages_saved_share", "moe_load_max_over_mean")


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
    saved = _read("window_pages_saved_share", result, ctx)
    assert 0.0 < saved < 100.0
    uneven = _read("moe_load_max_over_mean", result, ctx)
    assert uneven >= 1.0
    assert 0.0 < _read("afmoe_serve_mfu", result, ctx) < 1.0
    assert _read("decode_rows_per_step", result, ctx) > 1.0
    assert _read("backlog_prefill_stall_ms_per_step", result, ctx) > 0.0


@pytest.mark.parametrize("name", RING_READERS)
def test_ring_readers_find_nothing_on_an_empty_ring(name, tiny_run):
    result, ctx, _ = tiny_run
    _refill([])
    assert _read(name, result, ctx) is None


def test_trace_readers_on_a_made_up_trace(tiny_run):
    import trace_reduce

    result, ctx, _ = tiny_run
    pallas = ('%{} = f32[4,6,1,8] custom-call(), '
              'custom_call_target="tpu_custom_call"')
    ops = [(pallas.format("flash_decode_window.1"), 1e6 + 10, 2e5),
           (pallas.format("flash_decode.2"), 1e6 + 3e5, 1e5),
           (pallas.format("flash_decode_window"), 1e6 + 5e5, 2e5)]
    dev = trace_reduce.DeviceTrace(
        "/device:TPU:0",
        [("jit__decode(1)", 1e6, 1e6), ("jit__chunk(2)", 3e6, 2e6),
         ("jit__prefill(3)", 6e6, 1e6)], ops)
    result.trace = trace_reduce.Trace([dev], [])
    result.trace_window_ns = (0.0, 1e7)
    result.counters["traced"] = {"decode_steps": 1,
                                 "decode_kv_lens": [30, 7, 25],
                                 "prompt_lens": [40, 6]}
    m = flops_afmoe.model_shape(flops_afmoe.model_of(ctx.config))
    window_bytes = 3 * 64 * (20 + 7 + 20)    # 3 layers, 64 B a token
    assert flops_afmoe.window_attention_bytes(m, [30, 7, 25], 4) \
        == 2 * window_bytes
    assert _read("window_decode_attn_roofline", result, ctx) == pytest.approx(
        100 * window_bytes / PEAK["hbm_bytes_per_s"] / 4e-4)
    step = flops_afmoe.decode_steps_bytes(m, 1, [30, 7, 25], 2)
    assert _read("afmoe_decode_step_roofline", result, ctx) == pytest.approx(
        100 * step / PEAK["hbm_bytes_per_s"] / 1e-3)
    work = flops_afmoe.prefill_flops(m, 40) + flops_afmoe.prefill_flops(m, 6)
    assert _read("afmoe_prefill_mfu", result, ctx) == pytest.approx(
        100 * work / 3e-3 / PEAK["bf16_flops_per_s"])
    result.trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace("/device:TPU:0", [], [])], [])
    for name in ("window_decode_attn_roofline", "afmoe_decode_step_roofline",
                 "afmoe_prefill_mfu"):
        assert _read(name, result, ctx) is None

"""The DeepSeek-V2 cell's own files at a tiny size on the CPU: the
reference and ``flops_deepseek_v2.py`` by hand-computed cases, the
window driver with its preload end to end, its comparison shown to fail,
and the six readers it adds."""

import json
import os
import time

import numpy as np
import pytest

import flops_deepseek_v2 as flops
import harness
from reference import deepseek_v2_serve as ref

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LIMITS = {"served_logit_gap": 1e-3, "served_logit_gap_mean": 1e-4,
          "route_margin_min": 0.0, "route_left_out_share": 0.0}


def _load(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def _ctx(limits=LIMITS, seconds=2.0, seed=2 ** 31 + 33):
    return harness.Context(
        workload={"name": "test"}, config=_load("tiny-serve-deepseek-v2"),
        mix=_load("tiny-backlog-shared"), limits=dict(limits), peak=PEAK,
        seed=seed, seconds=seconds, trace=False,
        t_process=time.perf_counter())


# -- the yardstick --------------------------------------------------------------

def test_flops_counts_the_published_model_as_the_issue_does():
    config = harness.load_json("configs", "deepseek-v2-serve-ep8-l6.json")
    m = flops.model_shape(flops.model_of(config))
    assert (m.layers, m.dense_layers, m.expert_layers) == (6, 1, 5)
    assert flops.attention_params(m) == 149_225_472             # 149.2M
    assert flops.mlp_params(m, m.ffn) == 188_743_680            # 188.7M
    assert flops.mlp_params(m, m.expert_ffn) == 23_592_960      # 23.6M
    assert round(flops.expert_layer_params(m, m.held) / 1e6, 1) == 519.9
    assert round(flops.held_params(m) / 1e6) == 3814
    assert flops.expanded_pair_flops(m) == 81_920
    assert flops.absorbed_pair_flops(m) == 278_528
    assert flops.latent_token_bytes(m, 2) == 1152
    assert flops.routed_pairs_per_token(m) == 0.75
    # every published width is the catalog's
    for key, want in dict(
            hidden_size=5120, num_attention_heads=128, q_lora_rank=1536,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, intermediate_size=12288,
            moe_intermediate_size=1536, num_experts_per_tok=6, n_group=8,
            topk_group=3).items():
        assert config[key] == want, key
    assert config["model"]["router_width"] == 160
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]


def test_flops_by_hand():
    m = flops.DeepseekV2Shape(
        layers=2, dense_layers=1, hidden=4, heads=2, q_rank=3, kv_rank=2,
        nope=2, rope=2, v_dim=2, ffn=8, expert_ffn=3, router_width=8,
        held=2, top_k=2, n_shared=2, vocab=10)
    # wdq 4x3, wuq 3x(2x4), wdkv 4x4, wuk + wuv 2x(2x4), wo 4x4
    attn = 12 + 24 + 16 + 16 + 16
    assert flops.attention_params(m) == attn
    assert flops.latent_token_bytes(m, 2) == 8
    # one token: two attentions, a dense MLP, and in the expert layer the
    # router, the shared MLP of width 6 and 2 * 2 / 8 of a routed pair
    per_token = 2 * (2 * attn + 3 * 4 * 8 + 4 * 8 + 3 * 4 * 6
                     + 0.5 * 3 * 4 * 3)
    assert flops.layer_matmul_flops_per_token(m) == per_token
    assert flops.expanded_pair_flops(m) == 2 * (2 * 4 + 2 * 2)
    assert flops.absorbed_pair_flops(m) == 2 * (2 * 4 + 2 * 2)
    assert flops.decode_flops(m, 5) == per_token + 2 * 4 * 10 + 2 * 24 * 5
    # a context of 7 of which 4 rode in: 3 tokens, pairs 5 + 6 + 7
    assert flops.prefill_flops(m, 7, 4) == \
        3 * per_token + 2 * 24 * 18 + 2 * 4 * 10
    assert flops.prefill_flops(m, 4) == \
        4 * per_token + 2 * 24 * 10 + 2 * 4 * 10
    # three rows, two of them on one document of 4 tokens
    assert flops.distinct_tokens([6, 9, 5], [2], 4) == 20 - 4
    assert flops.latent_attention_bytes(m, 16, 2) == 2 * 8 * 16
    assert flops.experts_touched(m, 1) == pytest.approx(0.5)
    weights = (2 * attn + 96 + 32 + 72 + 36 * 0.5 + 40 + 4) * 2
    assert flops.decode_weight_bytes(m, 1, 2) == pytest.approx(weights)
    assert flops.decode_steps_bytes(m, 2, 2, 16, 2) == pytest.approx(
        2 * weights + 256)


def test_reference_by_hand_one_expert_layer():
    """One expert layer, two heads, rank 2, a rotary pair, two groups of
    two experts of which one group is kept and top-1 taken, the second
    group held: every step by hand."""
    import jax.numpy as jnp

    model = dict(
        vocab_size=3, hidden_size=2, num_attention_heads=2, q_lora_rank=2,
        kv_lora_rank=2, qk_nope_head_dim=2, qk_rope_head_dim=2, v_head_dim=2,
        num_hidden_layers=1, first_k_dense_replace=0, intermediate_size=2,
        moe_intermediate_size=1, n_routed_experts=2, router_width=4,
        experts_held=[2, 4], num_experts_per_tok=1, n_group=2, topk_group=1,
        routed_scaling_factor=3.0, n_shared_experts=2, rope_theta=10000,
        rope_scaling={"factor": 4, "original_max_position_embeddings": 8,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 0.5,
                      "mscale_all_dim": 0.5},
        rms_norm_eps=0.0)
    shape = ref.model_shape(model)
    rng = np.random.RandomState(0)
    leaf = lambda *s: rng.randn(*s).astype(np.float32)
    moe = {"router": leaf(2, 4),
           "experts": {"wg": leaf(2, 2, 1), "wu": leaf(2, 2, 1),
                       "wd": leaf(2, 1, 2)},
           "shared": {"wg": leaf(2, 2), "wu": leaf(2, 2), "wd": leaf(2, 2)}}
    layer = {"g1": leaf(2), "g2": leaf(2), "gq": leaf(2), "gkv": leaf(2),
             "wdq": leaf(2, 2), "wuq": leaf(2, 8), "wdkv": leaf(2, 4),
             "wuk": leaf(2, 4), "wuv": leaf(2, 4), "wo": leaf(4, 2),
             "moe": moe}
    params = {"embed": leaf(3, 2), "head": leaf(2, 3), "norm_f": leaf(2),
              "layers": [layer]}
    tokens = [2, 0, 1]
    got = np.asarray(ref.logits_all(
        params, jnp.asarray(tokens, jnp.int32), shape))

    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True)) * g
    silu = lambda x: x / (1 + np.exp(-x))
    # one rotary pair, frequency theta^0 = 1: low = floor(corr(32)) < 0
    # clips to 0, high = ceil(corr(1)) = 1, ramp_0 = 0: untouched
    inv_freq, low, high = ref.yarn_frequencies(shape)
    assert (low, high) == (0, 1) and inv_freq.tolist() == [1.0]
    m_all = 0.1 * 0.5 * np.log(4) + 1
    scale = 4 ** -0.5 * m_all * m_all
    assert ref.softmax_scale(shape) == pytest.approx(scale)

    def rope(x, p):
        c, s = np.cos(p), np.sin(p)
        return np.array([x[0] * c - x[1] * s, x[1] * c + x[0] * s])

    x = params["embed"][tokens]
    u = rms(x, layer["g1"])
    cq = rms(u @ layer["wdq"], layer["gq"])
    q = (cq @ layer["wuq"]).reshape(3, 2, 4)
    ckv = u @ layer["wdkv"]
    c = rms(ckv[:, :2], layer["gkv"])
    k_pe = np.stack([rope(ckv[p, 2:], p) for p in range(3)])
    k_nope = (c @ layer["wuk"]).reshape(3, 2, 2)
    v = (c @ layer["wuv"]).reshape(3, 2, 2)
    a = np.zeros((3, 4), np.float32)
    for p in range(3):
        for n in range(2):
            qn = np.concatenate([q[p, n, :2], rope(q[p, n, 2:], p)])
            s = np.array([qn @ np.concatenate([k_nope[j, n], k_pe[j]])
                          for j in range(p + 1)]) * scale
            w = np.exp(s - s.max())
            w /= w.sum()
            a[p, 2 * n:2 * n + 2] = sum(w[j] * v[j, n] for j in range(p + 1))
    h = x + a @ layer["wo"]
    u = rms(h, layer["g2"])
    logit = u @ moe["router"]
    score = np.exp(logit - logit.max(-1, keepdims=True))
    score /= score.sum(-1, keepdims=True)
    mlp = lambda p: (silu(u @ p["wg"]) * (u @ p["wu"])) @ p["wd"]
    y = mlp(moe["shared"])
    for t in range(3):
        group = int(np.argmax(score[t].reshape(2, 2).max(-1)))
        e = 2 * group + int(np.argmax(score[t, 2 * group:2 * group + 2]))
        if e >= 2:                       # held here: experts [2, 4)
            p = {key: w[e - 2] for key, w in moe["experts"].items()}
            y[t] += 3.0 * score[t, e] * (
                (silu(u[t] @ p["wg"]) * (u[t] @ p["wu"])) @ p["wd"])
    out = rms(h + y, params["norm_f"]) @ params["head"]
    np.testing.assert_allclose(got, out, rtol=2e-4, atol=2e-5)


def test_the_margin_is_the_lesser_of_the_experts_and_the_kept_sets():
    import jax.numpy as jnp

    m = ref.Shape(
        vocab=1, hidden=1, heads=1, q_rank=1, kv_rank=1, nope=1, rope=2,
        v_dim=1, layers=1, dense_layers=0, ffn=1, expert_ffn=1,
        router_width=6, held=(0, 2), top_k=2, n_group=3, topk_group=2,
        route_scale=1.0, n_shared=1, theta=10000.0,
        yarn=(1.0, 8, 32.0, 1.0, 1.0, 1.0), eps=0.0)
    score = jnp.asarray([
        # groups score .30, .25, .20: group 0 is kept, and the kept set
        # by .25 - .20; chosen .30 and .25; held expert .10 is out by .15
        [0.30, 0.10, 0.25, 0.05, 0.20, 0.10],
        # group 0 (.11) is left out by .01 under the second group (.12)
        [0.11, 0.02, 0.50, 0.05, 0.12, 0.10]], jnp.float32)
    sel, w, margin = ref.route(score, m)
    assert np.asarray(sel).tolist() == [[0, 2], [2, 4]]
    np.testing.assert_allclose(np.asarray(w), [[0.30, 0.25], [0.50, 0.12]],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(margin), [0.05, 0.01], atol=1e-6)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "deepseek_v2_serve.py")) as f:
        assert "apex_tpu" not in f.read()


# -- the window driver, end to end at a tiny size on the CPU -------------------

@pytest.fixture(scope="module")
def tiny_run():
    import drive_serve_deepseek_v2 as d
    from apex_tpu.telemetry import PHASE_RING

    PHASE_RING.clear()
    ctx = _ctx()
    result = d.run(ctx)
    return result, ctx, PHASE_RING.snapshot()


def test_driver_preloads_runs_and_is_correct(tiny_run):
    result, ctx, _ = tiny_run
    c = result.counters
    assert result.correct, result.checks
    assert result.failed == 0 and result.attempted > 0
    assert result.end_to_end["serve_tokens_per_s"] > 0
    assert c["served_tokens_compared"] > 0 and c["preload_s"] > 0
    # every request of the window found its document in the index
    admitted = len(c["prompt_lens"])
    # (and now and then a question's first token in an earlier context)
    assert admitted > 3 and min(c["prompt_shared"]) == 40
    assert max(c["prompt_shared"]) <= 43
    assert c["prefix_hits"] >= admitted
    assert set(c["decode_shared_rows"]) <= {1, 2, 3, 4}
    assert max(c["decode_shared_rows"]) > 1
    assert {ch.name for ch in result.checks} == {
        "served_logit_gap", "served_logit_gap_mean", "route_left_out_share",
        "recompiles_in_window"}


def test_the_preload_reads_the_documents_the_window_will_ask_about():
    import drive_serve_deepseek_v2 as d
    import traffic

    ctx = _ctx()
    docs = d.documents(ctx, 96)
    assert len(docs) == 3 and all(len(doc) == 40 for doc in docs)
    source = traffic.requests(ctx.mix, ctx.seed, 96)
    for _ in range(40):
        assert next(source).prompt[:40] in docs


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    import drive_serve_deepseek_v2 as d

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
    share = _read("prefix_shared_token_share", result, ctx)
    assert 100 * 40 / 60 <= share <= 100 * 40 / 44
    assert _read("dsv2_moe_load_max_over_mean", result, ctx) >= 1.0
    assert 0.0 < _read("dsv2_serve_mfu", result, ctx) < 1.0
    assert _read("decode_rows_per_step", result, ctx) > 1.0
    assert _read("backlog_prefill_stall_ms_per_step", result, ctx) > 0.0
    assert _read("backlog_host_cpu_ms_per_step", result, ctx) > 0.0


@pytest.mark.parametrize("name", ["prefix_shared_token_share",
                                  "dsv2_moe_load_max_over_mean"])
def test_ring_readers_find_nothing_on_an_empty_ring(name, tiny_run):
    result, ctx, _ = tiny_run
    _refill([])
    assert _read(name, result, ctx) is None


def test_the_new_readers_find_nothing_in_another_kinds_run(tiny_run):
    """A program without what this PR adds, or a cell of another kind:
    nothing to read, and nothing raised."""
    result, ctx, records = tiny_run
    _refill(records)
    bare = harness.Result(
        attempted=1, failed=0, end_to_end={}, window_start=result.window_start,
        window_s=result.window_s, memory_peak_bytes=0, checks=[],
        counters={"prompt_lens": [5], "decode_kv_lens": [6],
                  "traced": {"decode_kv_lens": [6], "prompt_lens": [5]}},
        trace=None, trace_window_ns=(0.0, 1.0))
    other = harness.Context(**{**ctx.__dict__,
                               "config": _load("tiny-serve-afmoe")})
    for name in ("dsv2_serve_mfu", "dsv2_decode_step_roofline",
                 "latent_decode_attn_roofline", "dsv2_chunk_mfu"):
        import trace_reduce
        bare.trace = trace_reduce.Trace(
            [trace_reduce.DeviceTrace("/device:TPU:0", [], [])], [])
        assert _read(name, bare, ctx) is None, name
    assert _read("dsv2_moe_load_max_over_mean", result, other) is None


def test_trace_readers_on_a_made_up_trace(tiny_run):
    import trace_reduce

    result, ctx, _ = tiny_run
    pallas = ('%{} = f32[4,6,1,8] custom-call(), '
              'custom_call_target="tpu_custom_call"')
    ops = [(pallas.format("flash_decode_latent.1"), 1e6 + 10, 2e5),
           (pallas.format("flash_decode.2"), 1e6 + 3e5, 1e5),
           (pallas.format("flash_decode_latent"), 1e6 + 5e5, 2e5)]
    dev = trace_reduce.DeviceTrace(
        "/device:TPU:0",
        [("jit__decode(1)", 1e6, 1e6), ("jit__chunk(2)", 3e6, 2e6)], ops)
    result.trace = trace_reduce.Trace([dev], [])
    result.trace_window_ns = (0.0, 1e7)
    result.counters["traced"] = {
        "decode_steps": 1, "decode_kv_lens": [50, 47, 55],
        "decode_shared_rows": [2, 1], "prompt_lens": [46, 52],
        "prompt_shared": [40, 40]}
    m = flops.model_shape(flops.model_of(ctx.config))
    distinct = 50 + 47 + 55 - 40
    work = sum(flops.decode_attention_flops(m, k) for k in (50, 47, 55))
    least = max(flops.latent_attention_bytes(m, distinct, 2)
                / PEAK["hbm_bytes_per_s"], work / PEAK["bf16_flops_per_s"])
    assert _read("latent_decode_attn_roofline", result, ctx) == \
        pytest.approx(100 * least / 4e-4)
    step = max(flops.decode_steps_bytes(m, 1, 3, distinct, 2)
               / PEAK["hbm_bytes_per_s"],
               sum(flops.decode_flops(m, k) for k in (50, 47, 55))
               / PEAK["bf16_flops_per_s"])
    assert _read("dsv2_decode_step_roofline", result, ctx) == \
        pytest.approx(100 * step / 1e-3)
    chunk = flops.prefill_flops(m, 46, 40) + flops.prefill_flops(m, 52, 40)
    assert _read("dsv2_chunk_mfu", result, ctx) == pytest.approx(
        100 * chunk / 2e-3 / PEAK["bf16_flops_per_s"])

"""The Granite-hybrid cell's own files at a tiny size on the CPU: the
reference and ``flops_granite_hybrid.py`` by hand-computed cases, the
window driver end to end with both controls, its comparison shown to
fail, and the five readers it adds."""

import json
import os
import time

import numpy as np
import pytest

import flops_granite_hybrid as flops
import harness
from reference import granite_hybrid_serve as ref

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LIMITS = {"served_logit_gap": 1e-4, "served_logit_gap_mean": 1e-5,
          "first_layer_state_gap": 1e-4}


def _load(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def _ctx(limits=LIMITS, seconds=2.0, seed=2 ** 31 + 35):
    return harness.Context(
        workload={"name": "test"}, config=_load("tiny-serve-granite-hybrid"),
        mix=_load("tiny-backlog"), limits=dict(limits), peak=PEAK,
        seed=seed, seconds=seconds, trace=False,
        t_process=time.perf_counter())


# -- the yardstick --------------------------------------------------------------

def test_flops_counts_the_published_model_as_the_issue_does():
    config = harness.load_json("configs", "granite-4.0-h-micro-serve.json")
    m = flops.model_shape(flops.model_of(config))
    assert (m.ssm_layers, m.attention_layers) == (36, 4)
    assert [i for i, t in enumerate(m.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    assert m.hidden * (m.d_inner + m.conv_dim + m.ssm_heads) == 17_432_576
    assert m.d_inner * m.hidden == 8_388_608
    assert flops.ssm_mixer_params(m) == 25_821_184             # 25.8M
    assert flops.ssm_small_params(m) == 21_760 + 4_096 + 192
    assert flops.attention_mixer_params(m) == 10_485_760        # 10.49M
    assert flops.mlp_params(m) == 50_331_648                    # 50.33M
    assert m.vocab * m.hidden == 205_520_896                    # 205.5M
    assert round(flops.total_params(m) / 1e6) == 3191
    assert round(flops.decode_weight_bytes(m) / 1e9, 2) == 6.38
    assert flops.state_bytes(m) == 2_097_152
    assert flops.tail_bytes(m) == 26_112
    assert round(flops.slot_bytes(m) / 1e6, 1) == 76.4
    assert flops.kv_token_bytes(m) == 8192
    # a step of 64 rows: 9.8 GB of state and tail, in and out
    assert round(64 * flops.decode_row_state_bytes(m) / 1e9, 1) == 9.8
    assert flops.ssm_update_call_flops(m, 1) == 3_145_728       # 3.1 MFLOP
    # every published key is the catalog's, nothing reduced
    for key, want in dict(
            hidden_size=2048, num_hidden_layers=40, num_attention_heads=32,
            num_key_value_heads=8, shared_intermediate_size=8192,
            mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
            mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
            mamba_chunk_size=256, vocab_size=100352,
            embedding_multiplier=12, residual_multiplier=0.22,
            attention_multiplier=0.015625, logits_scaling=8,
            tie_word_embeddings=True, max_position_embeddings=131072,
            position_embedding_type="nope").items():
        assert config[key] == want, key
    assert config["reduced"] == [] and len(config["layer_types"]) == 40
    assert config["model"] == {"dtype": "bfloat16",
                               "ssm_state_dtype": "float32"}
    assert set(config["assumed"]) >= {"split_order", "gated_norm",
                                      "state_dtype", "pool"}


def test_flops_by_hand():
    m = flops.GraniteHybridShape(
        layer_types=("mamba", "attention", "mamba"), hidden=4, heads=2,
        kv_heads=1, ffn=6, ssm_heads=2, ssm_head_dim=4, state=3, taps=4,
        vocab=10)
    assert (m.d_inner, m.conv_dim, m.head_dim) == (8, 14, 2)
    mixer = 4 * (8 + 14 + 2) + 8 * 4              # win, wout
    attn = 2 * 16 + 2 * 4 * 2                     # wq, wo, wk, wv
    mlp = 3 * 4 * 6
    assert flops.ssm_mixer_params(m) == mixer
    assert flops.attention_mixer_params(m) == attn
    assert flops.layer_params(m) == 2 * mixer + attn + 3 * mlp
    small = 5 * 14 + 8 + 6                        # conv w + b, gn, 3 scalars
    assert flops.total_params(m) == \
        2 * mixer + attn + 3 * mlp + 2 * small + 2 * 3 * 4 + 10 * 4 + 4
    # decay, outer product (2), add, S C (2) a state element; D x and
    # the gate a channel; four taps a convolution channel
    rec = 6 * 8 * 3 + 4 * 8 + 2 * 4 * 14
    assert flops.recurrence_flops_per_token(m) == rec
    per_token = 2 * (2 * mixer + attn + 3 * mlp) + 2 * rec
    assert flops.layer_flops_per_token(m) == per_token
    # one attention layer, 2 heads of 2: 4 * 2 * 2 a pair
    assert flops.decode_flops(m, 5) == per_token + 2 * 4 * 10 + 16 * 5
    assert flops.prefill_flops(m, 4) == \
        4 * per_token + 16 * 10 + 2 * 4 * 10
    # a request holds: a float32 state and a bf16 tail a mamba layer,
    # K and V of one head of 2 a token in the attention layer
    assert flops.state_bytes(m) == 8 * 3 * 4
    assert flops.tail_bytes(m) == 3 * 14 * 2
    assert flops.slot_bytes(m) == 2 * (96 + 84)
    assert flops.kv_token_bytes(m) == 2 * 1 * 2 * 2
    weights = flops.total_params(m) * 2
    assert flops.decode_steps_bytes(m, 2, 3, 11) == \
        2 * weights + 3 * 2 * 2 * (96 + 84) + 11 * 8
    assert flops.decode_steps_bytes(m, 0, 0, 0) == 0.0
    assert flops.ssm_update_call_bytes(m, 3) == \
        3 * (2 * 96 + 4 * (3 * 8 + 2 * 3))
    assert flops.ssm_update_call_flops(m, 3) == 3 * 6 * 8 * 3


def test_reference_by_hand_one_mamba_layer():
    """One state-space layer, two heads of two channels, a state of
    three, a convolution of two taps: every step by hand."""
    import jax.numpy as jnp

    model = dict(
        vocab_size=5, hidden_size=4, num_attention_heads=2,
        num_key_value_heads=1, layer_types=["mamba"],
        shared_intermediate_size=3, mamba_n_heads=2, mamba_d_head=2,
        mamba_d_state=3, mamba_n_groups=1, mamba_d_conv=2, mamba_expand=1,
        rms_norm_eps=0.0, embedding_multiplier=12, residual_multiplier=0.22,
        attention_multiplier=0.5, logits_scaling=8, num_local_experts=0,
        tie_word_embeddings=True, position_embedding_type="nope")
    shape = ref.model_shape(model)
    rng = np.random.RandomState(0)
    leaf = lambda *s: rng.randn(*s).astype(np.float32)
    layer = {"g1": leaf(4), "g2": leaf(4), "win": leaf(4, 4 + 10 + 2),
             "conv_w": leaf(2, 10), "conv_b": leaf(10), "dt_bias": leaf(2),
             "a_log": leaf(2), "d_skip": leaf(2), "gn": leaf(4),
             "wout": leaf(4, 4),
             "mlp": {"wg": leaf(4, 3), "wu": leaf(4, 3), "wd": leaf(3, 4)}}
    params = {"embed": leaf(5, 4), "norm_f": leaf(4), "layers": [layer]}
    tokens = [3, 0, 4]
    got = np.asarray(ref.logits_all(
        params, jnp.asarray(tokens, jnp.int32), shape))

    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True)) * g
    silu = lambda x: x / (1 + np.exp(-x))
    x0 = 12.0 * params["embed"][tokens]
    u = rms(x0, layer["g1"])
    proj = u @ layer["win"]
    z, xbc, dt = proj[:, :4], proj[:, 4:14], proj[:, 14:]
    state = np.zeros((2, 2, 3), np.float32)
    ys = []
    for t in range(3):
        before = xbc[t - 1] if t else np.zeros(10, np.float32)
        c = silu(layer["conv_w"][0] * before + layer["conv_w"][1] * xbc[t]
                 + layer["conv_b"])
        x, b, cc = c[:4].reshape(2, 2), c[4:7], c[7:]
        delta = np.log1p(np.exp(dt[t] + layer["dt_bias"]))
        for h in range(2):
            state[h] = (np.exp(-np.exp(layer["a_log"][h]) * delta[h])
                        * state[h] + delta[h] * np.outer(x[h], b))
        ys.append((state @ cc + layer["d_skip"][:, None] * x).reshape(4))
    g = rms(np.stack(ys) * silu(z), layer["gn"])
    h = x0 + 0.22 * (g @ layer["wout"])
    u = rms(h, layer["g2"])
    mlp = layer["mlp"]
    out = h + 0.22 * ((silu(u @ mlp["wg"]) * (u @ mlp["wu"])) @ mlp["wd"])
    want = rms(out, params["norm_f"]) @ params["embed"].T / 8.0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_reference_attention_layer_by_hand():
    """One NoPE grouped-query layer: two query heads read one K/V head,
    scores times the attention multiplier, no position anywhere."""
    import jax.numpy as jnp

    model = dict(
        vocab_size=5, hidden_size=4, num_attention_heads=2,
        num_key_value_heads=1, layer_types=["attention"],
        shared_intermediate_size=3, mamba_n_heads=2, mamba_d_head=2,
        mamba_d_state=3, mamba_n_groups=1, mamba_d_conv=2, mamba_expand=1,
        rms_norm_eps=0.0, embedding_multiplier=12, residual_multiplier=0.22,
        attention_multiplier=0.5, logits_scaling=8, num_local_experts=0,
        tie_word_embeddings=True, position_embedding_type="nope")
    shape = ref.model_shape(model)
    rng = np.random.RandomState(1)
    leaf = lambda *s: rng.randn(*s).astype(np.float32)
    layer = {"g1": leaf(4), "g2": leaf(4), "wq": leaf(4, 4),
             "wk": leaf(4, 2), "wv": leaf(4, 2), "wo": leaf(4, 4),
             "mlp": {"wg": leaf(4, 3), "wu": leaf(4, 3), "wd": leaf(3, 4)}}
    params = {"embed": leaf(5, 4), "norm_f": leaf(4), "layers": [layer]}
    tokens = [1, 4, 2]
    got = np.asarray(ref.logits_all(
        params, jnp.asarray(tokens, jnp.int32), shape))
    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True)) * g
    silu = lambda x: x / (1 + np.exp(-x))
    x0 = 12.0 * params["embed"][tokens]
    u = rms(x0, layer["g1"])
    q = (u @ layer["wq"]).reshape(3, 2, 2)
    k, v = u @ layer["wk"], u @ layer["wv"]
    ctx = np.zeros((3, 4), np.float32)
    for p in range(3):
        for n in range(2):
            s = np.array([q[p, n] @ k[j] for j in range(p + 1)]) * 0.5
            w = np.exp(s - s.max())
            w /= w.sum()
            ctx[p, 2 * n:2 * n + 2] = sum(w[j] * v[j] for j in range(p + 1))
    h = x0 + 0.22 * (ctx @ layer["wo"])
    u = rms(h, layer["g2"])
    mlp = layer["mlp"]
    out = h + 0.22 * ((silu(u @ mlp["wg"]) * (u @ mlp["wu"])) @ mlp["wd"])
    want = rms(out, params["norm_f"]) @ params["embed"].T / 8.0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_seeded_scalars_spread_the_heads_memories():
    import jax.numpy as jnp
    import weights

    model = flops.model_of(_load("tiny-serve-granite-hybrid"))
    params = ref.finish(weights.make(ref.param_layout(model), 7,
                                     jnp.float32))
    layer = params["layers"][0]
    a = np.exp(np.asarray(layer["a_log"]))
    step = np.log1p(np.exp(np.asarray(layer["dt_bias"])))
    assert 0.9 < a[0] < 1.1 and 14 < a[-1] < 18
    assert 5e-4 < step[0] < 2e-3 and 0.05 < step[-1] < 0.2
    # a row of the embedding has norm 1 after the multiplier
    rows = 12.0 * np.linalg.norm(np.asarray(params["embed"]), axis=1)
    assert 0.7 < rows.mean() < 1.3
    assert "win" not in params["layers"][5]


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "granite_hybrid_serve.py")) as f:
        assert "apex_tpu" not in f.read()


# -- the window driver, end to end at a tiny size on the CPU -------------------

@pytest.fixture(scope="module")
def tiny_run():
    import drive_serve_granite_hybrid as d
    from apex_tpu.telemetry import PHASE_RING

    PHASE_RING.clear()
    ctx = _ctx()
    result = d.run(ctx)
    return result, ctx, PHASE_RING.snapshot()


def test_driver_runs_and_is_correct(tiny_run):
    result, ctx, _ = tiny_run
    c = result.counters
    assert result.correct, result.checks
    assert result.failed == 0 and result.attempted > 0
    assert result.end_to_end["serve_tokens_per_s"] > 0
    assert c["served_tokens_compared"] > 0 and c["preemptions"] == 0
    assert c["state_slots"] == 6 and c["recompiles_in_window"] == 0
    assert {ch.name for ch in result.checks} == {
        "served_logit_gap", "served_logit_gap_mean",
        "first_layer_state_gap", "recompiles_in_window"}
    # two running requests' slots were read, after the tokens they took
    assert len(c["state_tokens_taken"]) == 2
    assert min(c["state_tokens_taken"]) > 8


def test_both_controls_run_and_the_fp8_one_reads_worse_than_the_program():
    """The program in float32 sits on the reference; the reference in
    fp8 does not.  (Sequences of 60 tokens are too short for a state
    rounded to bfloat16 to move a choice among 96 tokens: that control
    is shown on the hidden states below.)"""
    import drive_serve_granite_hybrid as d

    row = d.readings(_ctx(seconds=1.5), control=True)
    assert row["compared"] > 0 and set(d.CONTROLS) <= set(row)
    assert row["program"]["served_logit_gap"] < 1e-4
    assert row["fp8"]["served_logit_gap_mean"] > 1e-4
    assert row["state_bf16"]["served_logit_gap"] >= 0.0
    # the state itself tells all three apart
    assert row["program"]["first_layer_state_gap"] < 1e-5
    assert 1e-3 < row["state_bf16"]["first_layer_state_gap"]
    assert 1e-2 < row["fp8"]["first_layer_state_gap"]


def test_a_state_rounded_to_bfloat16_every_token_drifts():
    """The second control moves the hidden states by more than float32
    rounding and by less than the fp8 control: the slow heads' states
    carry the rounding of hundreds of tokens."""
    import jax.numpy as jnp
    import weights

    model = flops.model_of(_load("tiny-serve-granite-hybrid"))
    shape = ref.model_shape(model)
    params = ref.finish(weights.make(ref.param_layout(model), 11,
                                     jnp.float32))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 96, 256),
                         jnp.int32)
    exact = np.asarray(ref.hidden_states(params, tokens, shape))
    err = {name: float(np.linalg.norm(np.asarray(ref.hidden_states(
        params, tokens, shape, name)) - exact) / np.linalg.norm(exact))
        for name in ("state_bf16", "fp8")}
    assert 1e-4 < err["state_bf16"] < err["fp8"] < 0.5, err


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    import drive_serve_granite_hybrid as d

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


def test_a_state_kept_in_bfloat16_is_not_correct(monkeypatch):
    """The program itself with its pool in bfloat16: the logits stay
    inside their limits, the first layer's state does not."""
    import drive_serve_granite_hybrid as d

    ctx = _ctx(seconds=1.5)
    ctx.config = {**ctx.config, "model": {"dtype": "float32",
                                          "ssm_state_dtype": "bfloat16"}}
    ctx.limits["first_layer_state_gap"] = 1e-3
    result = d.run(ctx)
    assert [c.name for c in result.checks if not c.ok] == [
        "first_layer_state_gap"]


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
    share = _read("state_slots_held_share", result, ctx)
    assert 100 * 1 / 5 <= share <= 100 * 4 / 5      # at most max_batch of 5
    assert 0.0 < _read("granite_serve_mfu", result, ctx) < 1.0
    assert _read("decode_rows_per_step", result, ctx) > 1.0
    assert _read("backlog_prefill_stall_ms_per_step", result, ctx) > 0.0
    assert _read("backlog_host_cpu_ms_per_step", result, ctx) > 0.0
    assert _read("backlog_host_turn_ms_per_step", result, ctx) > 0.0


def test_the_slot_reader_finds_nothing_on_an_empty_ring(tiny_run):
    result, ctx, _ = tiny_run
    _refill([])
    assert _read("state_slots_held_share", result, ctx) is None


def test_the_new_readers_find_nothing_in_another_kinds_run(tiny_run):
    """A program without what this PR adds, or a cell of another kind:
    nothing to read, and nothing raised."""
    import trace_reduce

    result, ctx, records = tiny_run
    # the ring of a program without slots: no state_slots on a step
    _refill([r._replace(attrs={
        k: v for k, v in (r.attrs or {}).items() if k != "state_slots"})
        for r in records])
    assert _read("state_slots_held_share", result, ctx) is None
    bare = harness.Result(
        attempted=1, failed=0, end_to_end={},
        window_start=result.window_start, window_s=result.window_s,
        memory_peak_bytes=0, checks=[],
        counters={"prompt_lens": [5], "decode_kv_lens": [6],
                  "traced": {"decode_kv_lens": [6], "prompt_lens": [5]}},
        trace=trace_reduce.Trace(
            [trace_reduce.DeviceTrace("/device:TPU:0", [], [])], []),
        trace_window_ns=(0.0, 1.0))
    for name in ("granite_serve_mfu", "granite_decode_step_roofline",
                 "ssm_decode_update_roofline", "granite_prefill_mfu"):
        assert _read(name, bare, ctx) is None, name


def test_trace_readers_on_a_made_up_trace(tiny_run):
    import trace_reduce

    result, ctx, _ = tiny_run
    pallas = ('%{} = f32[3,1,128] custom-call(), '
              'custom_call_target="tpu_custom_call"')
    ops = [(pallas.format("ssm_decode_update.1"), 1e6 + 10, 1e5),
           (pallas.format("flash_decode.2"), 1e6 + 3e5, 1e5),
           (pallas.format("ssm_decode_update"), 1e6 + 5e5, 3e5)]
    dev = trace_reduce.DeviceTrace(
        "/device:TPU:0",
        [("jit__decode(1)", 1e6, 1e6), ("jit__prefill(2)", 3e6, 2e6)], ops)
    result.trace = trace_reduce.Trace([dev], [])
    result.trace_window_ns = (0.0, 1e7)
    result.counters["traced"] = {
        "decode_steps": 1, "decode_kv_lens": [50, 47, 55],
        "prompt_lens": [46, 52]}
    m = flops.model_shape(flops.model_of(ctx.config))
    rows = 3 * m.ssm_layers
    least = max(flops.ssm_update_call_bytes(m, rows)
                / PEAK["hbm_bytes_per_s"],
                flops.ssm_update_call_flops(m, rows)
                / PEAK["bf16_flops_per_s"])
    assert _read("ssm_decode_update_roofline", result, ctx) == \
        pytest.approx(100 * least / 4e-4)
    assert result.counters["ssm_decode_update_bytes"] == \
        flops.ssm_update_call_bytes(m, rows)
    nbytes = flops.decode_steps_bytes(m, 1, 3, 152)
    step = max(nbytes / PEAK["hbm_bytes_per_s"],
               sum(flops.decode_flops(m, k) for k in (50, 47, 55))
               / PEAK["bf16_flops_per_s"])
    assert _read("granite_decode_step_roofline", result, ctx) == \
        pytest.approx(100 * step / 1e-3)
    assert result.counters["granite_decode_step_bytes"] == nbytes
    work = flops.prefill_flops(m, 46) + flops.prefill_flops(m, 52)
    assert _read("granite_prefill_mfu", result, ctx) == pytest.approx(
        100 * work / 2e-3 / PEAK["bf16_flops_per_s"])

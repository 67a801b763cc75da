"""The benchmark's own tests (not tier-1; see conftest.py)."""

import copy
import itertools
import json
import os
import time

import pytest

import flops
import harness
import trace_reduce
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# set at this tiny size on the CPU, as the cells' are on the chip: above
# what the program reads, below what the fp8 control and the faults read
TRAIN_LIMITS = {"loss_gap": 2e-4, "grad_norm_gap": 0.01,
                "delta_norm_gap": 0.02}
SERVE_LIMITS = {"served_logit_gap": 1e-3}


def _load(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def _ctx(config, mix, limits, seconds=1.5, seed=2 ** 31 + 11):
    return harness.Context(
        workload={"name": "test"}, config=_load(config), mix=_load(mix),
        limits=limits, peak=PEAK, seed=seed, seconds=seconds, trace=False,
        t_process=time.perf_counter())


# -- the yardstick -------------------------------------------------------------

def test_flops_against_the_programs_parameter_count():
    from apex_tpu.transformer.testing import gpt1p3b_config, gpt_param_count

    for layers in (12, 24):
        cfg = gpt1p3b_config(num_layers=layers)
        shape = flops.model_shape(dict(
            num_layers=layers, hidden_size=cfg.hidden_size,
            num_attention_heads=cfg.num_attention_heads,
            vocab_size=cfg.vocab_size,
            max_position_embeddings=cfg.max_position_embeddings))
        assert flops.param_count(shape) == gpt_param_count(cfg)


def test_flops_of_the_train_step_and_of_attention():
    shape = flops.model_shape(harness.load_json(
        "configs", "gpt1p3b-train-l8.json")["model"])
    # 6 x the weights a token touches (no embedding lookup) plus causal
    # attention: about 4.5 GFLOP a token at sequence 2048
    weights_touched = (flops.param_count(shape)
                       - shape.positions * shape.hidden
                       - shape.layers * (9 * shape.hidden + shape.ffn)
                       - 2 * shape.hidden)
    per_token = flops.train_flops_per_token(shape, 2048)
    assert 6 * weights_touched < per_token < 6 * weights_touched * 1.1
    assert flops.attention_pairs(4, 4) == 10
    assert flops.attention_pairs(1, 7) == 7
    assert flops.attention_pairs(2, 5) == 4 + 5
    fwd = flops.attention_call(q_lens=[2048] * 4, kv_lens=[2048] * 4,
                               heads=16, head_dim=128, itemsize=2)
    assert flops.roofline_seconds(fwd, PEAK)["bound"] == "compute"
    one = flops.attention_call(q_lens=[1] * 32, kv_lens=[900] * 32,
                               heads=16, head_dim=128, itemsize=2)
    assert flops.roofline_seconds(one, PEAK)["bound"] == "bandwidth"


def test_traffic_offers_every_seed_the_same_work_in_another_order():
    fixed = traffic.load_mix("steady-p128-1536-o32-256")
    mix = {k: v for k, v in fixed.items() if k != "schedule_seed"}
    a = list(itertools.islice(traffic.requests(mix, 1, 51200), 64))
    b = list(itertools.islice(traffic.requests(mix, 2 ** 31 + 5, 51200), 64))
    for block in (slice(0, 32), slice(32, 64)):
        assert sorted(len(r.prompt) for r in a[block]) == \
            sorted(len(r.prompt) for r in b[block])
        assert sorted(r.max_new for r in a[block]) == \
            sorted(r.max_new for r in b[block])
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[31].due_s == pytest.approx(32 / mix["rate"])
    assert b[63].due_s == pytest.approx(64 / mix["rate"])
    assert min(len(r.prompt) for r in a) >= 128
    assert max(len(r.prompt) for r in a) <= 1536
    again = list(itertools.islice(traffic.requests(mix, 1, 51200), 64))
    assert [r.prompt for r in a] == [r.prompt for r in again]
    # with a schedule_seed the order is the mix's; the seed draws the ids
    c = list(itertools.islice(traffic.requests(fixed, 1, 51200), 64))
    d = list(itertools.islice(traffic.requests(fixed, 7, 51200), 64))
    assert [(len(r.prompt), r.max_new, r.due_s) for r in c] == \
        [(len(r.prompt), r.max_new, r.due_s) for r in d]
    assert [r.prompt for r in c] != [r.prompt for r in d]


def test_traffic_bursts_prefixes_and_other_distributions_are_data():
    mix = {"loop": "open", "rate": 4.0, "arrivals": {"on_s": 1.0, "off_s": 3.0},
           "prompt_len": {"dist": "lognormal", "median": 300, "sigma": 1.0,
                          "lo": 16, "hi": 1500},
           "max_new": {"dist": "choice", "values": [8, 64], "weights": [3, 1]},
           "shared_prefix": {"count": 2, "length": 128}, "block": 16}
    reqs = list(itertools.islice(traffic.requests(mix, 3, 1000), 64))
    assert reqs[-1].due_s == pytest.approx(16.0, rel=0.25)   # the mean rate
    assert all(r.due_s % 4.0 <= 1.0 + 1e-9 for r in reqs)    # only while on
    assert len({tuple(r.prompt[:128]) for r in reqs}) == 2
    assert sorted({r.max_new for r in reqs}) == [8, 64]
    assert sum(r.max_new == 8 for r in reqs) == 48


def test_trace_reduction_on_the_recorded_trace():
    """``recorded_trace.txt`` is the start of a real v5e trace of the
    serving engine (PR 26, ``trace_reduce.to_text_proto``)."""
    with open(os.path.join(HERE, "recorded_trace.txt")) as f:
        trace = trace_reduce.load(f.read(), text_proto=True)
    with open(os.path.join(HERE, "recorded_trace.expect.json")) as f:
        expect = json.load(f)
    dev = trace.devices[0]
    assert len(dev.ops) == expect["ops"]
    t0, t1 = trace_reduce.span_window(trace)
    busy = trace_reduce.busy_seconds(trace, t0, t1)
    assert busy == pytest.approx(expect["busy_s"], rel=1e-6)
    assert 0 < busy <= (t1 - t0) / 1e9
    # nested events are not counted twice: self times add up to the union
    total_self = sum(trace_reduce.op_self_seconds(dev).values())
    assert total_self == pytest.approx(
        sum(b - a for a, b in trace_reduce.busy_intervals(dev)) / 1e9,
        rel=1e-6)
    runs = trace_reduce.module_runs(trace)
    assert {k: len(v) for k, v in runs.items()} == expect["modules"]
    pallas, n = trace_reduce.op_seconds(dev, trace_reduce.is_pallas)
    assert n == expect["pallas_calls"]
    assert pallas == pytest.approx(expect["pallas_s"], rel=1e-6)
    bd = trace_reduce.breakdown(trace, t0, t1)
    assert len(bd["device_ops"]) <= 10 and bd["device_ops"][0][1] > 0
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(
        (t1 - t0) / 1e9 - busy, rel=1e-6)


def test_trace_reduction_by_hand():
    dev = trace_reduce.DeviceTrace("/device:TPU:0", modules=[
        ("jit_f(1)", 0.0, 100.0), ("jit_f(1)", 300.0, 100.0)], ops=[
        ("%while.1 = () while()", 0.0, 100.0),
        ("%k.1 = bf16[4,8]{1,0} custom-call(bf16[4,24]{1,0} %x), "
         'custom_call_target="tpu_custom_call"', 10.0, 30.0),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %y)", 50.0, 20.0),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %y)", 300.0, 100.0)])
    trace = trace_reduce.Trace([dev], spans=[("bench:step", 0.0, 250.0),
                                             ("bench:sleep", 250.0, 50.0)])
    assert trace_reduce.busy_seconds(trace) == pytest.approx(200e-9)
    self_s = trace_reduce.op_self_seconds(dev)
    assert self_s[dev.ops[0][0]] == pytest.approx(50e-9)
    gaps = trace_reduce.idle_gaps(trace, t0=0.0, t1=400.0)
    assert gaps == [("step", pytest.approx(200e-9))]
    name = dev.ops[1][0]
    assert trace_reduce.is_pallas(name)
    assert trace_reduce.result_shapes(name) == [("bf16", (4, 8))]
    assert trace_reduce.operand_shapes(name) == [("bf16", (4, 24))]
    assert trace_reduce.op_label(name) == "%k.1 pallas bf16[4,8]"
    inside = trace_reduce.ops_within(dev, "jit_f", trace_reduce.is_pallas)
    assert [len(x) for x in inside] == [1, 0]


def test_manifest_names_files_that_exist_and_names_that_pass():
    import re

    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    for cell in manifest["workloads"]:
        for key in ("name", "config", "traffic"):
            assert name.match(cell[key])
        assert len(cell["why"]) <= 200
        for sub, stem in (("configs", cell["config"]),
                          ("traffic", cell["traffic"]),
                          ("limits", cell["name"])):
            assert os.path.exists(os.path.join(
                root, "benchmark", sub, stem + ".json")), (sub, stem)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert name.match(metric["name"]) and unit.match(metric["unit"])
    for metric in manifest["per_layer"]:
        assert name.match(metric["layer"])
        assert metric["moves"] in e2e
        assert os.path.exists(os.path.join(
            root, "benchmark", "metrics", metric["name"] + ".py"))


# -- the window drivers, end to end at a tiny size on the CPU -----------------

def test_train_driver_runs_and_is_correct():
    import drive_train

    result = drive_train.run(_ctx("tiny-train", "tiny-steps", TRAIN_LIMITS))
    assert result.correct, result.checks
    assert result.end_to_end["train_tokens_per_s"] > 0
    assert result.counters["steps"] == result.attempted > 0


def test_four_chip_cell_is_data_only():
    """The (2, 2, 1) mesh of PERF.md's Open question (1): the same
    driver, another configuration file, four virtual devices."""
    import jax

    import drive_train

    if len(jax.devices()) < 4:
        pytest.skip("needs --xla_force_host_platform_device_count=4")
    result = drive_train.run(
        _ctx("tiny-train-dp2tp2", "tiny-steps", TRAIN_LIMITS))
    assert result.correct, result.checks
    assert result.counters["chips"] == 4
    assert result.counters["batch"] == 4       # 2 per chip x dp 2


@pytest.mark.parametrize("mix", ["tiny-open", "tiny-backlog"])
def test_serve_driver_runs_and_is_correct(mix):
    import drive_serve

    result = drive_serve.run(_ctx("tiny-serve", mix, SERVE_LIMITS))
    assert result.correct, result.checks
    assert result.failed == 0 and result.attempted > 0
    assert result.counters["served_tokens_compared"] > 0
    if mix == "tiny-open":
        assert result.end_to_end["tpot_p95_ms"] > 0
        assert len(result.counters["ttft_ms"]) == result.attempted
    else:
        assert result.end_to_end["serve_tokens_per_s"] > 0


# -- the comparison has been shown to fail ------------------------------------

def _train_readings(cast_name="exact", half_batch=False):
    import drive_train
    from apex_tpu.transformer import parallel_state

    ctx = _ctx("tiny-train", "tiny-steps", TRAIN_LIMITS)
    fs, make_weights, ref = drive_train.build(ctx)
    batches = drive_train.make_batches(ctx, fs, drive_train.CHECKED_STEPS)
    parallel_state.destroy_model_parallel()
    return ctx, drive_train, drive_train.reference_readings(
        ctx, ref, make_weights, batches, cast_name=cast_name,
        half_batch=half_batch)


def test_train_control_and_half_batch_come_out_not_correct():
    ctx, drive_train, want = _train_readings()
    for kw in (dict(cast_name="fp8"), dict(half_batch=True)):
        _, _, got = _train_readings(**kw)
        checks = drive_train.compare(got, want, TRAIN_LIMITS)
        assert not all(c.ok for c in checks), (kw, checks)


def _broken_step(monkeypatch, break_it):
    import drive_train

    real_build = drive_train.build

    def build(ctx):
        fs, make_weights, ref = real_build(ctx)
        return fs._replace(step=break_it(fs.step)), make_weights, ref

    monkeypatch.setattr(drive_train, "build", build)
    return drive_train


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    def break_it(step):
        def unchanged(params, opt_state, tokens, labels):
            import jax
            import jax.numpy as jnp
            copy = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)
            # the real step donates what it is given
            *_, loss = step(copy(params), copy(opt_state), tokens, labels)
            return params, opt_state, loss
        return unchanged

    drive_train = _broken_step(monkeypatch, break_it)
    result = drive_train.run(_ctx("tiny-train", "tiny-steps", TRAIN_LIMITS))
    assert not result.correct
    failed = {c.name for c in result.checks if not c.ok}
    assert "delta_norm_gap" in failed


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    def break_it(step):
        def half(params, opt_state, tokens, labels):
            n = tokens.shape[0] // 2
            import jax.numpy as jnp
            # the mean taken over the rest: the first half fed twice
            return step(params, opt_state,
                        jnp.concatenate([tokens[:n], tokens[:n]]),
                        jnp.concatenate([labels[:n], labels[:n]]))
        return half

    drive_train = _broken_step(monkeypatch, break_it)
    result = drive_train.run(_ctx("tiny-train", "tiny-steps", TRAIN_LIMITS))
    assert not result.correct


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    import drive_serve

    real_build = drive_serve.build

    def build(ctx):
        eng, make_weights, ref, scfg = real_build(ctx)
        decode = eng._decode_fn

        def altered(*args):
            tok, *pools = decode(*args)
            return ((tok + 1) % scfg.vocab_size, *pools)

        eng._decode_fn = altered
        return eng, make_weights, ref, scfg

    monkeypatch.setattr(drive_serve, "build", build)
    result = drive_serve.run(_ctx("tiny-serve", "tiny-backlog", SERVE_LIMITS))
    assert not result.correct
    assert [c.name for c in result.checks if not c.ok] == ["served_logit_gap"]


def test_serve_control_comes_out_not_correct():
    import drive_serve

    ctx = _ctx("tiny-serve", "tiny-backlog", SERVE_LIMITS)
    eng, make_weights, ref, _ = drive_serve.build(ctx)
    eng.warmup()
    offered, *_ = drive_serve.window(ctx, eng, harness.Tracer(False), 1.0)
    sample = drive_serve.sample_finished(offered, ctx.seed)
    gap, low, compared = drive_serve.widest_gap(
        ctx, ref, make_weights(), sample, cast_name="fp8")
    assert compared > 0
    assert gap <= SERVE_LIMITS["served_logit_gap"] < low


def test_run_py_refuses_a_cpu():
    import run as run_py

    with pytest.raises(SystemExit) as err:
        run_py.require_chip(1, harness.load_json("peaks.json"))
    assert err.value.code == 2

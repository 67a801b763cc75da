"""The readers of device time by the program's named scopes
(``metrics/scope_time.py`` and the six metrics over it) on the recorded
trace with a small recorded scope map beside it
(``recorded_trace.scopes.json``: ``telemetry.scopes.dump``'s format, two
variants of ``jit__decode``, the first another executable's), against a
program without a registry, and ``latent_blocks_fetched_share`` on a
hand-made ring."""

import json
import os
import re
import sys
import types

import pytest

import harness
import run as run_py
import trace_reduce
from metrics import scope_time

HERE = os.path.dirname(os.path.abspath(__file__))
GROUPS = (("attn", frozenset({"attn_full"})),
          ("mlp", frozenset({"mlp"})),
          ("head", frozenset({"head"})))


def recorded(config=None):
    with open(os.path.join(HERE, "recorded_trace.txt")) as f:
        trace = trace_reduce.load(f.read(), text_proto=True)
    result = harness.Result(
        attempted=1, failed=0, end_to_end={}, window_start=0.0,
        window_s=1.0, memory_peak_bytes=0, checks=[], trace=trace,
        trace_window_ns=trace_reduce.span_window(trace))
    ctx = types.SimpleNamespace(
        config=config or {"executables": {"decode": "jit__decode",
                                          "step": "jit__decode"}})
    return result, ctx


def recorded_maps():
    from apex_tpu.telemetry import scopes

    return scopes.load(os.path.join(HERE, "recorded_trace.scopes.json"))


def self_ms(trace, pattern) -> float:
    """By hand: self time of the instructions whose head matches."""
    return 1e3 * sum(
        sec for name, sec in
        trace_reduce.op_self_seconds(trace.devices[0]).items()
        if re.search(pattern, trace_reduce.op_head(name)))


def test_sums_over_the_recorded_trace_are_the_hand_checked_ones():
    result, _ = recorded()
    maps = recorded_maps()["jit__decode"]
    got = scope_time.ms_per_run(result, "jit__decode", GROUPS, maps=maps)
    with open(os.path.join(HERE, "recorded_trace.expect.json")) as f:
        expect = json.load(f)
    # the 18 decode kernels (1.445 ms each: what trace_reduce calls
    # pallas_s) and the slices of the pool that fed them in PR 26
    # (0.966 ms a layer): 26.009 + 17.387
    kernels = self_ms(result.trace, r"^%_decode\.")
    assert kernels == pytest.approx(1e3 * expect["pallas_s"], rel=1e-9)
    assert got["attn"] == pytest.approx(
        kernels + self_ms(result.trace, r"^%slice_bitcast_fusion"), rel=1e-9)
    assert got["attn"] == pytest.approx(43.3965, abs=1e-3)
    assert got["mlp"] == pytest.approx(
        self_ms(result.trace, r"^%fusion"), rel=1e-9)
    assert got["mlp"] == pytest.approx(0.3423, abs=1e-3)
    assert got["head"] == pytest.approx(0.0022, abs=1e-3)
    assert got[scope_time.REST] == pytest.approx(1.6264, abs=1e-3)
    # nested events are counted once: the groups and the rest are the run
    assert sum(got.values()) == pytest.approx(1e3 * expect["busy_s"],
                                              rel=1e-9)


def test_the_variant_whose_instructions_the_run_has_is_picked():
    result, _ = recorded()
    maps = recorded_maps()["jit__decode"]
    assert [m.variant for m in maps] == ["1024", "2048"]
    (_, ops), = scope_time.runs_with_ops(result, "jit__decode")
    assert len(ops) == 900
    assert scope_time.pick(maps, ops).variant == "2048"
    assert scope_time.pick(maps[:1], ops).variant == "1024"
    # instructions the map does not hold (%reduce.N, %reshape.N) are "?"
    runs, paths = scope_time.seconds_by_path(result, "jit__decode", maps)
    assert runs == 1
    assert 1e3 * paths["?"] == pytest.approx(
        self_ms(result.trace, r"^%(reduce|reshape)"), rel=1e-9)
    assert scope_time.group_of("?", GROUPS) == scope_time.REST


def test_an_instruction_belongs_to_the_first_group_on_its_path():
    groups = scope_time.DSV2_DECODE
    assert scope_time.group_of("layer/attn_latent/mla_absorb",
                               groups) == "mla_proj"
    assert scope_time.group_of("layer/attn_latent/flash_decode_latent",
                               groups) == "attn_latent"
    assert scope_time.group_of("layer/mlp", groups) == "experts"
    assert scope_time.group_of("layer", groups) == scope_time.REST
    assert scope_time.group_of("", scope_time.TRAIN_STEP) == scope_time.REST
    assert scope_time.group_of("fwd_bwd/flash_qkv_bwd",
                               scope_time.TRAIN_STEP) == "fwd_bwd"


READERS = ["zero_update_ms_per_step", "zero_copy_ms_per_step",
           "train_unscoped_ms_per_step", "dsv2_decode_mla_proj_ms_per_step",
           "dsv2_decode_experts_ms_per_step", "dsv2_decode_other_ms_per_step"]


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_none_without_a_registry(monkeypatch, name):
    """The parent of the PR that added the registry: ``apex_tpu.telemetry``
    is there and has no ``scope_maps``."""
    monkeypatch.setitem(sys.modules, "apex_tpu.telemetry",
                        types.ModuleType("apex_tpu.telemetry"))
    scope_time._cache.clear()
    result, ctx = recorded()
    assert run_py.read_layer_metric({"name": name}, result, ctx) is None
    scope_time._cache.clear()


@pytest.mark.parametrize("name", READERS)
def test_a_reader_over_the_programs_registry(monkeypatch, name):
    """With the registry answering with the recorded map the six readers
    return their group's part of the recorded run (of the recorded
    scopes only ``mlp`` is in a family), and those of one executable add
    up to it."""
    import apex_tpu.telemetry as telemetry

    maps = recorded_maps()
    monkeypatch.setattr(telemetry, "scope_maps",
                        lambda names=None: {n: maps[n] for n in names
                                            if n in maps})
    scope_time._cache.clear()
    result, ctx = recorded()
    value = run_py.read_layer_metric({"name": name}, result, ctx)
    assert isinstance(value, float) and value >= 0.0
    family = READERS[:3] if name in READERS[:3] else READERS[3:]
    total = sum(run_py.read_layer_metric({"name": n}, result, ctx)
                for n in family)
    # a family's groups and what they leave over are the whole run
    assert total == pytest.approx(45.3675, abs=1e-3)
    scope_time._cache.clear()


def test_no_run_of_the_executable_reads_none():
    result, ctx = recorded({"executables": {"decode": "jit__chunk"}})
    assert scope_time.ms_per_run(
        result, "jit__chunk", GROUPS,
        maps=recorded_maps()["jit__decode"]) is None
    assert scope_time.read_group(
        result, types.SimpleNamespace(config={}), "decode", GROUPS,
        "attn") is None


def _ring_with(monkeypatch, spans):
    """A ring of one engine step a span, each an ``engine.decode``."""
    from apex_tpu.telemetry import PHASE_RING, PhaseRecord

    PHASE_RING.clear()
    t, ident = 1_000_000, 1
    for attrs in spans:
        step = ident
        PHASE_RING.record(PhaseRecord("engine.decode", ident + 1, step,
                                      step, t + 10, t + 90, attrs))
        PHASE_RING.record(PhaseRecord("engine.step", step, None, step,
                                      t, t + 100, None))
        t, ident = t + 1000, ident + 2
    return harness.Result(
        attempted=1, failed=0, end_to_end={}, window_start=0.0,
        window_s=1.0, memory_peak_bytes=0, checks=[])


def test_latent_blocks_fetched_share_over_a_hand_made_ring(monkeypatch):
    from apex_tpu.telemetry import PHASE_RING

    kept = PHASE_RING.snapshot()
    try:
        result = _ring_with(monkeypatch, [
            {"rows": 4, "rids": (), "latent_blocks_walked": 100,
             "latent_blocks_fetched": 40},
            {"rows": 4, "rids": (), "latent_blocks_walked": 300,
             "latent_blocks_fetched": 60},
            {"rows": 1, "rids": ()}])       # another block's span
        read = lambda: run_py.read_layer_metric(
            {"name": "latent_blocks_fetched_share"}, result, None)
        assert read() == pytest.approx(25.0)
        result = _ring_with(monkeypatch, [{"rows": 2, "rids": ()}])
        assert read() is None
        PHASE_RING.clear()
        assert read() is None
    finally:
        PHASE_RING.clear()
        for r in kept:
            PHASE_RING.record(r)

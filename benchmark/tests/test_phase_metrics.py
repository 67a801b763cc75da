"""The per-layer readers of the serving engine's step phases
(``metrics/phase_ring.py`` and the eight metrics of PR 27), each on a
ring filled by a tiny run on the CPU, on an empty ring, and against a
program that has no ring."""

import json
import os
import time

import pytest

import harness
import run as run_py

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = {
    "host_turn_ms_per_step": "tiny-open",
    "host_build_ms_per_step": "tiny-open",
    "prefill_stall_ms_per_step": "tiny-open",
    "itl_p99_ms": "tiny-open",
    "prefill_padded_share": "tiny-open",
    "backlog_host_turn_ms_per_step": "tiny-backlog",
    "backlog_prefill_stall_ms_per_step": "tiny-backlog",
    "backlog_host_cpu_ms_per_step": "tiny-backlog",
}


def _load(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_runs():
    """mix -> (Result, Context, the ring as the run left it).  The ring
    is process-wide, so each run's records are put back before its
    readers run."""
    import drive_serve
    from apex_tpu.telemetry import PHASE_RING

    out = {}
    for mix in sorted(set(READERS.values())):
        PHASE_RING.clear()
        ctx = harness.Context(
            workload={"name": "test"}, config=_load("tiny-serve"),
            mix=_load(mix), limits={"served_logit_gap": 1e-3}, peak=PEAK,
            seed=2 ** 31 + 27, seconds=1.5, trace=False,
            t_process=time.perf_counter())
        result = drive_serve.run(ctx)
        assert result.correct, result.checks
        out[mix] = (result, ctx, PHASE_RING.snapshot())
    return out


def _refill(records):
    from apex_tpu.telemetry import PHASE_RING

    PHASE_RING.clear()
    for r in records:
        PHASE_RING.record(r)


def _read(name, result, ctx):
    return run_py.read_layer_metric({"name": name}, result, ctx)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_ring_filled_by_a_tiny_run(tiny_runs, name):
    result, ctx, records = tiny_runs[READERS[name]]
    _refill(records)
    value = _read(name, result, ctx)
    assert isinstance(value, float) and value >= 0
    steps = [r for r in records if r.name == "engine.step"]
    prefills = [r for r in records if r.name == "engine.prefill"]
    step_ms = max((r.t_end_ns - r.t_start_ns) / 1e6 for r in steps)
    if name.endswith("host_turn_ms_per_step") \
            or name == "host_build_ms_per_step":
        assert value <= step_ms
    elif name == "prefill_padded_share":
        row = ctx.config["builder"]["prefill_budget"]
        in_window = result.counters["prompt_lens"]
        assert all(r.attrs["S"] == row for r in prefills)
        assert 0 < value < 100
        # every prompt of the window was one prefill row (no request is
        # preempted at this size); the drain's prefills lie outside
        assert value == pytest.approx(
            100.0 * (1 - sum(in_window) / (row * len(in_window))), abs=5)
    elif name == "itl_p99_ms":
        assert value <= result.window_s * 1e3
    elif name == "backlog_host_cpu_ms_per_step":
        assert value > 0


def test_token_times_count_the_tokens_the_driver_counted(tiny_runs):
    from metrics import phase_ring

    result, ctx, records = tiny_runs["tiny-open"]
    times = phase_ring.token_times(records)
    # drained: every request offered got its tokens, each at a time of
    # its own
    assert len(times) == result.attempted
    assert all(t == sorted(t) and len(set(t)) == len(t)
               for t in times.values())
    # the driver opens its window a moment after ``window_start``, so
    # the last step before the close may fall outside the cut
    t0, t1 = phase_ring.window_ns(result)
    in_window = sum(t0 <= t <= t1 for ts in times.values() for t in ts)
    rows = ctx.config["builder"]["max_batch"]
    assert result.counters["tokens_out"] - rows <= in_window \
        <= result.counters["tokens_out"]


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_returns_none_on_an_empty_ring_and_without_a_ring(
        tiny_runs, name, monkeypatch):
    import apex_tpu.telemetry

    result, ctx, _ = tiny_runs[READERS[name]]
    _refill([])
    assert _read(name, result, ctx) is None
    # the parent of PR 27 has no ring: the import fails, nothing raises
    monkeypatch.delattr(apex_tpu.telemetry, "PHASE_RING")
    assert _read(name, result, ctx) is None


def test_the_manifest_lists_each_reader_in_its_cell():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cells = {"tiny-open": "serve-gpt1p3b-steady",
             "tiny-backlog": "serve-gpt1p3b-backlog"}
    e2e = {"tiny-open": "tpot_p95_ms", "tiny-backlog": "serve_tokens_per_s"}
    for name, mix in READERS.items():
        entry = by_name[name]
        assert entry["workloads"] == [cells[mix]]
        assert entry["moves"] == e2e[mix]
        assert entry["layer"] == "serving_engine"
        assert entry["source"] == "program_counter"

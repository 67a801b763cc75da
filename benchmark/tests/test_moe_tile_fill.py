"""The reader of ``moe_tile_fill_share``: by hand on made-up ring
records, nothing from a program without the counter, and a value in
(0, 100] from the LFM2 cell's window (``drive_serve_lfm2_moe``) at a tiny
size on the CPU."""

import json
import os
import time
from types import SimpleNamespace

import pytest

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _read(result, ctx=None):
    import run as run_py

    return run_py.read_layer_metric({"name": "moe_tile_fill_share"}, result,
                                    ctx)


def _fill(decodes):
    """A ring of one engine step a decode span, inside a window of
    0.5-100 s."""
    from apex_tpu.telemetry import PHASE_RING
    from apex_tpu.telemetry.phases import PhaseRecord

    PHASE_RING.clear()
    for i, attrs in enumerate(decodes):
        t = (i + 1) * 10 ** 9
        PHASE_RING.record(PhaseRecord("engine.step", 2 * i, None, i, t,
                                      t + 100, {}))
        PHASE_RING.record(PhaseRecord("engine.decode", 2 * i + 1, 2 * i, i,
                                      t + 10, t + 90, attrs))
    return SimpleNamespace(window_start=0.5, window_s=99.5)


def test_the_share_by_hand():
    result = _fill([
        {"moe_pairs_held": 256, "moe_rows_tiled": 1024},   # 25%
        {"moe_pairs_held": 30, "moe_rows_tiled": 400},     # 7.5%
        {"rows": 0},                                       # no counters
    ])
    assert _read(result) == pytest.approx((25.0 + 7.5) / 2)


def test_a_program_without_the_counter_reads_nothing():
    result = _fill([{"moe_pairs_held": 256, "moe_load_max": 9}] * 3)
    assert _read(result) is None


def test_a_tiny_lfm2_run_reads_a_share_of_at_most_100():
    import drive_serve_lfm2_moe as d
    from apex_tpu.telemetry import PHASE_RING

    def load(name):
        with open(os.path.join(HERE, name + ".json")) as f:
            return json.load(f)

    PHASE_RING.clear()
    ctx = harness.Context(
        workload={"name": "test"}, config=load("tiny-serve-lfm2-moe"),
        mix=load("tiny-backlog"),
        limits={"served_logit_gap": 1e-3, "served_logit_gap_mean": 1e-4,
                "route_margin_min": 0.0, "route_left_out_share": 0.0},
        peak=PEAK, seed=2 ** 31 + 41, seconds=1.5, trace=False,
        t_process=time.perf_counter())
    result = d.run(ctx)
    assert result.correct, result.checks
    assert 0.0 < _read(result, ctx) <= 100.0

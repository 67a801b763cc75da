"""In-run performance attribution (ISSUE 9): ProfileSampler through the
telemetry bus, the profile/memory event schema, the overhead budget, the
train-loop wiring, and the BENCH regress CLI gate.

The sampler tests run on a SYNTHETIC tracer (a capture backend that
writes a fixed Chrome-trace fixture), so the classifier -> bus -> schema
-> summarize path is deterministic on CPU; one live jax.profiler capture
rides the slow tier like PR 4's trace-backed case.
"""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import telemetry as tele
from apex_tpu.telemetry.__main__ import main as tele_cli

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------- helpers


class SynthTracer:
    """Capture backend writing a fixed device-timeline fixture: a 100us
    all-reduce with 60us of concurrent fusion compute and a 10us dot at
    [70, 80) -> exposed collective = 30us = 0.03 ms."""

    EVENTS = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 100.0,
         "name": "all-reduce.1"},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 0.0, "dur": 60.0,
         "name": "fusion.2"},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 70.0, "dur": 10.0,
         "name": "dot.3"},
    ]

    def __init__(self, fail_on=()):
        self.starts = 0
        self.fail_on = set(fail_on)
        self._dir = None

    def start(self, logdir):
        self.starts += 1
        if "start" in self.fail_on:
            raise RuntimeError("injected start failure")
        self._dir = logdir

    def stop(self):
        if "stop" in self.fail_on:
            raise RuntimeError("injected stop failure")
        with gzip.open(os.path.join(self._dir, "d.trace.json.gz"),
                       "wt") as f:
            json.dump({"traceEvents": self.EVENTS}, f)


def _bus(tmp_path, run_id="prof"):
    mem = tele.MemorySink()
    path = str(tmp_path / f"{run_id}.jsonl")
    bus = tele.TelemetryBus(run_id, sinks=[tele.JsonlSink(path), mem])
    return bus, mem, path


EXPOSED_MS = 0.03  # the fixture's analytic answer


# ------------------------------------------------------- event schema


def test_profile_and_memory_events_validate_round_trip(tmp_path):
    """ISSUE 9 satellite: the new types are in the closed event set and
    their payloads round-trip through emit -> JSONL -> validator."""
    bus, mem, path = _bus(tmp_path)
    bus.emit("profile", step=3, window_steps=1,
             phase_ms={"matmul": 1.5, "collective": 0.4},
             exposed_collective_ms=0.2, collective_ms=0.4,
             total_device_ms=2.0, overhead_ms=12.0)
    bus.emit("memory", step=3, stats_available=True, n_devices=1,
             live_bytes=123, peak_bytes=456)
    bus.emit("memory", step=4, stats_available=False, n_devices=0)
    bus.close()
    assert tele.validate_jsonl(path) == 3
    assert [e["type"] for e in tele.load_jsonl(path)] == [
        "profile", "memory", "memory"]


def test_profile_schema_rejects_malformed():
    bus = tele.TelemetryBus("x", sinks=[])
    ev = bus.emit("profile", step=1, window_steps=1, phase_ms={},
                  exposed_collective_ms=0.0, collective_ms=0.0,
                  total_device_ms=0.0, overhead_ms=0.0)
    tele.validate_event(ev)
    bad = dict(ev)
    del bad["phase_ms"]
    with pytest.raises(tele.SchemaError, match="phase_ms"):
        tele.validate_event(bad)
    bad = dict(ev, exposed_collective_ms="lots")
    with pytest.raises(tele.SchemaError, match="exposed_collective_ms"):
        tele.validate_event(bad)


def test_memory_schema_bool_not_int_discipline():
    """stats_available must be a real bool — 1/0 sentinels are exactly
    what the validator's bool discipline exists to reject."""
    bus = tele.TelemetryBus("x", sinks=[])
    ev = bus.emit("memory", step=1, stats_available=True, n_devices=1)
    tele.validate_event(ev)
    with pytest.raises(tele.SchemaError, match="stats_available"):
        tele.validate_event(dict(ev, stats_available=1))
    # and n_devices is an int, not a smuggled bool
    with pytest.raises(tele.SchemaError, match="n_devices"):
        tele.validate_event(dict(ev, n_devices=True))


def test_device_memory_payload_shape():
    p = tele.device_memory_payload()
    assert isinstance(p["stats_available"], bool)
    assert isinstance(p["n_devices"], int)
    if not p["stats_available"]:
        assert "live_bytes" not in p and "peak_bytes" not in p
    else:  # pragma: no cover — backend-dependent
        assert p["peak_bytes"] >= 0


# ------------------------------------------------------- sampler core


def test_sampler_cadence_emits_at_every_with_window(tmp_path):
    bus, mem, path = _bus(tmp_path)
    tr = SynthTracer()
    s = tele.ProfileSampler(bus, every=5, window=2, tracer=tr,
                            max_overhead=1e9)  # budget off: cadence test
    for step in range(1, 13):
        s.on_step(step)
    bus.close()
    profs = [e for e in mem.events if e["type"] == "profile"]
    mems = [e for e in mem.events if e["type"] == "memory"]
    # windows start after steps 5 and 10, close 2 steps later
    assert [e["step"] for e in profs] == [7, 12]
    assert len(mems) == 2
    assert s.samples == 2 and tr.starts == 2
    for e in profs:
        assert e["window_steps"] == 2
        assert e["phase_ms"]["collective"] == pytest.approx(0.1)
        assert e["exposed_collective_ms"] == pytest.approx(EXPOSED_MS)
        assert e["overhead_ms"] > 0
    # the stream a sampler produces passes the validate CLI (acceptance)
    assert tele_cli(["validate", path]) == 0


def test_sampler_books_overhead_to_profile_bucket(tmp_path):
    bus, mem, _ = _bus(tmp_path)
    acct = bus.accountant(window=10)
    s = tele.ProfileSampler(bus, every=2, window=1, tracer=SynthTracer(),
                            accountant=acct, max_overhead=1e9)
    for step in range(1, 6):
        s.on_step(step)
    assert s.samples >= 1
    assert acct.buckets["profile"] == pytest.approx(s.overhead_s)
    end = acct.finish(step=5)
    assert end["buckets_s"]["profile"] > 0
    bus.close()


def test_sampler_budget_defers_and_bounds_overhead(tmp_path):
    """The ≤1% bound is enforced by construction: with a fake clock
    (100 ms steps, 30 ms captures) the sampler must defer captures
    whenever another one would push overhead past max_overhead of the
    wall — asserted deterministically, no real sleeps."""
    bus, mem, _ = _bus(tmp_path)
    clock = {"t": 0.0}
    tr = SynthTracer()
    real_start, real_stop = tr.start, tr.stop

    def start(d):
        clock["t"] += 0.015  # 15 ms to start a capture
        real_start(d)

    def stop():
        clock["t"] += 0.015  # 15 ms to stop + parse
        real_stop()

    tr.start, tr.stop = start, stop
    s = tele.ProfileSampler(bus, every=10, window=1, tracer=tr,
                            max_overhead=0.01)
    s._now = lambda: clock["t"]
    for step in range(1, 1001):
        clock["t"] += 0.1  # the step itself
        s.on_step(step)
    bus.close()
    assert s.samples >= 1, "budget must not starve the sampler forever"
    assert s.deferred > 0, "with 30ms captures every 10x100ms steps the" \
                           " budget must defer some slots"
    assert s.overhead_fraction() <= 0.01 + 1e-9, s.totals()
    # deferral happens instead of violation: every scheduled slot either
    # sampled or deferred
    assert s.samples + s.deferred == 1000 // 10


def test_sampler_failure_disables_after_max_and_never_raises(tmp_path):
    bus, mem, _ = _bus(tmp_path)
    s = tele.ProfileSampler(bus, every=1, window=1,
                            tracer=SynthTracer(fail_on={"stop"}),
                            max_overhead=1e9, max_failures=3)
    for step in range(1, 10):
        s.on_step(step)  # must not raise
    assert s.disabled and s.failures == 3
    assert "injected stop failure" in s.last_error
    assert not any(e["type"] == "profile" for e in mem.events)
    bus.close()


def test_sampler_capture_explicit_window(tmp_path):
    """The bench entry point: capture(run_window) returns the report
    and emits the profile/memory pair."""
    bus, mem, path = _bus(tmp_path)
    ran = {"n": 0}
    s = tele.ProfileSampler(bus, window=1, tracer=SynthTracer())
    rep = s.capture(lambda: ran.__setitem__("n", ran["n"] + 1), step=42)
    bus.close()
    assert ran["n"] == 1
    assert rep is not None
    assert rep.exposed_collective_ms == pytest.approx(EXPOSED_MS)
    profs = [e for e in mem.events if e["type"] == "profile"]
    assert len(profs) == 1 and profs[0]["step"] == 42
    assert tele_cli(["validate", path]) == 0


# -------------------------------------------------- loop + summarize


def test_loop_wires_sampler_and_summarize_renders_phases(tmp_path, capsys):
    """run_resilient_training(profile_sampler=...): profile/memory
    events ride the run's stream, overhead books to the profile
    bucket, the stream validates, and summarize renders the phase
    breakdown + exposed-collective next to the step percentiles."""
    from apex_tpu.transformer.testing import run_resilient_training

    bus, mem, path = _bus(tmp_path, "loop")
    sampler = tele.ProfileSampler(bus, every=3, window=1,
                                  tracer=SynthTracer(), max_overhead=1e9)

    @jax.jit
    def stepfn(s, b):
        return s + b

    result = run_resilient_training(
        lambda s, b: (stepfn(s, b), None), jnp.zeros(()),
        [jnp.ones(())] * 10, telemetry=bus, profile_sampler=sampler)
    bus.close()
    assert result.step == 10 and sampler.samples >= 2
    # the loop handed the sampler its accountant
    assert sampler._acct is bus._accountant
    assert tele.validate_jsonl(path) == len(mem.events)
    end = [e for e in mem.events if e["type"] == "run_end"][-1]
    assert end["buckets_s"].get("profile", 0) > 0

    s = tele.summarize_events(mem.events)
    assert s["profile_samples"] == sampler.samples
    assert s["phase_ms"]["collective"] == pytest.approx(0.1)
    assert s["exposed_collective_ms"] == pytest.approx(EXPOSED_MS)
    txt = tele.format_summary(s)
    assert "phases" in txt and "exposed coll" in txt

    # the CLI renders the same stream (and --json carries the fields)
    assert tele_cli(["summarize", path, "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["exposed_collective_ms"] == pytest.approx(EXPOSED_MS)


def test_diff_carries_phase_and_exposed_rows(tmp_path, capsys):
    bus_a, mem_a, path_a = _bus(tmp_path, "a")
    sa = tele.ProfileSampler(bus_a, every=1, window=1,
                             tracer=SynthTracer(), max_overhead=1e9)
    for i in range(1, 4):
        sa.on_step(i)
    bus_a.emit("step", step=4, step_ms=5.0)
    bus_a.close()
    bus_b, mem_b, path_b = _bus(tmp_path, "b")
    bus_b.emit("step", step=1, step_ms=6.0)
    bus_b.emit("profile", step=1, window_steps=1,
               phase_ms={"collective": 0.02, "matmul": 0.3},
               exposed_collective_ms=0.001, collective_ms=0.02,
               total_device_ms=0.4, overhead_ms=1.0)
    bus_b.close()
    assert tele_cli(["summarize", path_a, "--diff", path_b]) == 0
    out = capsys.readouterr().out
    assert "exposed (ms)" in out
    assert "ph:collective" in out and "ph:matmul" in out


# ------------------------------------------------------- regress gate


def test_regress_direction_rules():
    from apex_tpu.telemetry.regress import key_direction

    assert key_direction("gpt1p3b_tokens_per_sec") == "higher"
    assert key_direction("resnet50_mfu_vs_roof") == "higher"
    assert key_direction("gpt1p3b_goodput") == "higher"
    assert key_direction("bert_varlen_vs_padded_speedup") == "higher"
    assert key_direction("resnet50_step_ms_p95") == "lower"
    assert key_direction("serving_tpot_p50") == "lower"
    assert key_direction("gpt1p3b_exposed_collective_ms") == "lower"
    assert key_direction("gpt1p3b_hbm_peak_gb") == "lower"
    assert key_direction("resnet50_phase_collective_ms") == "lower"
    # serving overload keys (ISSUE 10): SLO attainment up, tail
    # latency down, shed rate REPORTED but never gated (its right
    # value depends on the offered load — a gate must not guess)
    assert key_direction("serving_deadline_hit_rate") == "higher"
    assert key_direction("serving_tpot_p99_overload") == "lower"
    assert key_direction("serving_shed_rate") is None
    # speculation (ISSUE 12): committed tokens per decode-step row up;
    # the SLO-reference echoes are config, not measurements
    assert key_direction("serving_accepted_tokens_per_step") == "higher"
    assert key_direction("serving_slo_ref_first_token") is None
    assert key_direction("serving_slo_ref_per_token") is None
    # config echoes and counters are NOT gated
    assert key_direction("gpt1p3b_batch") is None
    assert key_direction("bench_schema") is None


def test_regress_compare_and_exit_codes(tmp_path):
    a = tmp_path / "a.json"
    b_ok = tmp_path / "b_ok.json"
    b_bad = tmp_path / "b_bad.json"
    base = {"metric": "resnet50_amp_o2_fusedlamb_images_per_sec",
            "value": 2400.0,
            "extras": {"gpt1p3b_tokens_per_sec": 10000.0,
                       "gpt1p3b_step_ms_p95": 200.0,
                       "gpt1p3b_batch": 4}}
    a.write_text(json.dumps(base))
    ok = json.loads(a.read_text())
    ok["value"] = 2380.0                       # -0.8%: inside 5%
    ok["extras"]["gpt1p3b_tokens_per_sec"] = 10400.0
    ok["extras"]["gpt1p3b_step_ms_p95"] = 208.0
    ok["extras"]["gpt1p3b_batch"] = 8          # ungated: may move freely
    b_ok.write_text(json.dumps(ok))
    bad = json.loads(a.read_text())
    bad["extras"]["gpt1p3b_tokens_per_sec"] = 8000.0  # -20%
    b_bad.write_text(json.dumps(bad))

    assert tele_cli(["regress", str(a), str(b_ok),
                     "--max-regress", "5"]) == 0
    assert tele_cli(["regress", str(a), str(b_bad),
                     "--max-regress", "5"]) == 1
    # a tighter threshold turns the ok pair's +4% p95 into a failure
    assert tele_cli(["regress", str(a), str(b_ok),
                     "--max-regress", "1"]) == 1
    # --keys makes a named key mandatory: a vanished headline fails
    assert tele_cli(["regress", str(a), str(b_ok), "--max-regress", "50",
                     "--keys", "does_not_exist"]) == 1


def test_regress_lower_is_better_direction(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps({"gpt1p3b_exposed_collective_ms": 50.0}))
    b.write_text(json.dumps({"gpt1p3b_exposed_collective_ms": 80.0}))
    # +60% exposed communication = regression on a lower-is-better key
    assert tele_cli(["regress", str(a), str(b),
                     "--max-regress", "10"]) == 1
    # the other way around is an improvement
    assert tele_cli(["regress", str(b), str(a),
                     "--max-regress", "10"]) == 0


def test_regress_zero_baseline_is_not_a_blind_spot(tmp_path):
    """Review finding: a gated key moving OFF a 0.0 baseline is an
    unbounded move, not a 0% change — e.g. exposed collective going
    0 -> 50 ms must fail the gate at any threshold."""
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps({"gpt1p3b_exposed_collective_ms": 0.0,
                             "gpt1p3b_tokens_per_sec": 0.0}))
    b.write_text(json.dumps({"gpt1p3b_exposed_collective_ms": 50.0,
                             "gpt1p3b_tokens_per_sec": 100.0}))
    # exposed 0 -> 50 regresses (lower-better); tok/s 0 -> 100 improves
    assert tele_cli(["regress", str(a), str(b),
                     "--max-regress", "1000"]) == 1
    assert tele_cli(["regress", str(b), str(a),
                     "--max-regress", "50"]) == 1  # tok/s 100 -> 0: -100%
    # both-zero pairs are a clean 0% pass
    z = tmp_path / "z.json"
    z.write_text(json.dumps({"gpt1p3b_exposed_collective_ms": 0.0}))
    assert tele_cli(["regress", str(z), str(z), "--max-regress", "1"]) == 0


def test_capture_books_overhead_exactly_once_on_emit_failure(tmp_path):
    """Review finding: a failure AFTER the window ran must not book the
    capture wall twice (it would overstate sampler overhead and skew
    goodput)."""
    bus, mem, _ = _bus(tmp_path)
    acct = bus.accountant(window=10)
    clock = {"t": 0.0}
    s = tele.ProfileSampler(bus, window=1, tracer=SynthTracer(),
                            accountant=acct)
    s._now = lambda: clock["t"]

    def boom(step, report, overhead_s):
        raise RuntimeError("emit failed")

    s._emit = boom
    rep = s.capture(lambda: clock.__setitem__("t", clock["t"] + 2.0),
                    step=1)
    bus.close()
    assert rep is not None              # the report itself succeeded
    assert s.failures == 1              # ...but the emit failure counted
    assert s.overhead_s == pytest.approx(2.0)   # once, not twice
    assert acct.buckets["profile"] == pytest.approx(2.0)


def test_regress_self_test_on_committed_records(capsys):
    """ISSUE 9 satellite: the gate runs against two committed BENCH
    records (r5 and its same-round builder rerun — a genuinely clean
    pair) and compares a meaningful number of gated keys."""
    a = os.path.join(REPO, "BENCH_r05.json")
    b = os.path.join(REPO, "BENCH_r05b_builder.json")
    rc = tele_cli(["regress", a, b, "--max-regress", "25", "--json"])
    rec = json.loads(capsys.readouterr().out)
    assert rc == 0, rec["failures"]
    gated = [r for r in rec["rows"] if r["gated"]]
    assert len(gated) >= 20, "the committed records must gate the " \
                             "flagship throughput/latency keys"
    keys = {r["key"] for r in gated}
    assert "gpt350m_tokens_per_sec" in keys
    assert "resnet50_amp_o2_fusedlamb_images_per_sec" in keys


def test_regress_serving_keys_mandatory_on_committed_pair(capsys):
    """ISSUE 10 satellite: ``serving_deadline_hit_rate`` is MANDATORY
    (via --keys) over the committed serving BENCH pair — if a future
    change drops the overload segment's headline key, the gate fails
    instead of silently comparing nothing."""
    a = os.path.join(REPO, "BENCH_r10_serving.json")
    b = os.path.join(REPO, "BENCH_r10b_serving.json")
    rc = tele_cli(["regress", a, b, "--max-regress", "75", "--json",
                   "--keys", "serving_deadline_hit_rate,"
                             "serving_tpot_p99_overload,"
                             "serving_shed_rate"])
    rec = json.loads(capsys.readouterr().out)
    assert rc == 0, rec["failures"]
    by_key = {r["key"]: r for r in rec["rows"]}
    assert by_key["serving_deadline_hit_rate"]["direction"] == "higher"
    assert by_key["serving_tpot_p99_overload"]["direction"] == "lower"
    assert by_key["serving_shed_rate"]["gated"] is False
    # the committed records really carry non-degenerate overload data
    assert 0.0 < by_key["serving_deadline_hit_rate"]["a"] <= 1.0
    # ...and a vanished mandatory key is a failure, not a skip
    assert tele_cli(["regress", a, b, "--max-regress", "75",
                     "--keys", "serving_deadline_hit_rate,gone_key"]) == 1


def test_regress_speculation_keys_mandatory_on_committed_r12_pair(capsys):
    """ISSUE 12 satellite: the speculation headline keys are MANDATORY
    over the committed r12 pair (A = speculation off, B = draft–verify
    + chunked prefill on, judged against A's own SLO bar).  The gate
    proves the acceptance criterion on committed data: accepted tokens
    per step moved OFF the 1.0 baseline while TTFT did not regress."""
    a = os.path.join(REPO, "BENCH_r12_serving.json")
    b = os.path.join(REPO, "BENCH_r12b_serving.json")
    rc = tele_cli(["regress", a, b, "--max-regress", "25", "--json",
                   "--keys", "serving_accepted_tokens_per_step,"
                             "serving_ttft_p50,"
                             "serving_tpot_p99_overload,"
                             "serving_deadline_hit_rate,"
                             "serving_shed_rate"])
    rec = json.loads(capsys.readouterr().out)
    assert rc == 0, rec["failures"]
    by_key = {r["key"]: r for r in rec["rows"]}
    acc = by_key["serving_accepted_tokens_per_step"]
    assert acc["direction"] == "higher"
    assert acc["a"] == 1.0 and acc["b"] > 1.0     # the speculation claim
    ttft = by_key["serving_ttft_p50"]
    assert ttft["direction"] == "lower" and ttft["b"] <= ttft["a"]
    assert by_key["serving_shed_rate"]["gated"] is False
    # the cpu-toy honesty stamp (ISSUE 12 small fix): the committed
    # absolute numbers must be self-labelled as CLI fixtures, not the
    # serving perf trajectory
    for path in (a, b):
        with open(path) as f:
            rec = json.load(f)
        assert rec["serving_config"]["geometry"] == "cpu-toy", path
    # ...and a vanished mandatory key is a failure, not a skip
    assert tele_cli(["regress", a, b, "--max-regress", "25",
                     "--keys", "serving_accepted_tokens_per_step,"
                               "gone_key"]) == 1


def test_regress_bucketed_zero_keys_mandatory_on_committed_r15_pair(capsys):
    """ISSUE 15 satellite: the overlap-aware-ZeRO headline keys are
    MANDATORY over the committed r15 pair (A = the legacy serialized
    dp×tp step, B = the bucketed-overlap default; both cpu-toy
    self-stamped).  The gate proves the acceptance criteria on
    committed data: the flagship exposed-collective key exists and did
    not regress, the per-bucket collective wall is gated lower-is-
    better, and the loss-trajectory goldens are BITWISE equal across
    the A/B — bucketing restructured the collectives without moving
    the math."""
    a = os.path.join(REPO, "BENCH_r15_gpt.json")
    b = os.path.join(REPO, "BENCH_r15b_gpt.json")
    rc = tele_cli(["regress", a, b, "--max-regress", "25", "--json",
                   "--keys", "gpt1p3b_exposed_collective_ms,"
                             "gpt3d_bucket_collective_ms,"
                             "gpt3d_loss_first,"
                             "gpt3d_loss_final,"
                             "gpt3d_zero_allreduce_bytes"])
    rec = json.loads(capsys.readouterr().out)
    assert rc == 0, rec["failures"]
    by_key = {r["key"]: r for r in rec["rows"]}
    exp = by_key["gpt1p3b_exposed_collective_ms"]
    assert exp["direction"] == "lower" and exp["b"] <= exp["a"]
    assert by_key["gpt3d_bucket_collective_ms"]["direction"] == "lower"
    # the loss goldens are informational (no direction rule) but must
    # be BITWISE equal: the parity claim, in record form
    for k in ("gpt3d_loss_first", "gpt3d_loss_final"):
        row = by_key[k]
        assert row["gated"] is False
        assert row["a"] == row["b"], (k, row)
    # counters are reported-not-gated; assert the structural claim
    # directly on the committed records
    ka, kb = (json.load(open(p)) for p in (a, b))
    assert ka["gpt3d_bucket_count"] == 0 and kb["gpt3d_bucket_count"] > 1
    assert ka["gpt3d_zero_allreduce_count"] \
        > kb["gpt3d_zero_allreduce_count"]
    assert ka["gpt3d_zero_allreduce_bytes"] \
        > 10 * kb["gpt3d_zero_allreduce_bytes"]
    assert ka["gpt3d_zero_reduce_scatter_count"] == 1
    assert kb["gpt3d_zero_reduce_scatter_count"] \
        == kb["gpt3d_bucket_count"] == kb["gpt3d_zero_all_gather_count"]
    # cpu-toy honesty stamp (r12 discipline)
    for rec_ in (ka, kb):
        assert rec_["gpt3d_config"]["geometry"] == "cpu-toy"
    # ...and a vanished mandatory key is a failure, not a skip
    assert tele_cli(["regress", a, b, "--max-regress", "25",
                     "--keys", "gpt1p3b_exposed_collective_ms,"
                               "gone_key"]) == 1


def test_bucket_ms_direction_rule():
    """The *_bucket_*_ms family (ISSUE 15) is gated lower-is-better —
    by the explicit family rule, not only the generic _ms suffix."""
    from apex_tpu.telemetry.regress import key_direction

    assert key_direction("gpt3d_bucket_collective_ms") == "lower"
    assert key_direction("anything_bucket_rs_wall_ms") == "lower"
    # counters/echoes in the same family stay ungated
    assert key_direction("gpt3d_bucket_count") is None
    assert key_direction("gpt3d_bucket_bytes") is None


def test_regress_fleet_keys_mandatory_on_committed_r16_pair(capsys):
    """ISSUE 16 satellite: the fleet headline keys are MANDATORY over
    the committed r16 pair (A = 1 replica, B = 3 replicas; same offered
    load, virtual-time fleet clock, both cpu-toy self-stamped).  The
    gate proves the acceptance criteria on committed data: aggregate
    decode throughput scales with replicas, and the rolling restart's
    p99 TTFT holds near steady on the fleet while the single replica
    pays the stop-the-world cost."""
    a = os.path.join(REPO, "BENCH_r16_fleet.json")
    b = os.path.join(REPO, "BENCH_r16b_fleet.json")
    rc = tele_cli(["regress", a, b, "--max-regress", "25", "--json",
                   "--keys", "fleet_decode_tokens_per_sec,"
                             "fleet_ttft_p99_restart_ms,"
                             "fleet_ttft_p99_steady_ms,"
                             "fleet_dropped"])
    rec = json.loads(capsys.readouterr().out)
    assert rc == 0, rec["failures"]
    by_key = {r["key"]: r for r in rec["rows"]}
    tok = by_key["fleet_decode_tokens_per_sec"]
    assert tok["direction"] == "higher" and tok["b"] > tok["a"]
    p99 = by_key["fleet_ttft_p99_restart_ms"]
    assert p99["direction"] == "lower" and p99["b"] <= p99["a"]
    # a drop counter has no "better" direction — reported, never gated
    assert by_key["fleet_dropped"]["gated"] is False
    ka, kb = (json.load(open(p)) for p in (a, b))
    # zero silent drops and zero recompiles after warmup — on BOTH
    # committed records, the standing contracts in record form
    for rec_ in (ka, kb):
        assert rec_["fleet_dropped"] == 0
        assert rec_["fleet_recompiles_after_warmup"] == 0
        assert rec_["fleet_config"]["geometry"] == "cpu-toy"
    # rolling restart HOLDS SLO on the fleet: the restart-segment tail
    # stays within 25% of steady when peers serve through the downtime
    # windows...
    assert kb["fleet_ttft_p99_restart_ms"] \
        <= 1.25 * kb["fleet_ttft_p99_steady_ms"], (kb,)
    # ...while the fleet-of-one control pays the full stop-the-world
    # cost for the same operation (the contrast that makes the fleet
    # tier worth its complexity)
    assert ka["fleet_ttft_p99_restart_ms"] \
        > 1.25 * ka["fleet_ttft_p99_steady_ms"], (ka,)
    # the restart arc really ran: every replica fenced once, and on
    # the fleet the live requests moved to peers
    assert ka["fleet_fences"] == 1 and kb["fleet_fences"] == 3
    assert kb["fleet_migrations"] > 0
    # ...and a vanished mandatory key is a failure, not a skip
    assert tele_cli(["regress", a, b, "--max-regress", "25",
                     "--keys", "fleet_decode_tokens_per_sec,"
                               "gone_key"]) == 1


def test_fleet_key_direction_rules():
    """The fleet key families (ISSUE 16) are gated by the explicit
    family rules — TTFT tails lower-is-better, aggregate throughput
    higher — while the operational counters stay ungated (a migration
    or fence count has no universally better direction)."""
    from apex_tpu.telemetry.regress import key_direction

    assert key_direction("fleet_ttft_p99_restart_ms") == "lower"
    assert key_direction("fleet_ttft_p99_steady_ms") == "lower"
    assert key_direction("fleet_decode_tokens_per_sec") == "higher"
    assert key_direction("fleet_migrations") is None
    assert key_direction("fleet_fences") is None
    assert key_direction("fleet_dropped") is None
    assert key_direction("fleet_restart_wall_s") is None


def test_pool_peak_direction_rule():
    """r17: the pool-occupancy high-water mark is gated lower-is-better
    by the explicit *_pool_peak$ rule (no generic suffix covers a
    fraction) — the quantized-KV headline's direction, pinned by name
    from the regress.py comment."""
    from apex_tpu.telemetry.regress import key_direction

    assert key_direction("serving_pool_peak") == "lower"
    assert key_direction("fleet_pool_peak") == "lower"
    # neighbors in the same family stay ungated: a shared-page count or
    # a pool size has no universally better direction
    assert key_direction("serving_shared_pages_peak") is None
    assert key_direction("serving_pool_pages") is None


def test_prefix_hit_rate_direction_rule():
    """r17: prefix-sharing hit rate is gated higher-is-better — by the
    explicit family rule (documented-redundant with _hit_rate$), while
    shed rate stays deliberately direction-free."""
    from apex_tpu.telemetry.regress import key_direction

    assert key_direction("serving_prefix_hit_rate") == "higher"
    assert key_direction("serving_deadline_hit_rate") == "higher"
    assert key_direction("serving_shed_rate") is None


def test_regress_serving_keys_mandatory_on_committed_r17_pair(capsys):
    """r17 satellite: the serving-mode headline keys are MANDATORY over
    the committed r17 pair (A = tp=1 full-precision unshared, B = tp=2
    + int8 pool + prefix sharing; same offered load, virtual-flops
    timebase, both cpu-toy self-stamped).  The gate proves the
    acceptance criteria on committed data: decode throughput scales
    with tp, the byte-matched int8 pool cuts the occupancy peak by at
    least the claimed 40%, and the shared-prompt trace actually hits
    the prefix index."""
    a = os.path.join(REPO, "BENCH_r17_serving.json")
    b = os.path.join(REPO, "BENCH_r17b_serving.json")
    rc = tele_cli(["regress", a, b, "--max-regress", "25", "--json",
                   "--keys", "decode_tokens_per_sec,"
                             "serving_pool_peak,"
                             "serving_prefix_hit_rate"])
    rec = json.loads(capsys.readouterr().out)
    assert rc == 0, rec["failures"]
    by_key = {r["key"]: r for r in rec["rows"]}
    tok = by_key["decode_tokens_per_sec"]
    assert tok["direction"] == "higher" and tok["b"] > tok["a"]
    peak = by_key["serving_pool_peak"]
    assert peak["direction"] == "lower"
    assert peak["b"] <= 0.6 * peak["a"]        # the >= 40% claim
    hit = by_key["serving_prefix_hit_rate"]
    assert hit["direction"] == "higher"
    assert hit["a"] == 0.0 and hit["b"] > 0.0  # sharing off vs hitting
    ka, kb = (json.load(open(p)) for p in (a, b))
    for rec_ in (ka, kb):
        # geometry + timebase provenance on BOTH records: emulated CPU
        # devices share one socket, so the tp speedup is only honest
        # under the virtual-flops timebase the records self-declare
        assert rec_["serving_config"]["geometry"] == "cpu-toy"
        assert rec_["serving_config"]["timebase"] == "virtual-flops"
    assert ka["serving_config"]["tp"] == 1 and ka["serving_config"][
        "kv_quant"] is None
    assert kb["serving_config"]["tp"] == 2 and kb["serving_config"][
        "kv_quant"] == "int8"
    assert kb["serving_config"]["prefix_sharing"] is not None
    # the B side really shared pages, not just counted hits
    assert kb["serving_shared_pages_peak"] > 0
    # ...and a vanished mandatory key is a failure, not a skip
    assert tele_cli(["regress", a, b, "--max-regress", "25",
                     "--keys", "decode_tokens_per_sec,"
                               "gone_key"]) == 1


def test_regress_disagg_keys_mandatory_on_committed_r18_pair(capsys):
    """r18 satellite: the disagg headline keys are MANDATORY over the
    committed r18 pair (A = 4 colocated replicas, B = the same four
    split 2 prefill + 2 decode behind the transport seam; same offered
    load, single decode wave per segment so the comparison gates the
    SHIPPING overhead rather than halved decode slots, both cpu-toy
    self-stamped).  The gate proves the acceptance criteria on
    committed data: every request's KV pages shipped (no local-prefill
    fallback, ``fleet_ship_fallback_rate`` gated lower-is-better at
    0.0), aggregate decode throughput holds within the regress budget,
    and both arrangements drop nothing and never recompile after
    warmup — including through the rolling restart both records
    carry."""
    a = os.path.join(REPO, "BENCH_r18_fleet.json")
    b = os.path.join(REPO, "BENCH_r18b_fleet.json")
    rc = tele_cli(["regress", a, b, "--max-regress", "25", "--json",
                   "--keys", "fleet_decode_tokens_per_sec,"
                             "fleet_ship_fallback_rate,"
                             "fleet_kv_ships,"
                             "fleet_dropped"])
    rec = json.loads(capsys.readouterr().out)
    assert rc == 0, rec["failures"]
    by_key = {r["key"]: r for r in rec["rows"]}
    assert by_key["fleet_decode_tokens_per_sec"]["direction"] == "higher"
    fall = by_key["fleet_ship_fallback_rate"]
    assert fall["direction"] == "lower"
    assert fall["a"] == 0.0 and fall["b"] == 0.0
    # a shipment counter has no "better" direction — reported, not gated
    assert by_key["fleet_kv_ships"]["gated"] is False
    ka, kb = (json.load(open(p)) for p in (a, b))
    # the A side is the colocated control: nothing ships, the keys
    # still exist (the --keys list must hold on BOTH sides)
    assert ka["fleet_config"]["mode"] == "colocated"
    assert ka["fleet_kv_ships"] == 0
    # the B side shipped EVERY request exactly once — zero fallbacks
    # AND zero double-ships (idempotency in record form)
    assert kb["fleet_config"]["mode"] == "disagg"
    assert kb["fleet_config"]["prefill_replicas"] == 2
    assert kb["fleet_kv_ships"] == kb["fleet_requests"]
    assert kb["fleet_ship_fallback_rate"] == 0.0
    for rec_ in (ka, kb):
        assert rec_["fleet_dropped"] == 0
        assert rec_["fleet_recompiles_after_warmup"] == 0
        assert rec_["fleet_config"]["geometry"] == "cpu-toy"
    # ...and a vanished mandatory key is a failure, not a skip
    assert tele_cli(["regress", a, b, "--max-regress", "25",
                     "--keys", "fleet_ship_fallback_rate,"
                               "gone_key"]) == 1


def test_multichip_records_are_geometry_stamped(tmp_path):
    """ISSUE 15 satellite (the ROADMAP maintenance note's last gap):
    every committed MULTICHIP_r*.json self-declares its geometry, and
    the loader refuses an unstamped record."""
    import glob

    from apex_tpu.telemetry import load_multichip_record

    paths = sorted(glob.glob(os.path.join(REPO, "MULTICHIP_r*.json")))
    assert len(paths) >= 8  # r06..r08 + r15..r19
    for p in paths:
        rec = load_multichip_record(p)
        assert rec["geometry"], p
    # the r15 record is the consolidated-leg run, on the emulated mesh
    r15 = load_multichip_record(os.path.join(REPO, "MULTICHIP_r15.json"))
    assert r15["ok"] is True and r15["geometry"] == "cpu-toy"
    assert "legs=[gpt_3d, chaos_mesh, chaos_data, chaos_serving]" \
        in r15["tail"]
    # the r16 record adds the serving-fleet migration leg (ISSUE 16)
    r16 = load_multichip_record(os.path.join(REPO, "MULTICHIP_r16.json"))
    assert r16["ok"] is True and r16["geometry"] == "cpu-toy"
    assert "dryrun leg chaos_fleet OK" in r16["tail"]
    assert "streams=bitwise drops=0" in r16["tail"]
    # refusal controls: unstamped record, non-record file
    p = tmp_path / "unstamped.json"
    p.write_text(json.dumps({"n_devices": 8, "rc": 0, "ok": True,
                             "tail": ""}))
    with pytest.raises(ValueError, match="geometry provenance"):
        load_multichip_record(str(p))
    q = tmp_path / "notarecord.json"
    q.write_text(json.dumps({"hello": 1}))
    with pytest.raises(ValueError, match="not a MULTICHIP"):
        load_multichip_record(str(q))


def test_regress_refuses_unparsed_driver_capture(capsys):
    """The r4 record's parsed:null capture must exit 2 (usage error),
    never green — a gate comparing nothing is no gate."""
    a = os.path.join(REPO, "BENCH_r04.json")
    b = os.path.join(REPO, "BENCH_r05.json")
    assert tele_cli(["regress", a, b, "--max-regress", "10"]) == 2
    assert "parsed=None" in capsys.readouterr().err


# --------------------------------------------- live capture (slow tier)


@pytest.mark.slow
def test_live_capture_end_to_end_with_collectives(tmp_path):
    """One REAL jax.profiler capture (like PR 4's trace-backed case):
    a shard_map psum program over the emulated 8-device mesh under the
    sampler.  CPU traces may lack device lanes or collective rows, so
    the hard asserts are structural (report exists, stream validates);
    when collective rows DO appear, exposed <= total collective wall."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs the emulated multi-device mesh")
    mesh = Mesh(devs, ("data",))

    @jax.jit
    def stepfn(x):
        def f(x):
            y = jnp.tanh(x @ x.T)
            return jax.lax.psum(y, "data")

        return shard_map(f, mesh=mesh, in_specs=P("data"),
                         out_specs=P())(x)

    x = jnp.ones((len(devs) * 16, 64), jnp.float32)
    stepfn(x).block_until_ready()

    bus, mem, path = _bus(tmp_path, "live")
    s = tele.ProfileSampler(bus, window=1)
    rep = s.capture(
        lambda: float(jnp.sum(stepfn(x))), step=1)
    bus.close()
    if rep is None:
        pytest.skip(f"profiler capture unavailable: {s.last_error}")
    assert tele.validate_jsonl(path) == len(mem.events)
    profs = [e for e in mem.events if e["type"] == "profile"]
    assert len(profs) == 1
    assert rep.total_ms >= 0
    if rep.collective_ms > 0:
        assert 0 <= rep.exposed_collective_ms <= rep.collective_ms + 1e-6

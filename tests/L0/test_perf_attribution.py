"""In-run performance attribution (ISSUE 9): ProfileSampler through the
telemetry bus, the profile/memory event schema, the overhead budget and
the train-loop wiring.

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

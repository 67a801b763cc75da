"""Telemetry subsystem tests (ISSUE 4): bus/sinks/schema, goodput
accounting, flight-recorder postmortems on the SIGTERM grace path and
chaos device loss, guard/watchdog/timers integration, and the ≤1%
overhead bound.

Every event any test emits is run through the schema validator
(:func:`apex_tpu.telemetry.validate_event`) — the stream contract IS
the feature; an event a tool can't parse is a print with extra steps.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu import checkpoint as ckpt
from apex_tpu import resilience as res
from apex_tpu import telemetry as tele
from apex_tpu.optimizers import FusedAdam
from apex_tpu.resilience import chaos
from apex_tpu.transformer.testing import run_resilient_training


# ---------------------------------------------------------------- helpers


def _bus(tmp_path, run_id="t", **kw):
    """A bus with both a JSONL sink (the file contract) and a memory
    sink (easy assertions)."""
    mem = tele.MemorySink()
    bus = tele.TelemetryBus(
        run_id, sinks=[tele.JsonlSink(str(tmp_path / f"{run_id}.jsonl")),
                       mem], **kw)
    return bus, mem, str(tmp_path / f"{run_id}.jsonl")


def _toy_state():
    k = jax.random.PRNGKey(0)
    params = {"dense": {"w": jax.random.normal(k, (4, 4), jnp.float32),
                        "b": jnp.zeros((4,), jnp.float32)}}
    opt = FusedAdam(lr=1e-2)
    scaler = amp.initialize("O2").scaler
    state = ckpt.TrainState.create(params, opt.init(params), scaler.init())
    return state, opt, scaler


def _make_step_fn(opt, scaler):
    @jax.jit
    def train_step(state, xy):
        x, y = xy

        def loss(p):
            pred = x @ p["dense"]["w"] + p["dense"]["b"]
            return scaler.scale(jnp.mean((pred - y) ** 2),
                                state.scaler_state)

        grads = jax.grad(loss)(state.params)
        grads, finite = scaler.unscale(grads, state.scaler_state)
        new_p, new_o = opt.step_if_finite(grads, state.opt_state,
                                          state.params, finite)
        return state.replace(
            step=state.step + 1, params=new_p, opt_state=new_o,
            scaler_state=scaler.update(state.scaler_state, finite)), finite

    return lambda s, b: train_step(s, b)


def _batches(n, key=jax.random.PRNGKey(3)):
    out = []
    for i in range(n):
        k = jax.random.fold_in(key, i)
        out.append((jax.random.normal(k, (8, 4), jnp.float32),
                    jax.random.normal(jax.random.fold_in(k, 1), (8, 4),
                                      jnp.float32)))
    return out


def _postmortems(d):
    return sorted(str(p) for p in os.listdir(d)
                  if str(p).startswith("postmortem_"))


# ------------------------------------------------------------- bus core


def test_bus_stamps_counts_and_validates(tmp_path):
    bus, mem, path = _bus(tmp_path)
    bus.emit("run_start", step=0, config={"x": 1})
    bus.emit("step", step=1, step_ms=12.5)
    bus.emit("ckpt_save", step=1, blocking=False, wall_ms=3.0)
    bus.close()
    assert bus.counts == {"run_start": 1, "step": 1, "ckpt_save": 1}
    for ev in mem.events:
        tele.validate_event(ev)
        assert ev["run_id"] == "t"
        assert isinstance(ev["t"], float) and isinstance(ev["mesh"], dict)
    # the JSONL sink wrote the identical stream
    assert tele.validate_jsonl(path) == 3
    assert [e["type"] for e in tele.load_jsonl(path)] == [
        "run_start", "step", "ckpt_save"]


def test_bus_rejects_unknown_event_type(tmp_path):
    bus, _, _ = _bus(tmp_path)
    with pytest.raises(tele.TelemetryError, match="unknown event type"):
        bus.emit("not_a_type", step=0)
    bus.close()


def test_schema_validator_rejects_malformed_events():
    ok = {"type": "step", "run_id": "r", "step": 1, "t": 0.1, "ts": 1.0,
          "mesh": {}, "step_ms": 2.0}
    tele.validate_event(ok)
    with pytest.raises(tele.SchemaError, match="missing stamp"):
        tele.validate_event({k: v for k, v in ok.items() if k != "run_id"})
    with pytest.raises(tele.SchemaError, match="unknown event type"):
        tele.validate_event(dict(ok, type="mystery"))
    with pytest.raises(tele.SchemaError, match="missing required field"):
        tele.validate_event({k: v for k, v in ok.items()
                             if k != "step_ms"})
    with pytest.raises(tele.SchemaError, match="step_ms"):
        tele.validate_event(dict(ok, step_ms="fast"))
    # bool must not satisfy an int-typed field
    skip = {"type": "skip", "run_id": "r", "step": 1, "t": 0.1, "ts": 1.0,
            "mesh": {}, "consecutive": True, "total_skipped": 0}
    with pytest.raises(tele.SchemaError, match="got bool"):
        tele.validate_event(skip)


def test_r17_prefix_fields_pin_bool_vs_int():
    """r17 satellite: ``request_admit.prefix_hit`` is a REAL bool (an
    int hit-COUNT would silently satisfy a sloppier spec and break the
    summarize denominator), ``decode_step.pool_shared_pages`` is a
    REAL int count (a bool would cap the gauge at 1) — and both are
    optional, so pre-r17 event streams still validate."""
    stamp = {"run_id": "r", "step": None, "t": 0.1, "ts": 1.0, "mesh": {}}
    admit = dict(stamp, type="request_admit", rid=0, context_tokens=9,
                 pages=2, preemptions=0)
    tele.validate_event(admit)                          # absent: sharing off
    tele.validate_event(dict(admit, prefix_hit=True))
    tele.validate_event(dict(admit, prefix_hit=False))  # misses emit too
    with pytest.raises(tele.SchemaError, match="prefix_hit must be bool"):
        tele.validate_event(dict(admit, prefix_hit=1))
    step = dict(stamp, type="decode_step", batch=1, new_tokens=1,
                pool_used=3, pool_pages=63)
    tele.validate_event(step)                           # absent: sharing off
    tele.validate_event(dict(step, pool_shared_pages=0))
    tele.validate_event(dict(step, pool_shared_pages=24))
    with pytest.raises(tele.SchemaError, match="got bool"):
        tele.validate_event(dict(step, pool_shared_pages=True))


def test_emit_survives_sink_failure():
    """Observability must never kill the run it observes: a sink whose
    write raises (ENOSPC, broken pipe) is dropped, the event still
    reaches the other sinks and the recorder, and emit returns."""
    class ExplodingSink:
        def write(self, ev):
            raise OSError("disk full")

        def close(self):
            pass

    mem = tele.MemorySink()
    bus = tele.TelemetryBus("boom", sinks=[ExplodingSink(), mem])
    ev = bus.emit("step", step=1, step_ms=1.0)  # must not raise
    assert ev["type"] == "step"
    assert len(bus.sinks) == 1  # the dead sink was dropped
    bus.emit("step", step=2, step_ms=1.0)
    assert [e["step"] for e in mem.events] == [1, 2]
    assert len(bus.recorder) == 2
    bus.close()


def test_flight_recorder_ring_keeps_last_n():
    rec = tele.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record({"i": i})
    assert len(rec) == 8
    assert [e["i"] for e in rec.snapshot()] == list(range(12, 20))
    with pytest.raises(ValueError):
        tele.FlightRecorder(capacity=0)


# ------------------------------------------------------- accounting


def test_accountant_batches_scalars_one_fetch_per_window(tmp_path,
                                                         monkeypatch):
    """The no-extra-device-syncs contract: scalars ride as references
    and are fetched in ONE device_get per `window` steps."""
    bus, mem, _ = _bus(tmp_path)
    acct = bus.accountant(window=5)
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    loss = jnp.asarray(1.5)
    for i in range(1, 11):
        acct.step_done(i, step_s=0.01,
                       scalars={"loss": loss, "scale": jnp.asarray(2.0)})
    assert calls["n"] == 2  # 10 steps / window 5 — one batched fetch each
    steps = [e for e in mem.events if e["type"] == "step"]
    assert [e["step"] for e in steps if "scalars" in e] == [5, 10]
    assert steps[4]["scalars"] == {"loss": 1.5, "scale": 2.0}
    bus.close()


def test_accountant_goodput_buckets_and_run_end(tmp_path):
    bus, mem, _ = _bus(tmp_path)
    acct = bus.accountant(window=4)
    for i in range(1, 5):
        acct.step_done(i, step_s=0.05, data_wait_s=0.01,
                       skipped=(i == 4))
    acct.pause(0.2, "restore")
    with pytest.raises(ValueError, match="unknown pause kind"):
        acct.pause(0.1, "coffee")
    end = acct.finish(step=4, reason="completed")
    tele.validate_event(end)
    assert end["steps"] == 4 and end["skips"] == 1
    b = end["buckets_s"]
    # 3 productive steps of 50ms; the skipped one booked separately
    assert abs(b["step"] - 0.15) < 1e-6
    assert abs(b["skipped"] - 0.05) < 1e-6
    assert abs(b["restore"] - 0.2) < 1e-6
    # synthetic durations exceed the real wall here -> the clamp holds
    assert 0 < end["goodput"] <= 1
    bus.close()


def test_accountant_books_compile_wall_out_of_goodput(tmp_path):
    """Compile wall measured inside a step (first step, mid-run
    reshape) must land in the `compile` bucket, not inflate productive
    step time — a change that doubles compile cost must show up as
    LOWER goodput, never unchanged."""
    bus, mem, _ = _bus(tmp_path, "comp")
    acct = bus.accountant(window=10)
    acct.step_done(1, step_s=7.0, compile_s=6.5)  # compile-laden step 1
    acct.step_done(2, step_s=0.5)
    end = acct.finish(step=2)
    b = end["buckets_s"]
    assert abs(b["compile"] - 6.5) < 1e-6
    assert abs(b["step"] - 1.0) < 1e-6  # 0.5 + (7.0 - 6.5)
    ev1 = [e for e in mem.events if e["type"] == "step"][0]
    # the event keeps the operator-visible full wall AND the split
    assert ev1["step_ms"] == 7000.0 and ev1["compile_ms"] == 6500.0
    bus.close()


def test_loop_books_real_compile_to_compile_bucket(tmp_path):
    """run_resilient_training wires the recompile listener: the first
    step's actual XLA compile lands in the compile bucket and as
    recompile events, and goodput reflects post-compile productivity."""
    bus, mem, _ = _bus(tmp_path, "jitcomp")

    @jax.jit
    def fresh_step(state, b):
        # constants make this a never-before-compiled program
        return {"w": state["w"] * 0.917364 + 0.111213}, None

    run_resilient_training(fresh_step, {"w": jnp.ones((64,))}, [None] * 4,
                           telemetry=bus)
    bus.close()
    assert any(e["type"] == "recompile" for e in mem.events)
    end = [e for e in mem.events if e["type"] == "run_end"][-1]
    assert end["buckets_s"].get("compile", 0) > 0
    step1 = [e for e in mem.events if e["type"] == "step"][0]
    assert step1.get("compile_ms", 0) > 0


def test_summarize_tolerates_torn_trailing_line(tmp_path):
    """An OOM-killed run can leave a partial last line; `summarize`
    must render the stream anyway (`validate` stays strict)."""
    from apex_tpu.telemetry.__main__ import main

    path = tmp_path / "torn.jsonl"
    _write_stream(path, "torn", n=6)
    with open(path, "a") as f:
        f.write('{"type": "step", "run_id": "torn", "st')  # torn write
    s = tele.summarize_file(str(path))
    assert s["steps"] == 6 and s["run_id"] == "torn"
    assert main(["summarize", str(path)]) == 0
    assert main(["validate", str(path)]) == 1  # strict path still flags
    with pytest.raises(tele.SchemaError):
        tele.load_jsonl(str(path))


def test_accountant_goodput_against_real_wall(tmp_path):
    """With real elapsed time dominating, goodput is productive-step
    seconds over wall — pauses and idle drag it down."""
    bus, _, _ = _bus(tmp_path, "wall")
    acct = bus.accountant(window=10)
    t0 = time.monotonic()
    time.sleep(0.03)  # idle (e.g. input pipeline warmup)
    acct.step_done(1, step_s=0.01)
    time.sleep(0.03)
    acct.pause(0.03, "restore")
    wall = time.monotonic() - t0
    g = acct.goodput()
    assert 0 < g <= 0.01 / wall + 0.05
    end = acct.finish(step=1)
    assert end["goodput"] < 0.5  # mostly idle: goodput must say so
    bus.close()


# ------------------------------------------------ guard / watchdog / timers


def test_step_guard_emits_skip_events_with_diagnostics(tmp_path):
    bus, mem, _ = _bus(tmp_path)
    guard = res.StepGuard(max_consecutive_skips=2, telemetry=bus)
    bad = {"g": jnp.asarray([1.0, jnp.nan, 2.0])}
    guard.update(True, step=1)
    with pytest.raises(res.DivergenceError) as ei:
        guard.update(False, bad, loss_scale=jnp.asarray(4096.0), step=2)
        guard.update(False, bad, loss_scale=jnp.asarray(2048.0), step=3)
    # the raise-path diagnostic names leaf + grad-norm + loss scale
    msg = str(ei.value)
    assert "['g']" in msg and "1 nan" in msg
    assert "global grad-norm" in msg and "loss scale" in msg
    skips = [e for e in mem.events if e["type"] == "skip"]
    assert len(skips) == 2
    for ev in skips:
        tele.validate_event(ev)
    assert skips[0]["step"] == 2 and skips[0]["loss_scale"] == 4096.0
    assert np.isnan(skips[0]["grad_norm"])  # nan grads -> nan norm
    assert skips[1]["consecutive"] == 2
    bus.close()


def test_watchdog_emits_event_and_postmortem_includes_report(tmp_path):
    bus, mem, _ = _bus(tmp_path)
    h = res.GracePeriodHandler()
    wd = res.Watchdog(timeout=0.05, handler=h, poll_interval=0.005,
                      telemetry=bus)
    try:
        with wd.step(7):
            time.sleep(0.3)
    finally:
        wd.close()
    assert h.should_stop and "watchdog_timeout" in h.reason
    events = [e for e in mem.events if e["type"] == "watchdog"]
    assert len(events) == 1 and events[0]["step"] == 7
    tele.validate_event(events[0])
    path = bus.flush_postmortem(h.reason, step=7, watchdog=wd)
    header = tele.load_jsonl(path)[0]
    assert "watchdog" in header  # heartbeat-age report rides the header
    assert "device_heartbeat_age_s" in header["watchdog"]
    bus.close()


def test_timers_log_routes_through_bus(tmp_path, capsys):
    from apex_tpu.transformer.pipeline_parallel._timers import Timers

    bus, mem, _ = _bus(tmp_path)
    timers = Timers(telemetry=bus)
    timers("fwd").start()
    timers("fwd").stop()
    out = timers.log(step=3)
    assert out.startswith("time (ms)") and "fwd" in out  # API preserved
    assert capsys.readouterr().out == ""  # routed, not printed
    ev = [e for e in mem.events if e["type"] == "timers"]
    assert len(ev) == 1 and "fwd" in ev[0]["timers_ms"]
    assert ev[0]["step"] == 3
    tele.validate_event(ev[0])
    # without a bus the reference behavior (print) is unchanged
    bare = Timers()
    bare("x").start()
    bare("x").stop()
    bare.log()
    assert "time (ms)" in capsys.readouterr().out
    bus.close()


def test_recompile_listener_emits_on_fresh_jit(tmp_path):
    bus, mem, _ = _bus(tmp_path)
    uninstall = tele.install_recompile_listener(bus)
    try:
        # a jit the process has never compiled before
        f = jax.jit(lambda x: x * 3.14159 + 2.71828)
        f(jnp.ones((3, 5))).block_until_ready()
    finally:
        uninstall()
    rec = [e for e in mem.events if e["type"] == "recompile"]
    assert rec, "no recompile event for a fresh jit"
    for ev in rec:
        tele.validate_event(ev)
        assert ev["duration_ms"] >= 0
    n = len(mem.events)
    f(jnp.ones((3, 5)) * 2).block_until_ready()  # cache hit after uninstall
    assert len(mem.events) == n
    bus.close()


# ------------------------------------------------- loop integration


@pytest.mark.chaos
def test_sigterm_grace_path_flushes_parseable_postmortem(tmp_path):
    """ISSUE 4 acceptance: killing a run (real SIGTERM through the
    GracePeriodHandler grace path) leaves a parseable postmortem
    covering the final ring-buffer window."""
    state, opt, scaler = _toy_state()
    step_fn = _make_step_fn(opt, scaler)
    bus, mem, stream = _bus(tmp_path, "sigterm")
    guard = res.StepGuard(max_consecutive_skips=4)
    with res.GracePeriodHandler() as h:
        pre = chaos.SimulatedPreemption(9, handler=h, telemetry=bus)
        result = run_resilient_training(
            step_fn, state, _batches(30),
            ckpt_dir=str(tmp_path / "ck"), save_every=4,
            handler=h, guard=guard, log_every=4,
            on_step=pre.poll, telemetry=bus)
    bus.close()
    assert result.preempted and result.stop_reason == "SIGTERM"
    assert result.step == 9

    pms = _postmortems(tmp_path)
    assert len(pms) == 1
    pm = tele.load_jsonl(str(tmp_path / pms[0]))
    assert tele.validate_events(pm) == len(pm)
    header = pm[0]
    assert header["type"] == "postmortem" and header["reason"] == "SIGTERM"
    assert header["ring_events"] == len(pm) - 1
    # the ring covers the run right up to the stop step
    ring_steps = [e["step"] for e in pm[1:] if e["type"] == "step"]
    assert ring_steps[-1] == 9 and ring_steps == sorted(ring_steps)
    # a guarded loop's step events are on the synced clock — the
    # guard's finite check bounds the device step, so step_ms is wall,
    # not host dispatch (and the stream says so)
    assert all(e["timing"] == "synced" for e in pm[1:]
               if e["type"] == "step")
    # the chaos injection itself is on the record
    assert any(e["type"] == "fault_injected" and e["kind"] == "preemption"
               for e in pm[1:])
    # main stream: validates whole, carries the same postmortem pointer
    assert tele.validate_jsonl(stream) > 0
    ptr = [e for e in tele.load_jsonl(stream) if e["type"] == "postmortem"]
    assert len(ptr) == 1 and ptr[0]["path"].endswith(pms[0])
    # run_end carries goodput with the ckpt fences booked
    end = [e for e in mem.events if e["type"] == "run_end"][-1]
    assert end["reason"] == "SIGTERM" and 0 < end["goodput"] <= 1
    assert "ckpt_fence" in end["buckets_s"]


def _toy_elastic_build():
    """Synthetic elastic workload: deterministic param bump per step,
    per-rank opt partitions whose total flat size (256) survives any
    8->4->2 reshard."""

    def build(devices):
        n = len(devices)
        params = {"w": jnp.zeros((16,), jnp.float32)}
        opt = {"exp_avg": jnp.zeros((n, 256 // n), jnp.float32)}

        def step_fn(state, batch):
            p, o = state
            return ({"w": p["w"] + 1.0}, o), None

        return step_fn, (params, opt), (P(), P("data"))

    return build


@pytest.mark.chaos
@pytest.mark.chaos_mesh
def test_device_loss_recovery_flushes_postmortem_and_events(tmp_path):
    """ISSUE 4 acceptance: a chaos DeviceLoss run leaves a postmortem
    naming the faulting step, and the main stream shows the full
    recovery arc — fault_injected -> device_loss -> ckpt_restore -> a
    run_end whose goodput ledger spans both loop attempts."""
    bus, mem, stream = _bus(tmp_path, "dloss")
    dl = chaos.DeviceLoss(at_step=3, device_ids=jax.devices()[4:8],
                          telemetry=bus)
    result = res.run_elastic_training(
        _toy_elastic_build(), jax.devices()[:8], [None] * 6,
        ckpt_dir=str(tmp_path / "ck"), save_every=1, on_step=dl.poll,
        max_restarts=2, log_every=2, telemetry=bus)
    bus.close()
    assert result.restarts == 1 and len(result.devices) == 4
    assert result.step == 6

    pms = _postmortems(tmp_path)
    assert len(pms) == 1
    pm = tele.load_jsonl(str(tmp_path / pms[0]))
    assert tele.validate_events(pm) == len(pm)
    assert pm[0]["reason"] == "DeviceLossError"
    # the postmortem contains the faulting step (loss injected at the
    # step-3 boundary poll)
    assert 3 in [e["step"] for e in pm[1:] if e["type"] == "step"]
    assert any(e["type"] == "fault_injected"
               and e["kind"] == "device_loss"
               and e["device_ids"] == [4, 5, 6, 7] for e in pm[1:])

    assert tele.validate_jsonl(stream) > 0
    evs = tele.load_jsonl(stream)
    dloss = [e for e in evs if e["type"] == "device_loss"]
    assert len(dloss) == 1 and dloss[0]["device_ids"] == [4, 5, 6, 7]
    assert dloss[0]["survivors"] == 4 and dloss[0]["recoverable"]
    restore = [e for e in evs if e["type"] == "ckpt_restore"]
    # step 3's save never happened (the poll raised first): the newest
    # intact checkpoint is step 2
    assert len(restore) == 1 and restore[0]["step"] == 2
    assert restore[0]["n_shards"] == 4
    # post-recovery events are stamped with the survivor submesh
    after = [e for e in evs if e["t"] > restore[0]["t"]
             and e["type"] == "step"]
    assert after and all(e["mesh"]["n_devices"] == 4 for e in after)
    # one cumulative ledger across both attempts: the last run_end's
    # rebuild/restore buckets are non-empty and step count is global
    end = [e for e in evs if e["type"] == "run_end"][-1]
    assert end["reason"] == "completed"
    assert "rebuild" in end["buckets_s"] and "restore" in end["buckets_s"]
    assert end["steps"] == 7  # 3 pre-loss + replayed 3..6 from step 2


@pytest.mark.chaos
def test_log_line_carries_steps_per_sec_and_heartbeat_age(tmp_path):
    state, opt, scaler = _toy_state()
    step_fn = _make_step_fn(opt, scaler)
    lines = []
    wd = res.Watchdog(timeout=30.0, poll_interval=0.01)
    try:
        run_resilient_training(step_fn, state, _batches(6),
                               guard=res.StepGuard(), watchdog=wd,
                               log_every=3, log_fn=lines.append)
    finally:
        wd.close()
    assert lines and all("steps/s" in ln for ln in lines)
    assert all("max_hb_age" in ln for ln in lines)
    assert all("skipped 0/" in ln for ln in lines)


def test_divergence_exit_flushes_postmortem(tmp_path):
    """Any exception leaving the loop — here the guard's own
    DivergenceError — dumps the ring before re-raising."""
    bus, mem, _ = _bus(tmp_path, "div")

    def step_fn(state, batch):
        return state, jnp.asarray(False)

    with pytest.raises(res.DivergenceError):
        run_resilient_training(step_fn, {"w": jnp.zeros(2)}, [None] * 9,
                               guard=res.StepGuard(max_consecutive_skips=3),
                               telemetry=bus)
    bus.close()
    pms = _postmortems(tmp_path)
    assert len(pms) == 1
    pm = tele.load_jsonl(str(tmp_path / pms[0]))
    assert pm[0]["reason"] == "DivergenceError"
    # the guard's skip events made it into the ring
    assert sum(e["type"] == "skip" for e in pm[1:]) == 3


# ------------------------------------------------------ summarize CLI


def _write_stream(path, run_id, n=20, ms=10.0, skip_at=()):
    bus = tele.TelemetryBus(run_id, sinks=[tele.JsonlSink(str(path))])
    acct = bus.accountant(window=5)
    bus.emit("run_start", step=0)
    for i in range(1, n + 1):
        acct.step_done(i, step_s=ms / 1e3, skipped=i in skip_at)
    acct.finish(step=n)
    bus.close()


def test_summarize_renders_percentiles_goodput_and_counts(tmp_path,
                                                          capsys):
    from apex_tpu.telemetry.__main__ import main

    a = tmp_path / "a.jsonl"
    _write_stream(a, "run-a", n=20, skip_at={7})
    assert main(["summarize", str(a)]) == 0
    out = capsys.readouterr().out
    assert "p50" in out and "p95" in out and "p99" in out
    assert "goodput" in out and "%" in out
    assert "step=20" in out and "run_end=1" in out

    s = tele.summarize_file(str(a))
    assert s["steps"] == 20 and s["skipped_steps"] == 1
    assert s["step_ms_p50"] > 0 and s["step_ms_p95"] >= s["step_ms_p50"]
    assert 0 < s["goodput"] <= 1

    assert main(["summarize", str(a), "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["run_id"] == "run-a" and rec["counts"]["step"] == 20


def test_summarize_diff_mode_ab_table(tmp_path, capsys):
    from apex_tpu.telemetry.__main__ import main

    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_stream(a, "run-a", n=10, ms=10.0)
    _write_stream(b, "run-b", n=10, ms=20.0)
    assert main(["summarize", str(a), "--diff", str(b)]) == 0
    out = capsys.readouterr().out
    assert "run-a" in out and "run-b" in out and "delta" in out
    # B's p50 is ~2x A's and the table says so
    assert "2.00x" in out


def test_summarize_estimates_goodput_without_run_end(tmp_path):
    """A crashed stream (no run_end) still summarizes — goodput falls
    back to productive-step seconds over the stream extent."""
    path = tmp_path / "crash.jsonl"
    bus = tele.TelemetryBus("crash", sinks=[tele.JsonlSink(str(path))])
    acct = bus.accountant(window=4)
    bus.emit("run_start", step=0)
    for i in range(1, 5):
        acct.step_done(i, step_s=0.01)
        time.sleep(0.012)
    bus.close()  # no finish(): simulated crash
    s = tele.summarize_file(str(path))
    assert s.get("goodput_estimated") and 0 < s["goodput"] <= 1


def test_validate_cli_flags_bad_stream(tmp_path, capsys):
    from apex_tpu.telemetry.__main__ import main

    good = tmp_path / "good.jsonl"
    _write_stream(good, "g", n=3)
    assert main(["validate", str(good)]) == 0
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"type": "step", "run_id": "x"}) + "\n")
    assert main(["validate", str(bad)]) == 1
    assert "INVALID" in capsys.readouterr().err


# ------------------------------------------------------ overhead bound


@pytest.mark.chaos
def test_telemetry_overhead_at_most_one_percent_of_step(tmp_path):
    """ISSUE 4 satellite: the per-step telemetry work (one step_done
    emit through a real JSONL sink; scalar fetches amortized over the
    window) must cost ≤1% of a toy train step's wall time."""
    @jax.jit
    def step(s, b):
        return s @ s * 0.999 + b

    s = jnp.ones((768, 768), jnp.float32)
    b = jnp.zeros((768, 768), jnp.float32)
    step(s, b).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        out = step(s, b)
    out.block_until_ready()
    step_wall = (time.perf_counter() - t0) / 5

    bus, _, _ = _bus(tmp_path, "ovh")
    acct = bus.accountant(window=10)
    loss = jnp.asarray(1.0)
    best = float("inf")
    for _ in range(5):  # best-of-5: reject fs hiccups, like the benches
        t0 = time.perf_counter()
        for i in range(200):
            acct.step_done(i, step_s=step_wall, scalars={"loss": loss})
        best = min(best, (time.perf_counter() - t0) / 200)
    bus.close()
    assert best <= 0.01 * step_wall, (
        f"telemetry {best * 1e6:.1f}us/step vs step {step_wall * 1e3:.2f}ms"
        f" = {100 * best / step_wall:.2f}% > 1%")


# ------------------------------------------- trace-capture-backed (slow)


@pytest.mark.slow
def test_device_clock_step_events_from_trace_capture(tmp_path):
    """Telemetry + the offline profiling layer: step events timed on
    DEVICE clocks via a profiler trace capture (the bench's wall-vs-
    device discipline applied to the stream).  Trace-capture-backed,
    so marked slow per the tier-1 budget rule."""
    from apex_tpu import profiling

    @jax.jit
    def f(x):
        return x @ x

    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    try:
        device_ms = profiling.device_time_ms(f, x, steps=2)
    except Exception as e:  # pragma: no cover — no profiler backend
        pytest.skip(f"trace capture unavailable: {e}")
    bus, mem, stream = _bus(tmp_path, "trace")
    bus.emit("step", step=1, step_ms=round(device_ms, 3), timing="device")
    bus.close()
    ev = tele.load_jsonl(stream)[0]
    tele.validate_event(ev)
    assert ev["timing"] == "device" and ev["step_ms"] > 0


# ------------------------------------------------ data plane (ISSUE 7)


def test_data_wait_bucket_and_stall_events_validate_end_to_end(tmp_path):
    """ISSUE 7 satellite: the accounting ``data_wait`` path and the
    ``data_stall``/``data_quarantine`` events validate against the
    schema driven through a REAL loop — a stalling prefetched source
    feeding run_resilient_training — not just hand-built dicts."""
    import numpy as np

    from apex_tpu.data import AsyncPrefetcher

    class SlowSource:
        """Checkpointable source whose production stalls every batch."""

        def __init__(self, n):
            self.n, self.i = n, 0

        def __iter__(self):
            return self

        def __next__(self):
            if self.i >= self.n:
                raise StopIteration
            time.sleep(0.03)
            self.i += 1
            return np.ones((4,), np.float32)

        def state_dict(self):
            return {"i": self.i}

        def load_state_dict(self, s):
            self.i = s["i"]

    bus, mem, stream = _bus(tmp_path, "datawait")
    pf = AsyncPrefetcher(SlowSource(5), depth=1, stall_threshold_s=0.005,
                         telemetry=bus)
    bus.emit("data_quarantine", record_id=7, reason="crc_mismatch",
             total=1, rate=0.001)
    result = run_resilient_training(
        lambda s, b: ({"w": s["w"] + float(np.sum(b))}, None),
        {"w": jnp.zeros(())}, data_iter=pf,
        ckpt_dir=str(tmp_path / "ck"), save_every=2, telemetry=bus)
    pf.close()
    bus.close()
    assert result.step == 5

    # the whole stream — stall + quarantine events included — is
    # schema-valid (strict mode, no torn-tail tolerance)
    assert tele.validate_jsonl(stream) == len(mem.events)
    stalls = [e for e in mem.events if e["type"] == "data_stall"]
    assert stalls and all(e["cause"] == "queue_dry" and e["wait_ms"] > 0
                          for e in stalls)
    # the loop measured real wait around next() and booked the bucket
    steps = [e for e in mem.events if e["type"] == "step"]
    assert any(e.get("data_wait_ms", 0) > 0 for e in steps)
    end = [e for e in mem.events if e["type"] == "run_end"][-1]
    assert end["buckets_s"].get("data_wait", 0) > 0

    # summarize surfaces the data plane on the one-screen view
    s = tele.summarize_events(mem.events)
    assert s["data_stalls"] == len(stalls)
    assert s["records_quarantined"] == 1
    txt = tele.format_summary(s)
    assert "data" in txt and "stalls" in txt

"""Serving fleet tier (ISSUE 16): SLO-aware routing over N replicas,
fencing + live migration, rolling restarts, fleet chaos, and the
autoscaling signal.

THE acceptance pin lives here: a replica killed mid-decode past every
recovery budget is fenced and its live requests migrate onto healthy
peers with token streams bitwise identical to an unmigrated
single-engine control — zero requests dropped.  The migration path
must also be zero-compile on the receiving replicas (their warmup
already built the executable set).
"""

import json
import random

import pytest

import apex_tpu.telemetry as tel
from apex_tpu.analysis import hot_path_guard
from apex_tpu.resilience.chaos import (BlackholeReplica, KillReplica,
                                       SlowReplica)
from apex_tpu.serving import (ServingEngine, ServingModelConfig, SimClock,
                              SpecConfig, init_params)
from apex_tpu.serving.fleet import (FENCED, FleetCapacityError, FleetRouter,
                                    HealthCheckTimeout, ReplicaProxy,
                                    SLOClass, rolling_restart, scale_hint,
                                    scale_hint_from_events)

pytestmark = [pytest.mark.serving, pytest.mark.fleet]

CFG = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                         num_layers=2, max_position=96)


@pytest.fixture(scope="module")
def serving_params():
    return init_params(CFG, seed=0)


def _factory(params, clock, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_budget", CFG.max_position)
    kw.setdefault("max_queue", 16)

    def build():
        return ServingEngine(CFG, params, clock=clock, **kw)

    return build


def _fleet(params, n=2, *, telemetry=None, clock=None, factory_kw=None,
           **router_kw):
    clock = clock if clock is not None else SimClock()
    reps = [ReplicaProxy(f"r{i}", _factory(params, clock,
                                           **(factory_kw or {})))
            for i in range(n)]
    return FleetRouter(reps, telemetry=telemetry, **router_kw), reps


def _prompts(n, seed=0, lo=4, hi=10):
    rng = random.Random(seed)
    return [[rng.randrange(1, CFG.vocab_size)
             for _ in range(rng.randrange(lo, hi))] for _ in range(n)]


def _control_streams(params, prompts, max_new=5, **kw):
    """Uninterrupted single-engine control: same prompts in the same
    submit order on one plain engine."""
    eng = _factory(params, SimClock(), **kw)()
    eng.warmup()
    for p in prompts:
        eng.submit(list(p), max_new_tokens=max_new)
    eng.run()
    return {r.rid: list(r.generated) for r in eng.sched.finished}


# ---------------------------------------------------------------------------
# Routing and SLO classes
# ---------------------------------------------------------------------------


class TestRouting:
    def test_least_loaded_placement_spreads(self, serving_params):
        fleet, reps = _fleet(serving_params, n=3)
        fleet.warmup()
        for p in _prompts(6):
            fleet.submit(p, max_new_tokens=3)
        depths = sorted(r.queue_depth() for r in reps)
        assert depths == [2, 2, 2]
        fleet.run()
        assert all(len(fleet.handles[r].generated) == 3 for r in range(6))

    def test_slo_class_assigns_deadline(self, serving_params):
        fleet, _ = _fleet(
            serving_params,
            slo_classes=[SLOClass("gold", deadline_s=30.0),
                         SLOClass("best_effort")])
        fleet.warmup()
        rid_g = fleet.submit([1, 2, 3], max_new_tokens=2, slo="gold")
        rid_b = fleet.submit([1, 2, 3], max_new_tokens=2, slo="best_effort")
        assert fleet.handles[rid_g].deadline_s == 30.0
        assert fleet.handles[rid_b].deadline_s is None

    def test_unknown_slo_class_raises(self, serving_params):
        fleet, _ = _fleet(serving_params)
        with pytest.raises(ValueError, match="unknown SLO class"):
            fleet.submit([1], max_new_tokens=1, slo="platinum")

    def test_all_queues_full_rejects_loudly(self, serving_params):
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="full", sinks=[mem])
        fleet, reps = _fleet(serving_params, n=2, telemetry=bus,
                             factory_kw={"max_queue": 1,
                                         "telemetry": bus})
        # no warmup/stepping: fill both bounded queues, then overflow
        for p in _prompts(3, seed=1):
            fleet.submit(p, max_new_tokens=2)
        rejected = [r for r in fleet.handles.values()
                    if r.finish_reason == "rejected"]
        assert len(rejected) == 1
        evs = [e for e in mem.events if e["type"] == "request_reject"]
        assert len(evs) == 1 and evs[0]["reason"] == "queue_full"

    def test_fenced_replicas_never_take_placement(self, serving_params):
        fleet, reps = _fleet(serving_params, n=2)
        reps[0].fence()
        for p in _prompts(4, seed=2):
            fleet.submit(p, max_new_tokens=2)
        assert reps[0].queue_depth() == 0
        assert reps[1].queue_depth() == 4
        reps[1].fence()
        with pytest.raises(RuntimeError, match="no healthy replicas"):
            fleet.submit([1], max_new_tokens=1)


# ---------------------------------------------------------------------------
# request_reject reasons (ISSUE 16 satellite)
# ---------------------------------------------------------------------------


class TestRejectReasons:
    def test_unservable_rejects_as_data_when_opted_in(self, serving_params):
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="unserv", sinks=[mem])
        eng = _factory(serving_params, SimClock(),
                       telemetry=bus, reject_unservable=True)()
        req = eng.submit([1] * 10, max_new_tokens=CFG.max_position)
        assert req.finish_reason == "rejected"
        assert req in eng.rejected
        evs = [e for e in mem.events if e["type"] == "request_reject"]
        assert len(evs) == 1 and evs[0]["reason"] == "unservable"

    def test_unservable_still_raises_by_default(self, serving_params):
        eng = _factory(serving_params, SimClock())()
        with pytest.raises(ValueError, match="exceeds"):
            eng.submit([1] * 10, max_new_tokens=CFG.max_position)

    def test_reason_enum_is_closed(self):
        ev = {"type": "request_reject", "run_id": "r", "step": 0, "t": 0.0,
              "ts": 0.0, "mesh": {}, "rid": 1, "reason": "felt_like_it",
              "queue_depth": 0}
        with pytest.raises(tel.schema.SchemaError, match="must be one of"):
            tel.validate_event(ev)


# ---------------------------------------------------------------------------
# serving_stall (ISSUE 16 satellite)
# ---------------------------------------------------------------------------


class TestServingStall:
    def test_budget_exhaustion_emits_and_raises(self, serving_params):
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="stall", sinks=[mem])
        eng = _factory(serving_params, SimClock(), telemetry=bus)()
        eng.warmup()
        eng.submit([1, 2, 3, 4], max_new_tokens=8)
        with pytest.raises(RuntimeError, match="did not drain"):
            eng.run(max_steps=1)
        evs = [e for e in mem.events if e["type"] == "serving_stall"]
        assert len(evs) == 1
        assert evs[0]["budget"] == 1
        assert evs[0]["waiting"] + evs[0]["running"] >= 1

    def test_raise_on_stall_false_returns_partial(self, serving_params):
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="stall2", sinks=[mem])
        eng = _factory(serving_params, SimClock(), telemetry=bus)()
        eng.warmup()
        eng.submit([1, 2, 3, 4], max_new_tokens=8)
        finished = eng.run(max_steps=1, raise_on_stall=False)
        assert finished == []                      # partial, not a lie
        assert [e["type"] for e in mem.events].count("serving_stall") == 1
        # the engine is still live: the budget was the only limit
        assert eng.run() and eng.sched.idle


# ---------------------------------------------------------------------------
# Heterogeneous snapshot/restore + adopt atomicity (ISSUE 16 satellite)
# ---------------------------------------------------------------------------


class TestHeterogeneousRestore:
    def _snapshot(self, params, n=5):
        src = _factory(params, SimClock(), max_queue=None)()
        for p in _prompts(n, seed=3):
            src.submit(p, max_new_tokens=4)
        return src.snapshot()

    def test_restore_into_smaller_max_queue_refused_atomically(
            self, serving_params):
        snap = self._snapshot(serving_params, n=5)
        tgt = _factory(serving_params, SimClock(), max_queue=2)()
        with pytest.raises(ValueError, match="max_queue"):
            tgt.restore(snap)
        # atomic: nothing queued, nothing retired, counters untouched
        assert not tgt.sched.waiting and not tgt.sched.running
        assert not tgt.sched.finished and tgt.steps == 0

    def test_restore_into_smaller_page_pool_refused_atomically(
            self, serving_params):
        src = _factory(serving_params, SimClock())()
        src.submit([1] * 40, max_new_tokens=20)    # needs 8 pages worst
        snap = src.snapshot()
        tgt = _factory(serving_params, SimClock(), num_pages=4)()
        with pytest.raises(ValueError, match="pages"):
            tgt.restore(snap)
        assert not tgt.sched.waiting and not tgt.sched.finished

    def test_adopt_merges_into_busy_engine(self, serving_params):
        snap = self._snapshot(serving_params, n=2)
        tgt = _factory(serving_params, SimClock())()
        tgt.warmup()
        own = tgt.submit([9] * 6, max_new_tokens=3)
        # rid 0 is taken by `own` — shift the incoming records into
        # free namespace (the router's global-rid job, done by hand)
        recs = json.loads(json.dumps(snap["requests"]))
        for i, r in enumerate(recs):
            r["rid"] = 100 + i
        adopted = tgt.adopt(recs)
        tgt.run()
        assert own.finish_reason is not None
        assert all(len(a.generated) == 4 for a in adopted)

    def test_adopt_refuses_rid_collision_atomically(self, serving_params):
        snap = self._snapshot(serving_params, n=2)
        tgt = _factory(serving_params, SimClock())()
        tgt.submit([9] * 6, max_new_tokens=3)      # takes rid 0
        recs = snap["requests"]
        assert recs[0]["rid"] == 0
        before = len(tgt.sched.waiting)
        with pytest.raises(ValueError, match="collides"):
            tgt.adopt(recs)
        assert len(tgt.sched.waiting) == before

    def test_adopt_refuses_past_queue_headroom_atomically(
            self, serving_params):
        snap = self._snapshot(serving_params, n=5)
        tgt = _factory(serving_params, SimClock(), max_queue=3)()
        with pytest.raises(ValueError, match="headroom"):
            tgt.adopt(snap["requests"])
        assert not tgt.sched.waiting


# ---------------------------------------------------------------------------
# Fence + migration: THE bitwise pin
# ---------------------------------------------------------------------------


class TestFenceAndMigrate:
    def test_killed_replica_fences_and_streams_stay_bitwise(
            self, serving_params):
        prompts = _prompts(6, seed=4)
        control = _control_streams(serving_params, prompts)
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="kill", sinks=[mem])
        fleet, reps = _fleet(serving_params, n=2, telemetry=bus,
                             fault_retries=2)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=5)
        with KillReplica("r0", at_step=3, telemetry=bus):
            with hot_path_guard("fleet migration", transfers=None) as g:
                fleet.run()
        # no compiles anywhere across fence + migration + drain: the
        # receiving replica's warmup already built every executable
        assert g.recompiles == 0 and g.syncs == []
        assert reps[0].state == FENCED
        # both budgets genuinely burned before the fence
        assert reps[0].engine.recoveries == reps[0].engine.max_recoveries
        assert reps[0].fault_attempts == fleet.fault_retries + 1
        fences = [e for e in mem.events if e["type"] == "replica_fence"]
        assert len(fences) == 1 and fences[0]["replica"] == "r0"
        assert fences[0]["cause"] == "DeviceLossError"
        moves = [e for e in mem.events if e["type"] == "request_migrate"]
        assert moves and all(m["from_replica"] == "r0"
                             and m["to_replica"] == "r1" for m in moves)
        # zero drops, every stream bitwise the control's
        assert len(fleet.handles) == len(prompts)
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"

    def test_last_replica_fence_refuses_loudly(self, serving_params):
        fleet, reps = _fleet(serving_params, n=1, fault_retries=0)
        fleet.warmup()
        fleet.submit([1, 2, 3, 4], max_new_tokens=4)
        with KillReplica("r0"):
            with pytest.raises(FleetCapacityError, match="no healthy"):
                fleet.run()

    @pytest.mark.slow
    def test_kill_at_every_boundary_sweep(self, serving_params):
        """The exhaustive form: kill r0 at every step index the
        healthy run ever reaches; every kill point must migrate to
        bitwise streams with zero drops."""
        prompts = _prompts(5, seed=5)
        control = _control_streams(serving_params, prompts)
        # measure the healthy run's step count once
        probe, _ = _fleet(serving_params, n=2)
        probe.warmup()
        for p in prompts:
            probe.submit(p, max_new_tokens=5)
        probe.run()
        total = max(r.engine.steps for r in probe.replicas)
        for at in range(1, total + 1):
            fleet, _ = _fleet(serving_params, n=2)
            fleet.warmup()
            for p in prompts:
                fleet.submit(p, max_new_tokens=5)
            with KillReplica("r0", at_step=at):
                fleet.run()
            for rid, toks in control.items():
                assert fleet.handles[rid].generated == toks, \
                    f"kill at {at}, rid {rid}"


# ---------------------------------------------------------------------------
# Health-check chaos: slow and blackholed replicas
# ---------------------------------------------------------------------------


class TestHealthChaos:
    def test_slow_replica_below_budget_is_tolerated(self, serving_params):
        fleet, reps = _fleet(serving_params, n=2, health_timeout_s=0.25)
        fleet.warmup()
        for p in _prompts(4, seed=6):
            fleet.submit(p, max_new_tokens=3)
        with SlowReplica("r0", latency_s=0.1):
            fleet.run()
        assert reps[0].state != FENCED
        assert all(h.finish_reason is not None or h.done
                   for h in fleet.handles.values())

    def test_slow_replica_past_budget_is_fenced(self, serving_params):
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="slowrep", sinks=[mem])
        fleet, reps = _fleet(serving_params, n=2, telemetry=bus,
                             health_timeout_s=0.25)
        fleet.warmup()
        prompts = _prompts(4, seed=7)
        control = _control_streams(serving_params, prompts, max_new=3)
        for p in prompts:
            fleet.submit(p, max_new_tokens=3)
        with SlowReplica("r0", latency_s=1.0):
            fleet.run()
        assert reps[0].state == FENCED
        fences = [e for e in mem.events if e["type"] == "replica_fence"]
        assert fences[0]["cause"] == "health_check_timeout"
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks

    def test_blackholed_replica_is_detected_not_waited_on(
            self, serving_params):
        fleet, reps = _fleet(serving_params, n=2)
        fleet.warmup()
        for p in _prompts(4, seed=8):
            fleet.submit(p, max_new_tokens=3)
        with BlackholeReplica("r0"):
            # bounded rounds: detection is virtual-latency, so a hang
            # here would be a router bug, not a slow test
            fleet.run(max_steps=500)
        assert reps[0].state == FENCED
        assert all(len(fleet.handles[r].generated) == 3
                   for r in fleet.handles)

    def test_ping_is_deterministic_and_sleepless(self, serving_params):
        rep = ReplicaProxy("solo", _factory(serving_params, SimClock()))
        assert rep.ping(0.25) == 0.0
        with BlackholeReplica("solo"):
            with pytest.raises(HealthCheckTimeout, match="inf"):
                rep.ping(0.25)


# ---------------------------------------------------------------------------
# Rolling restart
# ---------------------------------------------------------------------------


class TestRollingRestart:
    # slow since PR 22: pays for test_tpu_lowering / test_chip_smoke in tier-1
    @pytest.mark.slow
    def test_rolling_restart_mid_serve_is_bitwise(self, serving_params):
        prompts = _prompts(6, seed=9)
        control = _control_streams(serving_params, prompts)
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="roll", sinks=[mem])
        fleet, reps = _fleet(serving_params, n=3, telemetry=bus)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=5)
        for _ in range(3):                          # some work in flight
            fleet.step()
        rolling_restart(fleet)          # restarted engines re-warm here
        with hot_path_guard("post-restart drain", transfers=None) as g:
            fleet.run()
        # every RECEIVING replica serves its adopted work compile- and
        # sync-free: the restart re-warmed the full executable set
        assert g.recompiles == 0 and g.syncs == []
        fences = [e for e in mem.events if e["type"] == "replica_fence"]
        assert [f["cause"] for f in fences] == ["rolling_restart"] * 3
        assert all(r.restarts == 1 and r.healthy for r in reps)
        assert len(fleet.handles) == len(prompts)
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"

    def test_fleet_of_one_readmits_its_own_snapshot(self, serving_params):
        prompts = _prompts(4, seed=10)
        control = _control_streams(serving_params, prompts)
        fleet, reps = _fleet(serving_params, n=1)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=5)
        for _ in range(2):
            fleet.step()
        rolling_restart(fleet)
        fleet.run()
        assert reps[0].restarts == 1
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks

    def test_restart_repairs_a_fenced_replica(self, serving_params):
        fleet, reps = _fleet(serving_params, n=2)
        fleet.warmup()
        for p in _prompts(4, seed=11):
            fleet.submit(p, max_new_tokens=3)
        with KillReplica("r0"):
            fleet.run()
        assert reps[0].state == FENCED
        rolling_restart(fleet)
        assert all(r.healthy for r in reps)
        # the repaired replica takes new work again
        fleet.submit([1, 2, 3], max_new_tokens=2)
        assert reps[0].queue_depth() + reps[0].running() == 1
        fleet.run()


# ---------------------------------------------------------------------------
# Speculative + chunked replicas through the same machinery
# ---------------------------------------------------------------------------


class TestSpecChunkedFleet:
    def test_migration_bitwise_with_spec_and_chunked(self, serving_params):
        """The tentpole cross-check at tier-1 scale (the dry-run's
        chaos_fleet leg runs the bigger version): spec+chunked
        replicas, kill one mid-decode, control is a PLAIN engine —
        valid because draft-verify and chunked prefill are
        output-invariant by their own acceptance pins."""
        prompts = _prompts(4, seed=12, lo=12, hi=24)
        control = _control_streams(serving_params, prompts, max_new=6)
        spec_kw = {"spec": SpecConfig(k=2, chunk_size=8)}
        fleet, reps = _fleet(serving_params, n=2, factory_kw=spec_kw)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=6)
        with KillReplica("r0", at_step=2):
            fleet.run()
        assert reps[0].state == FENCED
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"


# ---------------------------------------------------------------------------
# Autoscaling signal
# ---------------------------------------------------------------------------


class TestScaleHint:
    def test_pure_thresholds(self):
        assert scale_hint(shed_rate=0.2, occupancy=0.1) == "scale_up"
        assert scale_hint(shed_rate=0.0, occupancy=0.9) == "scale_up"
        assert scale_hint(shed_rate=0.0, occupancy=0.5,
                          deadline_hit_rate=0.5) == "scale_up"
        assert scale_hint(shed_rate=0.0, occupancy=0.1) == "scale_down"
        assert scale_hint(shed_rate=0.0, occupancy=0.1,
                          deadline_hit_rate=1.0) == "scale_down"
        assert scale_hint(shed_rate=0.01, occupancy=0.5) == "hold"
        assert scale_hint(shed_rate=0.0, occupancy=0.5,
                          deadline_hit_rate=0.95) == "hold"

    def test_from_recorded_trace(self, serving_params):
        """The policy is replayable from a recorded stream alone —
        no live fleet needed."""
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="trace", sinks=[mem])
        eng = _factory(serving_params, SimClock(), telemetry=bus)()
        eng.warmup()
        for p in _prompts(4, seed=13):
            eng.submit(p, max_new_tokens=3)
        eng.run()
        assert scale_hint_from_events(mem.events) in (
            "scale_down", "hold")          # light load never scales up
        # synthetic overload trace: heavy shedding must scale up
        synth = [{"type": "request_reject"}] * 5 + \
                [{"type": "request_retire"}] * 5
        assert scale_hint_from_events(synth) == "scale_up"

    def test_router_emits_schema_valid_hint(self, serving_params):
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="hint", sinks=[mem])
        fleet, _ = _fleet(serving_params, n=2, telemetry=bus)
        fleet.warmup()
        for p in _prompts(3, seed=14):
            fleet.submit(p, max_new_tokens=2)
        fleet.run()
        hint = fleet.emit_scale_hint()
        evs = [e for e in mem.events if e["type"] == "fleet_scale_hint"]
        assert evs and evs[-1]["hint"] == hint
        for e in evs:
            tel.validate_event(e)


# ---------------------------------------------------------------------------
# Event schema pins
# ---------------------------------------------------------------------------


class TestFleetEventSchema:
    def _stamp(self, type_, **payload):
        ev = {"type": type_, "run_id": "r", "step": 0, "t": 0.0,
              "ts": 0.0, "mesh": {}}
        ev.update(payload)
        return ev

    def test_new_events_validate(self):
        tel.validate_event(self._stamp(
            "serving_stall", waiting=2, running=1, budget=100))
        tel.validate_event(self._stamp(
            "replica_fence", replica="r0", cause="DeviceLossError",
            live_requests=3, recoveries=3, fault_retries=2))
        tel.validate_event(self._stamp(
            "request_migrate", rid=7, from_replica="r0", to_replica="r1",
            tokens_done=4, was_running=True))
        tel.validate_event(self._stamp(
            "fleet_scale_hint", hint="hold", shed_rate=0.0, occupancy=0.4,
            replicas=3, healthy=3))

    def test_hint_enum_is_closed(self):
        with pytest.raises(tel.schema.SchemaError, match="must be one of"):
            tel.validate_event(self._stamp(
                "fleet_scale_hint", hint="buy_more_tpus", shed_rate=0.0,
                occupancy=0.4, replicas=3, healthy=3))

    def test_was_running_must_be_a_real_bool(self):
        with pytest.raises(tel.schema.SchemaError, match="bool"):
            tel.validate_event(self._stamp(
                "request_migrate", rid=7, from_replica="r0",
                to_replica="r1", tokens_done=4, was_running=1))

"""Fleet-wide distributed request tracing (ISSUE 19).

Three contracts live here:

* **Reconstruction** — :func:`build_traces` rebuilds one causal span
  tree per request from ANY set of per-replica streams: file order
  irrelevant, duplicated wire re-emissions merged (never forked),
  migration/preemption lives resolved, dangling parents loud.
* **TTFT decomposition** — the four components (queue / prefill /
  ship / decode-wait) sum to the engine's measured shipping-aware
  ``ttft_ms`` within :data:`TTFT_SUM_TOLERANCE_MS`, on colocated and
  disaggregated paths alike; the colocated control's ship component
  is identically zero.  The satellite-1 pin: a kv_ship retry storm
  lands in TTFT (deadline accounting FLIPS vs the colocated control
  on the same deadline).
* **Flight recorder** — a bounded ring dumped as a schema-valid
  postmortem bundle on fence / migrate refusal / recovery exhaustion;
  memory-only test buses never litter the cwd.
"""

import json
import os

import pytest

import apex_tpu.telemetry as tel
from apex_tpu.analysis import hot_path_guard
from apex_tpu.resilience.chaos import DeviceLossError
from apex_tpu.serving import (ServingEngine, ServingModelConfig, SimClock,
                              init_params)
from apex_tpu.serving.engine import set_fault_hook
from apex_tpu.serving.fleet import (FENCED, ChaosTransport, DisaggRouter,
                                    FleetRouter, LocalTransport,
                                    ReplicaProxy)
from apex_tpu.telemetry.__main__ import main as tel_main
from apex_tpu.telemetry.recorder import FlightRecorder
from apex_tpu.telemetry.schema import load_jsonl, validate_events
from apex_tpu.telemetry.summarize import (format_diff, format_summary,
                                          summarize_events)
from apex_tpu.telemetry.tracing import (SPAN_KINDS, TTFT_SUM_TOLERANCE_MS,
                                        Span, admission_life, build_traces,
                                        critical_path, format_trace,
                                        load_trace_streams,
                                        maybe_dump_flight_record,
                                        run_trace_cli, ttft_decomposition,
                                        validate_trace)

pytestmark = [pytest.mark.serving, pytest.mark.tracing]

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


def _disagg(params, *, telemetry=None, clock=None, factory_kw=None,
            **router_kw):
    clock = clock if clock is not None else SimClock()
    kw = dict(factory_kw or {})
    reps = [ReplicaProxy("p0", _factory(params, clock, prefill_only=True,
                                        **kw), role="prefill"),
            ReplicaProxy("d0", _factory(params, clock, kv_import=True,
                                        **kw), role="decode")]
    return DisaggRouter(reps, telemetry=telemetry, **router_kw), reps


PROMPT = [3, 7, 11, 13, 5, 2]


# ---------------------------------------------------------------------------
# Synthetic reconstruction units (no engine: pure span-event fixtures)
# ---------------------------------------------------------------------------

# The worked disaggregated request: arrival 0.0, admitted at 2.0
# (queue 2000ms), prefill done at 3.0 (prefill 1000ms), KV exported
# 3.0 -> shipped -> imported by 6.0 (ship 3000ms), first token
# streamable at 6.5 (decode-wait residual 500ms) — TTFT 6500ms.
_LIFE = admission_life(0, 2.0)
_RID = 7


def _span_ev(span_id, kind, t0, t1, parent=None, **kw):
    ev = dict(type="span", rid=_RID, span_id=span_id, kind=kind,
              t_start=t0, t_end=t1)
    if parent is not None:
        ev["parent_id"] = parent
    ev.update(kw)
    return ev


def _shipped_request_events():
    q = f"{_RID}:queue_wait:{_LIFE}"
    a = f"{_RID}:admit:{_LIFE}"
    exp = f"{_RID}:kv_export:{_LIFE}"
    ship = f"{_RID}:kv_ship:d0:1"
    return [
        _span_ev(q, "queue_wait", 0.0, 2.0),
        _span_ev(a, "admit", 2.0, 2.0, parent=q),
        _span_ev(f"{_RID}:prefill_chunk:{_LIFE}:0", "prefill_chunk",
                 2.0, 3.0, parent=a),
        _span_ev(exp, "kv_export", 3.0, 3.2, parent=a, replica="p0"),
        _span_ev(ship, "kv_ship", 3.2, 5.8, parent=exp, replica="p0",
                 attempt=1, outcome="ok"),
        _span_ev(f"{_RID}:kv_import:1", "kv_import", 5.0, 6.0,
                 parent=ship, replica="d0", attempt=1),
        _span_ev(f"{_RID}:decode_wait:{_LIFE}", "decode_wait", 3.0, 6.5,
                 parent=a),
        _span_ev(f"{_RID}:decode_steps:{_LIFE}", "decode_steps",
                 6.5, 9.0, parent=f"{_RID}:decode_wait:{_LIFE}"),
        _span_ev(f"{_RID}:stream_emit:{_LIFE}", "stream_emit", 6.5, 6.5,
                 parent=f"{_RID}:decode_wait:{_LIFE}"),
    ]


class TestReconstruction:
    def test_out_of_order_streams_reconstruct_one_tree(self):
        events = _shipped_request_events()
        # two "replica streams" interleaved worst-case: reversed halves
        shuffled = list(reversed(events[::2])) + list(reversed(events[1::2]))
        traces = build_traces(shuffled)
        assert set(traces) == {_RID}
        t = traces[_RID]
        assert len(t.spans) == len(events)
        assert validate_trace(t) == []
        assert [s.kind for s in t.roots()] == ["queue_wait"]
        d = ttft_decomposition(t)
        assert d == {"rid": _RID, "ttft_ms": 6500.0,
                     "ttft_queue_ms": 2000.0, "ttft_prefill_ms": 1000.0,
                     "ttft_ship_ms": 3000.0,
                     "ttft_decode_wait_ms": 500.0}

    def test_critical_path_splices_ship_chain(self):
        t = build_traces(_shipped_request_events())[_RID]
        kinds = [s.kind for s in critical_path(t)]
        assert kinds == ["queue_wait", "admit", "kv_export",
                         "decode_wait", "kv_ship", "kv_import",
                         "stream_emit"]

    def test_duplicate_redelivery_merges_never_forks(self):
        events = _shipped_request_events()
        # a duplicated wire copy re-emits the SAME span id, possibly
        # with a narrower interval and missing attributes
        dup = dict(events[5], t_start=5.5, t_end=5.9)
        dup.pop("parent_id")
        dup.pop("attempt")
        traces = build_traces(events + [dup, dict(events[0])])
        t = traces[_RID]
        assert len(t.spans) == len(events)
        assert t.duplicates == 2
        imp = t.spans[f"{_RID}:kv_import:1"]
        # merge widened nothing here (the original covers the dup) and
        # kept the causal link the duplicate lacked
        assert (imp.t_start, imp.t_end) == (5.0, 6.0)
        assert imp.parent_id == f"{_RID}:kv_ship:d0:1"
        assert ttft_decomposition(t)["ttft_ship_ms"] == 3000.0

    def test_merge_widens_interval_and_fills_gaps(self):
        a = Span(rid=1, span_id="s", kind="admit", t_start=2.0, t_end=3.0)
        b = Span(rid=1, span_id="s", kind="admit", t_start=1.0, t_end=2.5,
                 parent_id="q", replica="r0")
        a.merge(b)
        assert (a.t_start, a.t_end) == (1.0, 3.0)
        assert a.parent_id == "q" and a.replica == "r0"

    def test_orphan_span_is_loud(self):
        events = _shipped_request_events()
        events.append(_span_ev(f"{_RID}:kv_import:9", "kv_import",
                               5.0, 6.0, parent=f"{_RID}:kv_ship:d9:9"))
        t = build_traces(events)[_RID]
        problems = validate_trace(t)
        assert len(problems) == 1 and "dangling parent" in problems[0]
        assert [s.span_id for s in t.orphans()] == [f"{_RID}:kv_import:9"]
        assert "ORPHAN" in format_trace(t)

    def test_unknown_kind_and_inverted_interval_flagged(self):
        t = build_traces([
            _span_ev("x:1", "teleport", 0.0, 1.0),
            _span_ev("x:2", "admit", 3.0, 1.0),
        ])[_RID]
        problems = validate_trace(t)
        assert any("unknown kind" in p for p in problems)
        assert any("ends before it starts" in p for p in problems)

    def test_preempted_request_uses_latest_life_before_first_token(self):
        """A preempted request's FINAL life admits after its first
        token existed; the decomposition must attribute prefill to the
        latest life that started before decode_wait, and queue to that
        life's queue_wait."""
        life2 = admission_life(1, 8.0)
        events = _shipped_request_events()
        q2 = f"{_RID}:queue_wait:{life2}"
        events += [
            _span_ev(q2, "queue_wait", 0.0, 8.0),
            _span_ev(f"{_RID}:admit:{life2}", "admit", 8.0, 8.0,
                     parent=q2),
        ]
        # the final-life stream_emit points at a decode_wait whose
        # parent admit came LATER than the wait began
        t = build_traces(events)[_RID]
        wait = t.spans[f"{_RID}:decode_wait:{_LIFE}"]
        wait.parent_id = f"{_RID}:admit:{life2}"
        d = ttft_decomposition(t)
        assert d["ttft_queue_ms"] == 2000.0
        assert d["ttft_prefill_ms"] == 1000.0

    def test_ship_segment_survives_broken_causal_link(self):
        """A kv_import whose parent ship span never landed in any
        recorded stream still decomposes: fall back to the latest
        preceding kv_export."""
        events = [e for e in _shipped_request_events()
                  if e["kind"] != "kv_ship"]
        t = build_traces(events)[_RID]
        assert ttft_decomposition(t)["ttft_ship_ms"] == 3000.0

    def test_unfinished_trace_is_incomplete_in_time_not_structure(self):
        events = [e for e in _shipped_request_events()
                  if e["kind"] not in ("stream_emit", "decode_steps")]
        t = build_traces(events)[_RID]
        assert validate_trace(t) == []
        assert ttft_decomposition(t) is None
        assert critical_path(t) == []

    def test_span_kinds_derive_from_schema(self):
        assert set(SPAN_KINDS) == {
            "queue_wait", "admit", "prefill_chunk", "kv_export",
            "kv_ship", "kv_import", "decode_wait", "decode_steps",
            "migrate_hop", "stream_emit"}


# ---------------------------------------------------------------------------
# Trace context on the wire
# ---------------------------------------------------------------------------


class TestWireTraceContext:
    def test_ctx_rides_envelope_outside_payload_crc(self):
        t = LocalTransport()
        seen = []
        t.register("d", "echo", lambda p: (seen.append(t.current_trace)
                                           or {"ok": True}))
        ctx = {"rid": 4, "span_id": "4:kv_ship:d:1", "attempt": 1}
        assert t.call("d", "echo", {"x": 1}, trace=ctx)["ok"]
        assert seen == [ctx]
        # the context is scoped to the delivery, not left dangling
        assert t.current_trace is None

    def test_corruption_fault_never_touches_ctx(self):
        chaos = ChaosTransport(LocalTransport(),
                               schedule={("migrate", "corrupt"): {1}})
        ctx = {"rid": 9, "span_id": "9:kv_ship:d0:2", "attempt": 2}
        wire = chaos.inner.serialize("d", "migrate", {"records": [1]},
                                     trace=ctx)
        env = json.loads(chaos._corrupt(wire, "migrate"))
        assert env["trace"] == ctx        # verbatim through the fault

    def test_duplicate_wire_copies_carry_identical_ctx(self):
        t = LocalTransport()
        ctx = {"rid": 2, "span_id": "2:kv_ship:d0:1", "attempt": 1}
        wire = t.serialize("d", "kv_page", {"page_index": 0}, trace=ctx)
        # the duplicate is the SAME bytes — same span id on both ends,
        # which is exactly why build_traces can merge instead of fork
        assert json.loads(wire)["trace"] == ctx
        t.register("d", "kv_page", lambda p: {"ok": True})
        assert t.deliver(wire) == t.deliver(wire)


# ---------------------------------------------------------------------------
# Real engine: colocated decomposition pins
# ---------------------------------------------------------------------------


def _colocated_run(params, tmp_path=None, n=4):
    sinks = [tel.MemorySink()]
    if tmp_path is not None:
        sinks.append(tel.JsonlSink(str(tmp_path / "colo.jsonl")))
    bus = tel.TelemetryBus(run_id="trace-colo", sinks=sinks)
    eng = _factory(params, SimClock(), telemetry=bus)()
    eng.warmup()
    for i in range(n):
        eng.submit([2 + i, 5, 9, 4 + i], max_new_tokens=4)
    eng.run()
    return eng, sinks[0].events


class TestColocatedDecomposition:
    def test_components_sum_to_measured_ttft(self, serving_params):
        eng, events = _colocated_run(serving_params)
        retires = {e["rid"]: e for e in events
                   if e["type"] == "request_retire"}
        traces = build_traces(events)
        assert set(traces) == set(retires)
        for rid, t in traces.items():
            assert validate_trace(t) == []
            d = ttft_decomposition(t)
            assert d is not None
            parts = (d["ttft_queue_ms"] + d["ttft_prefill_ms"]
                     + d["ttft_ship_ms"] + d["ttft_decode_wait_ms"])
            assert abs(parts - retires[rid]["ttft_ms"]) \
                <= TTFT_SUM_TOLERANCE_MS
            # the colocated sanity zero: no ship leg, in the spans OR
            # the shipping-aware retire payload
            assert d["ttft_ship_ms"] == 0.0
            assert "ship_ms" not in retires[rid]
            assert not t.by_kind("kv_ship") and not t.by_kind("kv_import")

    def test_span_events_validate_against_schema(self, serving_params):
        _, events = _colocated_run(serving_params)
        assert any(e["type"] == "span" for e in events)
        validate_events(events)   # raises SchemaError on drift

    def test_decode_loop_span_emission_is_host_sync_free(
            self, serving_params):
        """Satellite 3: tracing must not buy observability with decode
        stalls — spans buffer host-side state only."""
        bus = tel.TelemetryBus(run_id="trace-hot",
                               sinks=[tel.MemorySink()])
        eng = _factory(serving_params, SimClock(), telemetry=bus)()
        eng.warmup()
        for i in range(3):
            eng.submit([2 + i, 5, 9], max_new_tokens=4)
        with hot_path_guard("traced serve", transfers=None) as g:
            eng.run()
        assert g.recompiles == 0 and g.syncs == []
        assert any(e["type"] == "span"
                   for e in bus.sinks[0].events)

    def test_trace_cli_exit_0_on_recorded_stream(self, serving_params,
                                                 tmp_path, capsys):
        _colocated_run(serving_params, tmp_path)
        path = str(tmp_path / "colo.jsonl")
        assert run_trace_cli([path]) == 0
        assert tel_main(["trace", path]) == 0
        out = capsys.readouterr().out
        assert "critical path:" in out and "ttft" in out
        assert tel_main(["trace", path, "--json"]) == 0
        rec = json.loads(capsys.readouterr().out)
        assert rec["problems"] == [] and len(rec["traces"]) == 4


# ---------------------------------------------------------------------------
# Disaggregated path: ship storm, shipping-aware TTFT, deadline flip
# ---------------------------------------------------------------------------


def _storm_fleet(params, *, deadline_s=None, tmp_path=None):
    """1 prefill + 1 decode replica; the first two kv_page messages
    drop in flight, so the single shipment retries twice (backoff 2
    then 4 rounds) before landing — a deterministic ship storm.  The
    router ticks the shared clock once per ROUND: backoff rounds cost wall time even while every engine
    idles, which is exactly the wall the ship decomposition must
    surface."""
    sinks = [tel.MemorySink()]
    if tmp_path is not None:
        sinks.append(tel.JsonlSink(str(tmp_path / "storm.jsonl")))
    bus = tel.TelemetryBus(run_id="trace-storm", sinks=sinks)
    chaos = ChaosTransport(LocalTransport(),
                           schedule={("kv_page", "drop"): {1, 2}},
                           telemetry=bus)
    clock = SimClock()
    fleet, reps = _disagg(params, telemetry=bus, clock=clock,
                          factory_kw={"telemetry": bus}, transport=chaos,
                          on_round=clock.advance)
    fleet.warmup()
    rid = fleet.submit(list(PROMPT), max_new_tokens=4,
                       deadline_s=deadline_s)
    fleet.run()
    return fleet, rid, sinks[0].events


class TestShippingAwareTTFT:
    def test_ship_storm_lands_in_ttft_and_sums(self, serving_params):
        fleet, rid, events = _storm_fleet(serving_params)
        retire = [e for e in events if e["type"] == "request_retire"
                  and e["rid"] == rid][0]
        assert retire["ship_ms"] > 0.0
        assert retire["ttft_ms"] >= retire["ship_ms"]
        t = build_traces(events)[rid]
        assert validate_trace(t) == []
        ships = t.by_kind("kv_ship")
        assert [s.outcome for s in ships] == ["retry", "retry", "ok"]
        assert [s.attempt for s in ships] == [1, 2, 3]
        assert all(s.reason == "timeout" for s in ships[:2])
        # the import parents on the WINNING attempt's span id (carried
        # on the wire), not on either dropped attempt
        imp = t.by_kind("kv_import")[-1]
        assert imp.parent_id == ships[-1].span_id
        d = ttft_decomposition(t)
        assert d["ttft_ship_ms"] > 0.0
        parts = (d["ttft_queue_ms"] + d["ttft_prefill_ms"]
                 + d["ttft_ship_ms"] + d["ttft_decode_wait_ms"])
        assert abs(parts - retire["ttft_ms"]) <= TTFT_SUM_TOLERANCE_MS

    def test_ship_retry_storm_flips_deadline_vs_colocated(
            self, serving_params):
        """Satellite 1 acceptance: with shipping-aware accounting, the
        SAME deadline that a colocated engine comfortably makes is
        MISSED under a kv_ship retry storm — the ship wall is real SLO
        time, not bookkeeping."""
        # calibrate: the storm run's actual finish on the shared clock
        fleet, rid, _ = _storm_fleet(serving_params)
        req = fleet.handles[rid]
        calib_finish, calib_tokens = req.finish_t, list(req.generated)
        deadline_s = (calib_finish - 1e-6) - req.arrival_t
        # identical storm, now with the deadline armed: the request
        # must still COMPLETE (its last token predates the sweep that
        # notices the deadline) — but as a recorded SLO miss
        fleet2, rid2, events2 = _storm_fleet(serving_params,
                                             deadline_s=deadline_s)
        req2 = fleet2.handles[rid2]
        assert req2.finish_reason in ("eos", "length")
        assert list(req2.generated) == calib_tokens
        retire2 = [e for e in events2 if e["type"] == "request_retire"
                   and e["rid"] == rid2][0]
        assert retire2["deadline_hit"] is False
        assert retire2["ship_ms"] > 0.0
        # colocated control: same prompt, same budget, same deadline —
        # without the ship wall the deadline is easy
        bus = tel.TelemetryBus(run_id="trace-colo-dl",
                               sinks=[tel.MemorySink()])
        eng = _factory(serving_params, SimClock(), telemetry=bus)()
        eng.warmup()
        eng.submit(list(PROMPT), max_new_tokens=4, deadline_s=deadline_s)
        eng.run()
        ctrl = [e for e in bus.sinks[0].events
                if e["type"] == "request_retire"][0]
        assert ctrl["deadline_hit"] is True
        assert "ship_ms" not in ctrl

    def test_storm_stream_decomposes_via_cli(self, serving_params,
                                             tmp_path):
        _storm_fleet(serving_params, tmp_path=tmp_path)
        assert run_trace_cli([str(tmp_path / "storm.jsonl")],
                             echo=lambda *_: None) == 0


# ---------------------------------------------------------------------------
# Migration hops join the trace
# ---------------------------------------------------------------------------


class TestMigrationTracing:
    def test_fence_migration_hop_is_a_root_span(self, serving_params):
        chaos = ChaosTransport(LocalTransport(),
                               schedule={("ping", "drop"): {1}})
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="trace-migrate", sinks=[mem])
        clock = SimClock()
        reps = [ReplicaProxy(f"r{i}",
                             _factory(serving_params, clock,
                                      telemetry=bus))
                for i in range(2)]
        fleet = FleetRouter(reps, telemetry=bus, transport=chaos)
        fleet.warmup()
        for i in range(4):
            fleet.submit([2 + i, 5, 9, 4], max_new_tokens=4)
        fleet.run()
        assert reps[0].state == FENCED
        moved = [e["rid"] for e in mem.events
                 if e["type"] == "request_migrate"]
        assert moved
        traces = build_traces(mem.events)
        hops = [s for rid in moved
                for s in traces[rid].by_kind("migrate_hop")]
        assert hops and all(s.parent_id is None for s in hops)
        assert all(f":migrate_hop:r0:r1:" in s.span_id for s in hops)
        # migrated lives still reconstruct complete and sum: the whole
        # point of deriving span ids from application identity
        for t in traces.values():
            assert validate_trace(t) == []
        retires = {e["rid"]: e["ttft_ms"] for e in mem.events
                   if e["type"] == "request_retire"}
        for rid, ttft in retires.items():
            d = ttft_decomposition(traces[rid])
            parts = (d["ttft_queue_ms"] + d["ttft_prefill_ms"]
                     + d["ttft_ship_ms"] + d["ttft_decode_wait_ms"])
            assert abs(parts - ttft) <= TTFT_SUM_TOLERANCE_MS


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded_and_oldest_first(self):
        bus = tel.TelemetryBus(run_id="ring",
                               recorder=FlightRecorder(capacity=8))
        for i in range(20):
            bus.emit("step", step=i, step_ms=1.0)
        snap = bus.recorder.snapshot()
        assert len(bus.recorder) == 8 and len(snap) == 8
        assert [e["step"] for e in snap] == list(range(12, 20))

    def test_memory_only_bus_never_dumps(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)   # any leak would be visible here
        bus = tel.TelemetryBus(run_id="memonly", sinks=[tel.MemorySink()])
        bus.emit("step", step=0, step_ms=1.0)
        assert maybe_dump_flight_record(bus, "replica_fence:test") is None
        assert maybe_dump_flight_record(None, "whatever") is None
        assert not list(tmp_path.glob("postmortem_*.jsonl"))

    def test_file_backed_bus_dumps_schema_valid_bundle(self, tmp_path):
        bus = tel.TelemetryBus(
            run_id="fr", sinks=[tel.JsonlSink(str(tmp_path / "s.jsonl"))],
            recorder=FlightRecorder(capacity=8))
        for i in range(12):
            bus.emit("step", step=i, step_ms=1.0)
        path = maybe_dump_flight_record(bus, "migrate_refused", step=12)
        assert path is not None and os.path.exists(path)
        lines = load_jsonl(path)
        assert lines[0]["type"] == "postmortem"
        assert lines[0]["reason"] == "migrate_refused"
        assert [e["step"] for e in lines[1:]] == list(range(4, 12))
        validate_events(lines)

    def test_replica_fence_dumps_the_fenced_ring(self, serving_params,
                                                 tmp_path):
        chaos = ChaosTransport(LocalTransport(),
                               schedule={("ping", "drop"): {1}})
        bus = tel.TelemetryBus(
            run_id="fence-dump",
            sinks=[tel.JsonlSink(str(tmp_path / "fleet.jsonl"))])
        clock = SimClock()
        reps = [ReplicaProxy(f"r{i}",
                             _factory(serving_params, clock,
                                      telemetry=bus))
                for i in range(2)]
        fleet = FleetRouter(reps, telemetry=bus, transport=chaos)
        fleet.warmup()
        for i in range(3):
            fleet.submit([2 + i, 5, 9], max_new_tokens=3)
        fleet.run()
        assert reps[0].state == FENCED
        bundles = sorted(tmp_path.glob("postmortem_*.jsonl"))
        assert bundles
        header = load_jsonl(str(bundles[0]))[0]
        assert header["reason"].startswith("replica_fence:")

    def test_recovery_exhaustion_dumps_before_reraise(
            self, serving_params, tmp_path):
        bus = tel.TelemetryBus(
            run_id="exhaust",
            sinks=[tel.JsonlSink(str(tmp_path / "e.jsonl"))])
        eng = _factory(serving_params, SimClock(), telemetry=bus,
                       max_recoveries=0)()
        eng.warmup()
        eng.submit(list(PROMPT), max_new_tokens=4)

        def boom(event, info):
            if event == "decode":
                raise DeviceLossError([0], "chaos")

        prev = set_fault_hook(boom)
        try:
            with pytest.raises(DeviceLossError):
                eng.run()
        finally:
            set_fault_hook(prev)
        bundles = sorted(tmp_path.glob("postmortem_*.jsonl"))
        assert bundles
        header = load_jsonl(str(bundles[0]))[0]
        assert header["reason"] == "recovery_exhausted:DeviceLossError"


# ---------------------------------------------------------------------------
# Trace CLI exit codes
# ---------------------------------------------------------------------------


def _write_stream(path, events):
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
    return str(path)


class TestTraceCli:
    def test_exit_1_on_orphan(self, tmp_path, capsys):
        events = _shipped_request_events()
        events.append(_span_ev(f"{_RID}:kv_import:9", "kv_import",
                               5.0, 6.0, parent="never-emitted"))
        path = _write_stream(tmp_path / "orphan.jsonl", events)
        assert tel_main(["trace", path]) == 1
        assert "PROBLEM" in capsys.readouterr().out

    def test_exit_1_on_decomposition_sum_mismatch(self, tmp_path, capsys):
        events = _shipped_request_events()
        events.append({"type": "request_retire", "rid": _RID,
                       "reason": "length", "new_tokens": 4,
                       "preemptions": 0, "ttft_ms": 9999.0})
        path = _write_stream(tmp_path / "mismatch.jsonl", events)
        assert tel_main(["trace", path]) == 1
        assert "sums to" in capsys.readouterr().out

    def test_exit_0_splits_streams_any_which_way(self, tmp_path):
        """The same events split across per-replica files reconstruct
        identically — including the retire record living in a
        DIFFERENT file than the spans it corroborates."""
        events = _shipped_request_events()
        retire = {"type": "request_retire", "rid": _RID,
                  "reason": "length", "new_tokens": 4,
                  "preemptions": 0, "ttft_ms": 6500.0}
        a = _write_stream(tmp_path / "p0.jsonl", events[::2])
        b = _write_stream(tmp_path / "d0.jsonl",
                          events[1::2] + [retire])
        assert run_trace_cli([a, b], echo=lambda *_: None) == 0
        assert run_trace_cli([b, a], echo=lambda *_: None) == 0

    def test_exit_2_on_unreadable_stream(self, tmp_path):
        assert run_trace_cli([str(tmp_path / "nope.jsonl")],
                             echo=lambda *_: None) == 2

    def test_exit_2_on_unknown_rid(self, tmp_path):
        path = _write_stream(tmp_path / "s.jsonl",
                             _shipped_request_events())
        assert run_trace_cli([path], rid=123,
                             echo=lambda *_: None) == 2
        assert run_trace_cli([path], rid=_RID,
                             echo=lambda *_: None) == 0

    def test_torn_tail_stream_still_joins(self, tmp_path):
        path = _write_stream(tmp_path / "torn.jsonl",
                             _shipped_request_events())
        with open(path, "a") as f:
            f.write('{"type": "span", "rid"')   # the crash mid-line
        assert run_trace_cli([path], echo=lambda *_: None) == 0


# ---------------------------------------------------------------------------
# Summarize integration
# ---------------------------------------------------------------------------


class TestSummarizeIntegration:
    def test_decomposition_keys_and_diff_rows(self, serving_params):
        _, events = _colocated_run(serving_params)
        s = summarize_events(events)
        assert s["serving_traced_requests"] == 4
        for comp in ("queue", "prefill", "ship", "decode_wait"):
            assert f"serving_ttft_{comp}_ms" in s
        assert s["serving_ttft_ship_ms"] == 0.0
        assert "ttft split" in format_summary(s)
        diff = format_diff(s, s)
        assert "ttft queue" in diff and "ttft ship" in diff


# ---------------------------------------------------------------------------
# Multi-seed chaos grid (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestChaosGrid:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_traces_complete_under_randomized_faults(self, serving_params,
                                                     seed):
        """Whatever a seeded fault mix does to the wire — drops,
        delays, duplicates, corruption — every request finishes and
        its trace reconstructs complete with a summing decomposition."""
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id=f"grid-{seed}", sinks=[mem])
        rates = {("kv_page", f): 0.12 for f in
                 ("drop", "delay", "duplicate", "corrupt")}
        rates.update({("kv_commit", "drop"): 0.1,
                      ("migrate", "drop"): 0.1})
        chaos = ChaosTransport(LocalTransport(), rates=rates, seed=seed,
                               telemetry=bus)
        fleet, _ = _disagg(serving_params, telemetry=bus,
                           factory_kw={"telemetry": bus},
                           transport=chaos, fault_retries=3)
        fleet.warmup()
        rids = [fleet.submit([2 + i, 5, 9, 4 + i, 7], max_new_tokens=4)
                for i in range(6)]
        fleet.run()
        for rid in rids:
            assert fleet.handles[rid].finish_reason in ("eos", "length")
        retires = {e["rid"]: e["ttft_ms"] for e in mem.events
                   if e["type"] == "request_retire"}
        traces = build_traces(mem.events)
        assert set(traces) >= set(rids)
        for rid in rids:
            assert validate_trace(traces[rid]) == []
            d = ttft_decomposition(traces[rid])
            parts = (d["ttft_queue_ms"] + d["ttft_prefill_ms"]
                     + d["ttft_ship_ms"] + d["ttft_decode_wait_ms"])
            assert abs(parts - retires[rid]) <= TTFT_SUM_TOLERANCE_MS

"""Disaggregated prefill/decode over the transport seam (ISSUE 18).

THE acceptance pin lives here: a prefill tier ships finished KV pages
to a decode tier over a lossy wire — every message class (ping /
migrate / kv_page / kv_commit) crossed with every injected fault
(drop / delay / duplicate / reorder / corrupt) lands on its documented
outcome (retry, dedupe, CRC re-request, fence, or local-prefill
fallback), token streams stay bitwise identical to a colocated
single-engine control, and zero requests are dropped.  The happy path
is additionally compile-free on every replica (warmup built the
import executable too).
"""

import json
import random

import pytest

import apex_tpu.telemetry as tel
from apex_tpu.analysis import hot_path_guard
from apex_tpu.resilience.chaos import KillReplica
from apex_tpu.serving import (ServingEngine, ServingModelConfig, SimClock,
                              SpecConfig, init_params)
from apex_tpu.serving.engine import AdmissionRefused
from apex_tpu.serving.fleet import (FENCED, ChaosTransport, DisaggRouter,
                                    FleetCapacityError, FleetRouter,
                                    LocalTransport, PageImporter,
                                    ReplicaProxy, TransportCorruption,
                                    register_error)
from apex_tpu.serving.fleet.transport import FAULTS
from apex_tpu.serving.kv_cache import verify_page_payload
from apex_tpu.telemetry.summarize import summarize_events

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


def _disagg(params, *, n_prefill=1, n_decode=1, telemetry=None,
            clock=None, factory_kw=None, prefill_kw=None, decode_kw=None,
            **router_kw):
    """Role-split fleet: ``p*`` replicas are prefill-only, ``d*``
    replicas warm the page-import executable."""
    clock = clock if clock is not None else SimClock()
    pkw = dict(factory_kw or {})
    pkw.update(prefill_kw or {})
    dkw = dict(factory_kw or {})
    dkw.update(decode_kw or {})
    reps = [ReplicaProxy(f"p{i}",
                         _factory(params, clock, prefill_only=True, **pkw),
                         role="prefill")
            for i in range(n_prefill)]
    reps += [ReplicaProxy(f"d{i}",
                          _factory(params, clock, kv_import=True, **dkw),
                          role="decode")
             for i in range(n_decode)]
    return DisaggRouter(reps, telemetry=telemetry, **router_kw), reps


def _prompts(n, seed=0, lo=4, hi=10):
    rng = random.Random(seed)
    return [[rng.randrange(1, CFG.vocab_size)
             for _ in range(rng.randrange(lo, hi))] for _ in range(n)]


def _control_streams(params, prompts, max_new=5, **kw):
    """Uninterrupted colocated control: same prompts in the same
    submit order on one plain engine."""
    eng = _factory(params, SimClock(), **kw)()
    eng.warmup()
    for p in prompts:
        eng.submit(list(p), max_new_tokens=max_new)
    eng.run()
    return {r.rid: list(r.generated) for r in eng.sched.finished}


def _shipment(params, clock, prompt, max_new=5, **kw):
    """Run one prompt through a prefill-only engine and export it:
    returns ``(record, pages_payload, kv_len)``."""
    eng = _factory(params, clock, prefill_only=True, **kw)()
    eng.warmup()
    req = eng.submit(list(prompt), max_new_tokens=max_new)
    eng.step()
    assert req.prefill_pos is None and req.generated
    return eng.export_request(req.rid)


# ---------------------------------------------------------------------------
# The transport seam itself
# ---------------------------------------------------------------------------


class TestTransportSeam:
    def test_pipeline_roundtrip_mints_fresh_msg_ids(self):
        t = LocalTransport()
        seen = []
        t.register("d", "echo",
                   lambda p: (seen.append(p["x"]) or {"x": p["x"]}))
        assert t.call("d", "echo", {"x": 1}) == {"x": 1}
        assert t.call("d", "echo", {"x": 2}) == {"x": 2}
        assert seen == [1, 2]
        w1 = json.loads(t.serialize("d", "echo", {}))
        w2 = json.loads(t.serialize("d", "echo", {}))
        assert w1["msg_id"] != w2["msg_id"]

    def test_duplicate_wire_message_processes_once(self):
        t = LocalTransport()
        hits = []
        t.register("d", "bump",
                   lambda p: (hits.append(1) or {"hits": len(hits)}))
        wire = t.serialize("d", "bump", {})
        r1 = t.deliver(wire)
        r2 = t.deliver(wire)           # the duplicated copy
        assert r1 == r2 and len(hits) == 1

    def test_envelope_crc_catches_in_flight_tamper(self):
        t = LocalTransport()
        t.register("d", "echo", lambda p: {"ok": True})
        env = json.loads(t.serialize("d", "echo", {"x": 1}))
        env["payload"]["x"] = 2        # mutate without re-stamping
        reply = t.deliver(json.dumps(env))
        with pytest.raises(TransportCorruption, match="CRC"):
            t.deserialize_reply(reply)

    def test_registered_errors_cross_typed(self):
        class ProbeFailed(RuntimeError):
            pass

        register_error(ProbeFailed)
        t = LocalTransport()

        def boom(p):
            raise ProbeFailed("pop")

        t.register("d", "boom", boom)
        with pytest.raises(ProbeFailed, match="pop"):
            t.call("d", "boom", {})

    def test_unregistered_handler_error_propagates_raw(self):
        t = LocalTransport()

        def bug(p):
            raise ValueError("handler bug")

        t.register("d", "bug", bug)
        # a handler BUG must not be laundered into a retryable reply
        with pytest.raises(ValueError, match="handler bug"):
            t.call("d", "bug", {})

    def test_missing_handler_is_loud(self):
        with pytest.raises(KeyError, match="no handler"):
            LocalTransport().call("d", "nope", {})

    def test_reorder_never_fires_on_control_classes(self):
        chaos = ChaosTransport(LocalTransport(),
                               schedule={("ping", "reorder"): {1, 2},
                                         ("migrate", "reorder"): {1}})
        chaos.register("d", "ping", lambda p: {"pong": True})
        chaos.register("d", "migrate", lambda p: {"ok": True})
        for _ in range(2):
            assert chaos.call("d", "ping", {})["pong"]
        assert chaos.call("d", "migrate", {})["ok"]
        # request-reply classes are ordered by construction: the armed
        # cells are documented no-ops and must not inject anything
        assert chaos.injected == {}


# ---------------------------------------------------------------------------
# Chaos matrix — control plane (ping / migrate)
# ---------------------------------------------------------------------------


class TestControlPlaneChaosMatrix:
    @pytest.mark.parametrize("fault,cause", [
        ("drop", "transport_timeout"),
        ("delay", "transport_timeout"),
        ("corrupt", "transport_corruption"),
    ])
    def test_ping_fault_fences_and_work_reroutes(self, serving_params,
                                                 fault, cause):
        """A lost / late / corrupted health probe is indistinguishable
        from a dead replica: fence on the spot, migrate, streams stay
        bitwise."""
        prompts = _prompts(4, seed=1)
        control = _control_streams(serving_params, prompts)
        chaos = ChaosTransport(LocalTransport(),
                               schedule={("ping", fault): {1}})
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id=f"ping-{fault}", sinks=[mem])
        fleet, reps = _fleet(serving_params, n=2, telemetry=bus,
                             transport=chaos)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=5)
        fleet.run()
        assert reps[0].state == FENCED
        fences = [e for e in mem.events if e["type"] == "replica_fence"]
        assert [f["cause"] for f in fences] == [cause]
        assert chaos.injected == {f"ping:{fault}": 1}
        assert len(fleet.handles) == len(prompts)
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"

    def test_ping_duplicate_is_harmless(self, serving_params):
        prompts = _prompts(3, seed=2)
        control = _control_streams(serving_params, prompts, max_new=3)
        chaos = ChaosTransport(LocalTransport(),
                               schedule={("ping", "duplicate"): {1}})
        fleet, reps = _fleet(serving_params, n=2, transport=chaos)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=3)
        fleet.run()
        assert all(r.healthy for r in reps)     # nobody fenced
        assert chaos.injected == {"ping:duplicate": 1}
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks

    @pytest.mark.parametrize("fault", ["drop", "delay", "corrupt",
                                       "duplicate"])
    def test_migrate_fault_retries_dedupe_and_stay_bitwise(
            self, serving_params, fault):
        """Migration snapshots survive every wire fault: loss and
        corruption cost an immediate retry; a delayed-but-processed
        shipment's retry hits the rid-dedupe; a duplicated wire
        message hits the msg-id memo.  Nothing adopts twice, streams
        stay bitwise, zero drops."""
        prompts = _prompts(4, seed=3)
        control = _control_streams(serving_params, prompts)
        chaos = ChaosTransport(LocalTransport(),
                               schedule={("migrate", fault): {1}})
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id=f"mig-{fault}", sinks=[mem])
        fleet, reps = _fleet(serving_params, n=2, telemetry=bus,
                             transport=chaos, fault_retries=1)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=5)
        with KillReplica("r0", at_step=2):
            fleet.run()
        assert reps[0].state == FENCED
        assert chaos.injected == {f"migrate:{fault}": 1}
        moves = [e for e in mem.events if e["type"] == "request_migrate"]
        rids = [e["rid"] for e in moves]
        assert moves and len(rids) == len(set(rids))   # one hop per rid
        assert len(fleet.handles) == len(prompts)
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"

    def test_migrate_budget_exhaustion_is_loud(self, serving_params):
        """Control-plane operations have no fallback tier: a migrate
        that outlives its retry budget raises instead of silently
        dropping the snapshot."""
        chaos = ChaosTransport(LocalTransport(),
                               rates={("migrate", "drop"): 1.0})
        fleet, _ = _fleet(serving_params, n=2, transport=chaos,
                          fault_retries=1)
        fleet.warmup()
        for p in _prompts(4, seed=4):
            fleet.submit(p, max_new_tokens=5)
        with KillReplica("r0", at_step=2):
            with pytest.raises(RuntimeError, match="failed after"):
                fleet.run()


# ---------------------------------------------------------------------------
# Disaggregated serving — the happy path
# ---------------------------------------------------------------------------


class TestDisaggServing:
    def test_streams_bitwise_and_compile_free(self, serving_params):
        prompts = _prompts(6, seed=20)
        control = _control_streams(serving_params, prompts)
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="disagg", sinks=[mem])
        fleet, reps = _disagg(serving_params, n_prefill=2, n_decode=2,
                              telemetry=bus)
        fleet.warmup()
        rids = [fleet.submit(p, max_new_tokens=5) for p in prompts]
        # intake lands on the prefill tier only
        assert all(fleet.placement[r].startswith("p") for r in rids)
        with hot_path_guard("disagg serve", transfers=None) as g:
            fleet.run()
        # decode replicas never compile for adopted work: warmup
        # already built the import executable alongside the decode set
        assert g.recompiles == 0 and g.syncs == []
        ships = [e for e in mem.events if e["type"] == "kv_ship"]
        assert len(ships) == len(prompts)
        assert all(e["attempts"] == 0 and e["payload_bytes"] > 0
                   and e["pages"] >= 1 for e in ships)
        assert {e["from_replica"] for e in ships} <= {"p0", "p1"}
        # transfer-aware placement spreads the burst over BOTH decode
        # replicas instead of serializing behind one batch
        assert {e["to_replica"] for e in ships} == {"d0", "d1"}
        assert not [e for e in mem.events
                    if e["type"] == "kv_ship_fallback"]
        # ownership moved wholesale: requests finish on the decode
        # tier, prefill replicas end empty
        assert all(fleet.placement[r].startswith("d") for r in rids)
        assert all(r.queue_depth() + r.running() == 0 for r in reps[:2])
        assert len(fleet.handles) == len(prompts)
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"

    def test_quantized_pool_ships_scale_planes(self, serving_params):
        """int8 pools ship codes AND scales; the decode replica's
        stream matches the quantized colocated control bitwise."""
        prompts = _prompts(4, seed=21)
        control = _control_streams(serving_params, prompts,
                                   kv_quant="int8")
        fleet, _ = _disagg(serving_params,
                           factory_kw={"kv_quant": "int8"})
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=5)
        fleet.run()
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"

    def test_mixed_only_fleet_matches_base_router(self, serving_params):
        """A DisaggRouter over mixed replicas is the r16 router: no
        role to split on, nothing ships."""
        prompts = _prompts(4, seed=22)
        control = _control_streams(serving_params, prompts)
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="mixed", sinks=[mem])
        clock = SimClock()
        reps = [ReplicaProxy(f"r{i}", _factory(serving_params, clock))
                for i in range(2)]
        fleet = DisaggRouter(reps, telemetry=bus)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=5)
        fleet.run()
        assert not [e for e in mem.events if e["type"] == "kv_ship"]
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks

    def test_role_validation_is_loud(self, serving_params):
        clock = SimClock()
        with pytest.raises(ValueError, match="unknown replica role"):
            ReplicaProxy("x", _factory(serving_params, clock),
                         role="oracle")
        pre = [ReplicaProxy("p0", _factory(serving_params, clock,
                                           prefill_only=True),
                            role="prefill")]
        with pytest.raises(ValueError, match="decode-capable"):
            DisaggRouter(pre)
        dec = [ReplicaProxy("d0", _factory(serving_params, clock,
                                           kv_import=True),
                            role="decode")]
        with pytest.raises(ValueError, match="prefill-capable"):
            DisaggRouter(dec)

    def test_decode_tier_loss_is_loud(self, serving_params):
        fleet, reps = _disagg(serving_params)
        fleet.warmup()
        fleet.submit(_prompts(1, seed=23)[0], max_new_tokens=3)
        reps[1].fence()                 # the only decode replica dies
        with pytest.raises(RuntimeError, match="decode tier"):
            fleet.run()

    def test_migration_never_targets_prefill_replicas(self,
                                                      serving_params):
        """A decode replica dying mid-decode migrates its adopted work
        to the OTHER decode replica — never onto the prefill tier,
        whose engines would queue it forever."""
        prompts = _prompts(4, seed=24, lo=8, hi=12)
        control = _control_streams(serving_params, prompts, max_new=6)
        fleet, reps = _disagg(serving_params, n_prefill=1, n_decode=2,
                              fault_retries=0)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=6)
        with KillReplica("d0", at_step=4):
            fleet.run()
        assert reps[1].state == FENCED          # d0
        assert all(not v.startswith("p")
                   for v in fleet.placement.values())
        assert len(fleet.handles) == len(prompts)
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"


# ---------------------------------------------------------------------------
# The export / adopt seam on the engines
# ---------------------------------------------------------------------------


class TestExportAdoptSeam:
    def test_export_then_adopt_continues_bitwise(self, serving_params):
        clock = SimClock()
        prompt = _prompts(1, seed=30, lo=10, hi=11)[0]
        control = _control_streams(serving_params, [prompt], max_new=6)
        record, pages, kv_len = _shipment(serving_params, clock, prompt,
                                          max_new=6)
        assert len(pages) >= 1 and kv_len >= len(prompt)
        for p in pages:
            assert verify_page_payload(p)
        dec = ReplicaProxy("d0", _factory(serving_params, clock,
                                          kv_import=True), role="decode")
        dec.warmup()
        adopted = dec.engine.adopt_prefilled(record, pages, kv_len)
        assert dec.find_request(adopted.rid) is adopted
        dec.engine.run()
        assert list(adopted.generated) == control[adopted.rid]

    def test_export_releases_the_prefill_footprint(self, serving_params):
        clock = SimClock()
        eng = _factory(serving_params, clock, prefill_only=True)()
        eng.warmup()
        free0 = eng.cache.pages_free
        req = eng.submit(_prompts(1, seed=31)[0], max_new_tokens=4)
        eng.step()
        assert eng.cache.pages_free < free0
        eng.export_request(req.rid)
        assert req.finish_reason == "shipped"
        # shipped is NOT a local completion: it retires for real on
        # the decode replica
        assert req not in eng.sched.finished
        assert eng.cache.pages_free == free0

    def test_corrupted_page_is_never_adopted(self, serving_params):
        clock = SimClock()
        record, pages, kv_len = _shipment(
            serving_params, clock, _prompts(1, seed=32, lo=10, hi=11)[0])
        pages[0] = dict(pages[0], k="BBBB" + pages[0]["k"][4:])
        assert not verify_page_payload(pages[0])
        dec = _factory(serving_params, clock, kv_import=True)()
        dec.warmup()
        free0 = dec.cache.pages_free
        with pytest.raises(ValueError, match="CRC"):
            dec.adopt_prefilled(record, pages, kv_len)
        # atomic refusal: no request admitted, no page allocated
        assert not dec.sched.running and dec.cache.pages_free == free0

    def test_adopt_validation_is_loud(self, serving_params):
        clock = SimClock()
        record, pages, kv_len = _shipment(
            serving_params, clock, _prompts(1, seed=33, lo=12, hi=13)[0])
        assert len(pages) >= 2
        dec = _factory(serving_params, clock, kv_import=True)()
        dec.warmup()
        with pytest.raises(ValueError, match="page"):
            dec.adopt_prefilled(record, pages[:1], kv_len)
        dec.adopt_prefilled(record, pages, kv_len)
        with pytest.raises(ValueError, match="rid"):
            dec.adopt_prefilled(record, pages, kv_len)

    def test_full_batch_refuses_retryably(self, serving_params):
        clock = SimClock()
        dec = _factory(serving_params, clock, kv_import=True,
                       max_batch=1)()
        dec.warmup()
        for seed in (34, 35):
            record, pages, kv_len = _shipment(
                serving_params, clock,
                _prompts(1, seed=seed, lo=10, hi=11)[0])
            record = dict(record, rid=seed)
            if seed == 34:
                dec.adopt_prefilled(record, pages, kv_len)
            else:
                # capacity is retryable (AdmissionRefused), unlike the
                # ValueError validation failures above
                with pytest.raises(AdmissionRefused):
                    dec.adopt_prefilled(record, pages, kv_len)

    def test_quantized_export_carries_scale_planes(self, serving_params):
        clock = SimClock()
        _, pages, _ = _shipment(serving_params, clock,
                                _prompts(1, seed=36, lo=10, hi=11)[0],
                                kv_quant="int8")
        for p in pages:
            assert {"k", "v", "crc_k", "crc_v",
                    "k_scale", "v_scale"} <= set(p)
            assert verify_page_payload(p)
            # a tampered SCALE plane fails the same CRC
            assert not verify_page_payload(
                dict(p, k_scale="BBBB" + p["k_scale"][4:]))


# ---------------------------------------------------------------------------
# The receiver: idempotency + resume
# ---------------------------------------------------------------------------


class TestPageImporter:
    def _rig(self, serving_params, seed=40):
        clock = SimClock()
        record, pages, kv_len = _shipment(
            serving_params, clock,
            _prompts(1, seed=seed, lo=12, hi=13)[0])
        assert len(pages) >= 2
        rep = ReplicaProxy("d0", _factory(serving_params, clock,
                                          kv_import=True), role="decode")
        rep.warmup()
        imp = PageImporter(rep)
        tid = f"t{record['rid']}"

        def page(i, data=None):
            return imp.on_page({"transfer_id": tid, "page_index": i,
                                "n_pages": len(pages),
                                "data": data or pages[i]})

        def commit():
            return imp.on_commit({"transfer_id": tid, "record": record,
                                  "kv_len": kv_len,
                                  "n_pages": len(pages)})

        return rep, imp, pages, page, commit

    def test_missing_pages_resume_not_restart(self, serving_params):
        rep, imp, pages, page, commit = self._rig(serving_params)
        assert page(0) == {"ok": True}
        r = commit()
        assert r["ok"] is False and r["reason"] == "missing_pages"
        assert r["missing"] == list(range(1, len(pages)))
        for i in r["missing"]:          # re-ship exactly the gaps
            assert page(i) == {"ok": True}
        assert commit()["ok"] is True
        assert rep.find_request(int(r.get("rid", 0)) or 0) is not None

    def test_commit_reply_is_memoized(self, serving_params):
        rep, imp, pages, page, commit = self._rig(serving_params, seed=41)
        for i in range(len(pages)):
            page(i)
        r1 = commit()
        assert r1["ok"] is True
        # a retried / duplicated commit returns the memoized success —
        # it cannot double-admit
        assert commit() == r1
        assert len(rep.engine.sched.running) == 1
        # a straggler page after commit is a no-op too
        assert page(0) == {"ok": True}

    def test_duplicate_page_is_a_noop(self, serving_params):
        rep, imp, pages, page, commit = self._rig(serving_params, seed=42)
        assert page(0) == {"ok": True}
        assert page(0) == {"ok": True}
        for i in range(1, len(pages)):
            page(i)
        assert commit()["ok"] is True

    def test_corrupt_page_refused_and_not_buffered(self, serving_params):
        rep, imp, pages, page, commit = self._rig(serving_params, seed=43)
        bad = dict(pages[0], k="BBBB" + pages[0]["k"][4:])
        r = page(0, data=bad)
        assert r == {"ok": False, "reason": "crc_mismatch",
                     "page_index": 0}
        for i in range(1, len(pages)):
            page(i)
        # the refused page never entered the buffer: the commit still
        # reports it missing until a clean copy lands
        assert commit()["missing"] == [0]
        assert page(0) == {"ok": True}
        assert commit()["ok"] is True


# ---------------------------------------------------------------------------
# Chaos matrix — data plane (kv_page / kv_commit)
# ---------------------------------------------------------------------------


#: (message class, fault) -> the retry reasons the shipment layer is
#: allowed to book for it (empty = absorbed with no transfer retry).
DATA_PLANE_CELLS = [
    ("kv_page", "drop", {"timeout"}),
    ("kv_page", "delay", {"timeout"}),
    ("kv_page", "duplicate", set()),
    ("kv_page", "reorder", set()),
    ("kv_page", "corrupt", {"crc_mismatch"}),
    ("kv_commit", "drop", {"timeout"}),
    ("kv_commit", "delay", {"timeout"}),
    ("kv_commit", "duplicate", set()),
    ("kv_commit", "corrupt", {"corrupt"}),
]


class TestDataPlaneChaosMatrix:
    @pytest.mark.parametrize("cls,fault,reasons", DATA_PLANE_CELLS,
                             ids=[f"{c}-{f}"
                                  for c, f, _ in DATA_PLANE_CELLS])
    def test_shipment_survives(self, serving_params, cls, fault, reasons):
        prompts = _prompts(3, seed=25)
        control = _control_streams(serving_params, prompts)
        chaos = ChaosTransport(LocalTransport(),
                               schedule={(cls, fault): {1}})
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id=f"{cls}-{fault}", sinks=[mem])
        fleet, reps = _disagg(serving_params, telemetry=bus,
                              transport=chaos)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=5)
        fleet.run()
        assert chaos.injected == {f"{cls}:{fault}": 1}
        got = {e["reason"] for e in mem.events
               if e["type"] == "kv_ship_retry"}
        assert got == reasons
        assert not [e for e in mem.events
                    if e["type"] == "kv_ship_fallback"]
        ships = [e for e in mem.events if e["type"] == "kv_ship"]
        assert len(ships) == len(prompts)
        # exactly one adoption per request, even under delay/duplicate
        assert len(reps[1].engine.sched.finished) == len(prompts)
        assert len(fleet.handles) == len(prompts)
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"


# ---------------------------------------------------------------------------
# Degradation: retry budget, capacity, destination loss
# ---------------------------------------------------------------------------


class TestShipmentDegradation:
    def test_budget_exhaustion_falls_back_to_local_prefill(
            self, serving_params):
        """Every kv_page lost forever: past the budget the request
        migrates to the decode replica and re-prefills LOCALLY —
        slower, still bitwise, zero drops."""
        prompts = _prompts(3, seed=26)
        control = _control_streams(serving_params, prompts)
        chaos = ChaosTransport(LocalTransport(),
                               rates={("kv_page", "drop"): 1.0})
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="fallback", sinks=[mem])
        fleet, _ = _disagg(serving_params, telemetry=bus,
                           factory_kw={"telemetry": bus},
                           transport=chaos, fault_retries=1)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=5)
        fleet.run()
        falls = [e for e in mem.events if e["type"] == "kv_ship_fallback"]
        assert len(falls) == len(prompts)
        assert all(e["reason"] == "timeout" for e in falls)
        assert not [e for e in mem.events if e["type"] == "kv_ship"]
        assert len(fleet.handles) == len(prompts)
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"
        s = summarize_events(mem.events)
        assert s["serving_ship_fallback_rate"] == 1.0
        assert s["serving_ship_success_rate"] == 0.0

    def test_no_capacity_backs_off_until_a_slot_frees(self,
                                                      serving_params):
        """A full decode batch is a capacity refusal, not a failure:
        the sender backs off into the SAME buffered pages and lands
        once a slot frees."""
        prompts = _prompts(3, seed=27)
        control = _control_streams(serving_params, prompts, max_new=3)
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="capacity", sinks=[mem])
        fleet, _ = _disagg(serving_params, telemetry=bus,
                           decode_kw={"max_batch": 1}, fault_retries=5)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=3)
        fleet.run()
        retries = [e for e in mem.events if e["type"] == "kv_ship_retry"]
        assert retries and {e["reason"] for e in retries} == \
            {"no_capacity"}
        assert not [e for e in mem.events
                    if e["type"] == "kv_ship_fallback"]
        ships = [e for e in mem.events if e["type"] == "kv_ship"]
        assert len(ships) == len(prompts)
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"

    def test_destination_fence_retargets_the_transfer(self,
                                                      serving_params):
        """The decode destination dying mid-transfer retargets the
        shipment to a live decode replica from scratch."""
        prompt = _prompts(1, seed=28)[0]
        control = _control_streams(serving_params, [prompt], max_new=4)
        chaos = ChaosTransport(LocalTransport(),
                               rates={("kv_page", "drop"): 1.0})
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="retarget", sinks=[mem])
        fleet, reps = _disagg(serving_params, n_decode=2, telemetry=bus,
                              transport=chaos, fault_retries=20)
        fleet.warmup()
        rid = fleet.submit(prompt, max_new_tokens=4)
        for _ in range(3):
            fleet.step()
        assert fleet._transfers[rid].dst == "d0"
        reps[1].fence()                 # d0 dies mid-transfer
        chaos.rates.clear()             # the wire heals
        fleet.run()
        ships = [e for e in mem.events if e["type"] == "kv_ship"]
        assert [e["to_replica"] for e in ships] == ["d1"]
        assert fleet.placement[rid] == "d1"
        assert fleet.handles[rid].generated == control[rid]


# ---------------------------------------------------------------------------
# Everything at once
# ---------------------------------------------------------------------------


def _data_plane_rates(p):
    return {(cls, fault): p
            for cls in ("migrate", "kv_page", "kv_commit")
            for fault in FAULTS}


class TestChaosEverything:
    def test_all_faults_armed_streams_stay_bitwise(self, serving_params):
        """The tentpole pin: every fault class armed on every data-
        plane message class at once, plus scheduled control-plane
        faults (a prefill replica fences mid-run) — streams bitwise,
        zero drops, every r18 event schema-valid."""
        prompts = _prompts(8, seed=18, lo=6, hi=14)
        control = _control_streams(serving_params, prompts, max_new=6)
        chaos = ChaosTransport(
            LocalTransport(), seed=7,
            rates=_data_plane_rates(0.15),
            schedule={("ping", "drop"): {9},
                      ("ping", "duplicate"): {3},
                      ("ping", "reorder"): {5}})
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="chaos-disagg", sinks=[mem])
        chaos.telemetry = bus
        fleet, reps = _disagg(serving_params, n_prefill=2, n_decode=2,
                              telemetry=bus, transport=chaos,
                              fault_retries=3)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=6)
        fleet.run()
        assert chaos.injected            # chaos actually happened
        # the 9th ping (round 3, first probe) fenced prefill replica p0
        fences = [e for e in mem.events if e["type"] == "replica_fence"]
        assert [f["replica"] for f in fences] == ["p0"]
        for e in mem.events:
            if e["type"] in ("kv_ship", "kv_ship_retry",
                             "kv_ship_fallback", "fault_injected",
                             "request_migrate", "replica_fence"):
                tel.validate_event(e)
        assert len(fleet.handles) == len(prompts)
        assert all(r.done for r in fleet.handles.values())
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, f"rid {rid}"

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_full_grid_sweep(self, serving_params, seed):
        """The heavy grid: higher data-plane rates across more
        traffic, one scheduled prefill fence, per-seed."""
        prompts = _prompts(10, seed=100 + seed, lo=6, hi=14)
        control = _control_streams(serving_params, prompts, max_new=6)
        # Control-plane faults stay rarer than the data plane: migrate
        # has no fallback tier, so its retry budget must statistically
        # always survive (at 0.15/fault, five consecutive faulted
        # attempts are likely somewhere in a 3-seed grid).
        rates = _data_plane_rates(0.25)
        rates.update({("migrate", f): 0.05 for f in FAULTS})
        chaos = ChaosTransport(LocalTransport(), seed=seed, rates=rates,
                               schedule={("ping", "drop"): {9}})
        fleet, _ = _disagg(serving_params, n_prefill=2, n_decode=2,
                           transport=chaos, fault_retries=4)
        fleet.warmup()
        for p in prompts:
            fleet.submit(p, max_new_tokens=6)
        fleet.run()
        assert len(fleet.handles) == len(prompts)
        for rid, toks in control.items():
            assert fleet.handles[rid].generated == toks, \
                f"seed {seed} rid {rid}"


# ---------------------------------------------------------------------------
# Prefix-affinity placement (r18 satellite)
# ---------------------------------------------------------------------------


class TestPrefixAffinity:
    def test_warm_replica_wins_over_load(self, serving_params):
        fleet, reps = _fleet(
            serving_params, n=2,
            factory_kw={"prefix_sharing": True,
                        "spec": SpecConfig(k=0, chunk_size=8)})
        fleet.warmup()
        stem = [(i % 60) + 1 for i in range(16)]
        rid_a = fleet.submit(list(stem), max_new_tokens=4)
        assert fleet.placement[rid_a] == "r0"
        fleet.run()
        # r0's LOCAL index now holds the 16-token prefix (and retains
        # its pages, so r0 carries a sliver of occupancy); nothing was
        # shipped to r1.  Two cold submissions land one request on
        # each replica, leaving r0 STRICTLY more loaded than r1:
        rid_b1 = fleet.submit(_prompts(1, seed=45)[0], max_new_tokens=8)
        assert fleet.placement[rid_b1] == "r1"   # cold: least-loaded
        rid_b2 = fleet.submit(_prompts(1, seed=46)[0], max_new_tokens=8)
        assert fleet.placement[rid_b2] == "r0"
        assert reps[0].load_score() > reps[1].load_score()
        rid_c = fleet.submit(stem + [7, 8, 9], max_new_tokens=4)
        assert fleet.placement[rid_c] == "r0"    # affinity beats load
        rid_d = fleet.submit(_prompts(1, seed=47)[0], max_new_tokens=4)
        assert fleet.placement[rid_d] == "r1"    # cold: least-loaded
        fleet.run()
        assert fleet.handles[rid_c].prefix_hit

    def test_affinity_off_without_sharing(self, serving_params):
        """No index, no affinity: routing is pure least-loaded, as
        before r18."""
        fleet, _ = _fleet(serving_params, n=2)
        fleet.warmup()
        stem = [(i % 60) + 1 for i in range(16)]
        fleet.submit(list(stem), max_new_tokens=3)
        fleet.run()
        fleet.submit(_prompts(1, seed=47)[0], max_new_tokens=8)
        rid = fleet.submit(list(stem) + [5], max_new_tokens=3)
        assert fleet.placement[rid] == "r1"      # least-loaded only


# ---------------------------------------------------------------------------
# Capacity refusal reporting (r18 satellite)
# ---------------------------------------------------------------------------


class TestCapacityRefusal:
    def test_refusal_reports_the_full_shortfall(self, serving_params):
        """A refused plan names EVERY unplaceable request and the
        required-vs-available page arithmetic — on the exception and
        on the ``migrate_refused`` event.  The shortfall here is queue
        headroom: the survivor's bounded queue (max_queue=1) can adopt
        exactly one of the dead replica's five live requests."""
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="refused", sinks=[mem])
        clock = SimClock()
        reps = [ReplicaProxy("r0", _factory(serving_params, clock)),
                ReplicaProxy("r1", _factory(serving_params, clock,
                                            max_batch=1, max_queue=1))]
        fleet = FleetRouter(reps, telemetry=bus, fault_retries=0)
        fleet.warmup()
        # Headroom-preferring routing fills r1's one queue slot with
        # the second submit, then piles the rest onto r0: 5 vs 1.
        for p in _prompts(6, seed=50, lo=8, hi=10):
            fleet.submit(p, max_new_tokens=5)
        assert sum(1 for n in fleet.placement.values() if n == "r0") == 5
        with KillReplica("r0", at_step=2):
            with pytest.raises(FleetCapacityError) as ei:
                fleet.run()
        err = ei.value
        assert len(err.unplaceable) == 4         # ALL of them, not one
        assert set(err.unplaceable) <= {rid for rid, n in
                                        fleet.placement.items()
                                        if n == "r0"}
        assert err.pages_required > 0 and err.pages_available >= 0
        evs = [e for e in mem.events if e["type"] == "migrate_refused"]
        assert len(evs) == 1
        ev = evs[0]
        tel.validate_event(ev)
        assert ev["replica"] == "r0"
        assert ev["unplaceable"] == list(err.unplaceable)
        assert ev["requests"] == len(err.unplaceable)
        assert ev["pages_required"] == err.pages_required
        assert ev["pages_available"] == err.pages_available


# ---------------------------------------------------------------------------
# Telemetry: schema, summary
# ---------------------------------------------------------------------------


class TestShipTelemetry:
    def _stamp(self, type_, **payload):
        ev = {"type": type_, "run_id": "r", "step": 0, "t": 0.0,
              "ts": 0.0, "mesh": {}}
        ev.update(payload)
        return ev

    def test_new_events_validate(self):
        tel.validate_event(self._stamp(
            "kv_ship", rid=3, from_replica="p0", to_replica="d1",
            pages=4, payload_bytes=8192, attempts=1))
        tel.validate_event(self._stamp(
            "kv_ship_retry", rid=3, from_replica="p0", to_replica="d1",
            attempt=1, reason="timeout", backoff_rounds=2))
        tel.validate_event(self._stamp(
            "kv_ship_retry", rid=3, from_replica="p0", to_replica="d1",
            attempt=0, reason="crc_mismatch"))   # immediate re-send
        tel.validate_event(self._stamp(
            "kv_ship_fallback", rid=3, from_replica="p0",
            to_replica="d1", attempts=3, reason="no_capacity"))
        tel.validate_event(self._stamp(
            "migrate_refused", replica="r0", unplaceable=[4, 5],
            requests=2, pages_required=8, pages_available=3))

    def test_retry_reason_enum_is_closed(self):
        with pytest.raises(tel.schema.SchemaError, match="must be one of"):
            tel.validate_event(self._stamp(
                "kv_ship_retry", rid=3, from_replica="p0",
                to_replica="d1", attempt=1, reason="cosmic_rays"))

    def test_summary_reports_ship_rates(self):
        events = ([{"type": "kv_ship"}] * 3
                  + [{"type": "kv_ship_fallback"}]
                  + [{"type": "request_retire"}] * 4)
        s = summarize_events(events)
        assert s["serving_ship_success_rate"] == 0.75
        assert s["serving_ship_fallback_rate"] == 0.25
        quiet = summarize_events([{"type": "request_retire"}])
        assert quiet["serving_ship_success_rate"] is None
        assert quiet["serving_ship_fallback_rate"] is None

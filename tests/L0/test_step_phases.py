"""Step phases (ISSUE 27): the host-phase spans inside
``ServingEngine.step()`` — ``apex_tpu.telemetry.phase`` records in the
one in-memory ring, on ``time.perf_counter_ns``, and as ``apex:``
``TraceAnnotation``s in a profiler session.  Nothing here reads a
duration for its size: only order, nesting and counts.
"""

import time

import numpy as np
import pytest

import jax

from apex_tpu import telemetry as tel
from apex_tpu.serving import (ServingEngine, ServingModelConfig, SimClock,
                              init_params)
from apex_tpu.serving.spec import SpecConfig
from apex_tpu.telemetry import PHASE_RING, FlightRecorder, phase
from apex_tpu.telemetry import phases as phases_mod

pytestmark = pytest.mark.serving

CFG = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                         num_layers=2, max_position=96)
STEP_CHILDREN = {"engine.prefill", "engine.grow", "engine.decode"}
LEAVES = {"engine.prefill": {"prefill.build", "prefill.dispatch",
                             "prefill.scatter", "prefill.fetch"},
          "engine.decode": {"decode.build", "decode.dispatch",
                            "decode.fetch", "decode.commit"}}


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=0)


def _prompts(n=4):
    return [[int(x) for x in
             np.random.RandomState(100 + i).randint(0, CFG.vocab_size,
                                                    5 + 3 * i)]
            for i in range(n)]


def _engine(params, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("clock", SimClock())
    return ServingEngine(CFG, params, page_size=8, max_batch=4,
                         prefill_budget=CFG.max_position, **kw)


def _run(params, n=4, max_new=10, **kw):
    """A tiny run on an empty ring: (engine, requests, the ring's
    records)."""
    PHASE_RING.clear()
    eng = _engine(params, **kw)
    reqs = [eng.submit(p, max_new) for p in _prompts(n)]
    eng.run()
    return eng, reqs, PHASE_RING.snapshot()


def _bus():
    mem = tel.MemorySink()
    return tel.TelemetryBus(run_id="phases-l0", sinks=[mem]), mem


def _token_times(records):
    """rid -> the time of each of its tokens, from the ring alone: a
    ``prefill.fetch`` is one token of its ``engine.prefill``'s request;
    an ``engine.decode`` is one token (or ``committed[i]``) of each of
    its rows."""
    by_id = {r.id: r for r in records}
    times = {}
    for r in records:
        if r.name == "prefill.fetch":
            rid = by_id[r.parent].attrs["rid"]
            times.setdefault(rid, []).append(r.t_end_ns)
        elif r.name == "engine.decode":
            rids = r.attrs["rids"]
            for rid, n in zip(rids, r.attrs.get("committed",
                                                (1,) * len(rids))):
                times.setdefault(rid, []).extend([r.t_end_ns] * n)
    return times


def test_phases_nest_and_every_parent_exists(params):
    _, _, records = _run(params)
    by_id = {r.id: r for r in records}
    assert len(by_id) == len(records)
    names = {r.name for r in records}
    assert names == {"engine.step"} | STEP_CHILDREN \
        | LEAVES["engine.prefill"] | LEAVES["engine.decode"]
    for r in records:
        if r.name == "engine.step":
            assert r.parent is None
            continue
        parent = by_id[r.parent]        # KeyError: a parent is missing
        if r.name in STEP_CHILDREN:
            assert parent.name == "engine.step"
        else:
            assert r.name in LEAVES[parent.name]
        # a step's phases share the engine's step index
        assert r.step == parent.step is not None
        # a phase with nothing to say keeps no dict alive in the ring
        assert r.attrs is None or r.attrs


def test_children_lie_inside_their_parent_and_self_time_is_not_negative(
        params):
    _, _, records = _run(params)
    by_id = {r.id: r for r in records}
    covered = {}
    for r in records:
        assert r.t_end_ns >= r.t_start_ns
        if r.parent is not None:
            parent = by_id[r.parent]
            assert parent.t_start_ns <= r.t_start_ns
            assert r.t_end_ns <= parent.t_end_ns
            covered[r.parent] = covered.get(r.parent, 0) \
                + r.t_end_ns - r.t_start_ns
    for pid, ns in covered.items():
        parent = by_id[pid]
        assert parent.t_end_ns - parent.t_start_ns - ns >= 0


def test_the_ring_is_bounded_and_drops_the_oldest(monkeypatch):
    assert PHASE_RING.capacity == 65_536
    small = FlightRecorder(4)
    monkeypatch.setattr(phases_mod, "PHASE_RING", small)
    for i in range(7):
        with phase("p", i=i):
            pass
    assert [r.attrs["i"] for r in small.snapshot()] == [3, 4, 5, 6]


def test_a_phase_records_when_its_block_raises_and_unwinds_the_stack():
    PHASE_RING.clear()
    with pytest.raises(ZeroDivisionError):
        with phase("outer", step=7):
            with phase("inner"):
                1 / 0
    with phase("after"):
        pass
    inner, outer, after = PHASE_RING.snapshot()
    assert (inner.name, outer.name) == ("inner", "outer")
    assert inner.parent == outer.id and inner.step == outer.step == 7
    assert after.parent is None and after.step is None
    assert after.attrs is None


def test_n_steps_leave_n_step_records_with_increasing_index(params):
    PHASE_RING.clear()
    eng = _engine(params)
    for p in _prompts(3):
        eng.submit(p, 6)
    n = 0
    while not eng.sched.idle:
        eng.step()
        n += 1
    steps = [r for r in PHASE_RING.snapshot() if r.name == "engine.step"]
    assert len(steps) == n == eng.steps
    assert [r.step for r in steps] == list(range(n))
    assert all(r.attrs["cpu_ns"] >= 0 for r in steps)


def test_decode_record_carries_the_rows_of_the_bus_event_and_the_scheduler(
        params):
    PHASE_RING.clear()
    bus, mem = _bus()
    eng = _engine(params, telemetry=bus)
    for p in _prompts(4):
        eng.submit(p, 8)
    while not eng.sched.idle:
        eng.step()
        assert PHASE_RING.snapshot()[-1].name == "engine.step"
    decodes = [r for r in PHASE_RING.snapshot()
               if r.name == "engine.decode"]
    events = [e for e in mem.events if e["type"] == "decode_step"]
    assert len(decodes) == len(events) > 0
    # a launch a decode step; the last span only lands what is left
    assert sum(r.attrs["rows"] > 0 for r in decodes) == eng.decode_steps
    assert decodes[-1].attrs["rows"] == 0 and decodes[-1].attrs["rids"]
    for rec, ev in zip(decodes, events):
        # rows: what was launched; rids: whose token landed (ISSUE 34)
        assert rec.attrs["rows"] == ev["batch"]
        assert len(rec.attrs["rids"]) == ev["new_tokens"]
        assert rec.attrs["in_flight"] == ev["in_flight"]
        assert rec.step == ev["step"]
    # every launch lands, one span later
    assert [r.attrs["rows"] for r in decodes[:-1]] == \
        [len(r.attrs["rids"]) for r in decodes[1:]]
    assert [r.attrs["in_flight"] for r in decodes] == \
        [0] + [1] * (len(decodes) - 2) + [0]


def test_decode_rids_are_the_rows_whose_token_landed(params):
    PHASE_RING.clear()
    eng = _engine(params)
    reqs = [eng.submit(p, 8) for p in _prompts(4)]
    landed = {r.rid: 1 for r in reqs}       # the first token: the prefill's
    while not eng.sched.idle:
        before = len(PHASE_RING)
        launched = tuple(r.rid for r in eng.sched.running if r.in_flight)
        eng.step()
        new = PHASE_RING.snapshot()[before:]
        decodes = [r for r in new if r.name == "engine.decode"]
        if decodes:
            # what landed is what the launch before carried; rows that
            # finished on it are retired by the next step
            assert decodes[0].attrs["rids"] == launched
            assert set(launched) <= {r.rid for r in eng.sched.running}
            for rid in launched:
                landed[rid] += 1
        assert all(len(r.generated) == landed[r.rid] for r in reqs
                   if r.admit_t is not None)


@pytest.mark.parametrize("mode", ["plain", "spec", "chunked", "preempt"])
def test_token_times_rebuilt_from_the_ring(params, mode):
    kw = {"plain": {}, "spec": {"spec": SpecConfig(k=3)},
          "chunked": {"spec": SpecConfig(k=0, chunk_size=8)},
          # a pool too small for four requests: evict and re-prefill
          "preempt": {"num_pages": 7, "max_pages_per_request": 4}}[mode]
    eng, reqs, records = _run(params, **kw)
    times = _token_times(records)
    for req in reqs:
        assert req.finish_reason == "length"
        ts = times[req.rid]
        assert len(ts) == len(req.generated)
        assert ts == sorted(ts)
        if mode != "spec":
            assert len(set(ts)) == len(ts)
    if mode == "spec":
        assert any("committed" in r.attrs for r in records
                   if r.name == "engine.decode")
    if mode == "preempt":
        assert sum(r.preemptions for r in reqs) > 0
        assert sum(r.attrs["evicted"] for r in records
                   if r.name == "engine.step") > 0


def test_real_plus_padded_prefill_tokens_are_prefills_times_the_row(params):
    eng, reqs, records = _run(params)
    prefills = [r for r in records if r.name == "engine.prefill"]
    assert len(prefills) == len(reqs)
    assert [r.attrs["rid"] for r in prefills] == [q.rid for q in reqs]
    assert [r.attrs["C"] for r in prefills] == [len(q.prompt) for q in reqs]
    real = sum(r.attrs["C"] for r in prefills)
    padded = sum(r.attrs["S"] - r.attrs["C"] for r in prefills)
    assert real + padded == len(prefills) * eng.prefill_budget
    steps = [r for r in records if r.name == "engine.step"]
    assert sum(r.attrs["admitted"] for r in steps) == len(reqs)
    assert sum(r.attrs["retired"] for r in steps) == len(reqs)


def test_the_prefill_spans_S_is_the_width_of_the_row_launched():
    # ISSUE 32: the row is the narrowest rung of the engine's ladder that
    # holds the context, and `S` says which, not `prefill_budget`
    cfg = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                             num_layers=2, max_position=256)
    PHASE_RING.clear()
    eng = ServingEngine(cfg, init_params(cfg, seed=0), num_pages=160,
                        page_size=8, max_batch=4, clock=SimClock())
    launched = []
    inner = eng._prefill_fn

    def spy(p, tokens, *rest):
        launched.append(int(tokens.shape[1]))
        return inner(p, tokens, *rest)

    eng._prefill_fn = spy
    lengths = (7, 128, 129, 200, 192, 250)
    for n in lengths:
        eng.submit([1 + i % 50 for i in range(n)], 4)
    eng.run()
    prefills = [r for r in PHASE_RING.snapshot()
                if r.name == "engine.prefill"]
    assert [r.attrs["C"] for r in prefills] == list(lengths)
    assert [r.attrs["S"] for r in prefills] == launched
    assert launched == [128, 128, 256, 256, 256, 256]
    assert launched == [eng.prefill_width(n) for n in lengths]
    padded = sum(r.attrs["S"] - r.attrs["C"] for r in prefills)
    assert padded < len(prefills) * eng.prefill_budget - sum(lengths)


def test_phase_ms_passes_the_schema_and_sums_to_no_more_than_step_ms(params):
    bus, mem = _bus()
    _, _, records = _run(params, telemetry=bus)
    events = [e for e in mem.events if e["type"] == "decode_step"]
    decodes = [r for r in records if r.name == "engine.decode"]
    assert events
    for ev, rec in zip(events, decodes):
        tel.validate_event(ev)
        # a launch with one before it in flight has all four; the
        # first has nothing to fetch, the last nothing to launch
        want = LEAVES["engine.decode"]
        if not ev["in_flight"]:
            want = ({"decode.build", "decode.dispatch"} if ev["batch"]
                    else {"decode.fetch", "decode.commit"})
        assert set(ev["phase_ms"]) == want
        assert all(v >= 0 for v in ev["phase_ms"].values())
        assert sum(ev["phase_ms"].values()) <= ev["step_ms"] * (1 + 1e-9)
        # one measurement: the bus event is the ring's record
        assert ev["step_ms"] == rec.ms
    with pytest.raises(tel.SchemaError, match="phase_ms"):
        tel.validate_event(dict(events[0], phase_ms=[1.0]))


def test_greedy_streams_are_identical_with_and_without_a_bus(params):
    bus, _ = _bus()
    _, with_bus, _ = _run(params, telemetry=bus)
    _, without, _ = _run(params)
    assert [r.generated for r in with_bus] == [r.generated for r in without]


def test_under_a_sim_clock_the_phases_still_carry_perf_counter_times(params):
    t0 = time.perf_counter_ns()
    eng, reqs, records = _run(params)
    t1 = time.perf_counter_ns()
    assert isinstance(eng.clock, SimClock)
    # the engine's own fields are on the virtual clock ...
    assert all(float(r.finish_t).is_integer() for r in reqs)
    # ... the phases are not
    assert all(t0 <= r.t_start_ns <= r.t_end_ns <= t1 for r in records)
    steps = [r for r in records if r.name == "engine.step"]
    assert all(a.t_end_ns <= b.t_start_ns for a, b in zip(steps, steps[1:]))


def test_apex_events_lie_in_the_host_plane_of_a_profiler_session(
        params, tmp_path):
    """Three steps under a CPU profiler session: the phases are in the
    profiler's own trace, on its clock, under the ``apex:`` prefix."""
    import glob

    from jax.profiler import ProfileData

    eng = _engine(params)
    for p in _prompts(2):
        eng.submit(p, 6)
    eng.step()                               # compile outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    names = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("apex:"):
                    names[ev.name] = names.get(ev.name, 0) + 1
    assert names["apex:engine.step"] == 3
    assert names["apex:engine.decode"] == 3
    assert names["apex:decode.fetch"] == 3
    assert not any(n.startswith("bench:") for n in names)

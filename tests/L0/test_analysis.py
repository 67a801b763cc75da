"""ISSUE 11 — apex_tpu.analysis: project-invariant linter + hot-path
sanitizer.

Four layers, mirroring the package:

1. framework mechanics — suppression comments, baseline match/stale
   accounting, path normalization, the CLI exit-code gate;
2. the rule catalog — every rule has POSITIVE (flags the seeded bug)
   and NEGATIVE (stays quiet on the sanctioned form) fixtures: a rule
   with no negative fixture is a rule that flags everything;
3. the schema satellite — EVENT_TYPES is derived from EVENT_FIELDS
   (drift impossible by construction), optional fields are type-checked
   when present, bool-not-int covers them too;
4. the runtime half — ``hot_path_guard`` pins the serving engine's
   zero-compiles-after-warmup contract and the flagship step's
   steady-state no-recompile/no-host-sync property, each with a
   CONTROL showing the guard actually fires on a seeded violation.

Plus the regression pins for the genuine violations the first lint run
surfaced (guards.py / checkpoint.py broad-except narrowing, the
serving warmup's missing third executable).
"""

import json
import os
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.analysis import (Baseline, HotPathViolation,
                               hot_path_guard, lint_paths, lint_source,
                               normalize_path)
from apex_tpu.analysis.framework import suppressed_lines
from apex_tpu.analysis.rules import (RULES, ExceptionSwallowing,
                                     HostSyncInHotPath, LockDiscipline,
                                     MissingDonation,
                                     TelemetrySchemaDrift,
                                     UnseededNondeterminism)

pytestmark = pytest.mark.analysis

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _lint(src, path="apex_tpu/fixture.py", rule_cls=None):
    rules = [rule_cls()] if rule_cls is not None else None
    return lint_source(textwrap.dedent(src), path, rules)


def _ids(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# framework mechanics
# ---------------------------------------------------------------------------


def test_normalize_path_strips_prefix():
    assert normalize_path("/abs/prefix/apex_tpu/serving/engine.py") == \
        "apex_tpu/serving/engine.py"
    assert normalize_path("apex_tpu/x.py") == "apex_tpu/x.py"
    assert normalize_path("elsewhere/y.py") == "elsewhere/y.py"


def test_suppression_same_line_and_comment_above():
    src = ("x = 1  # lint: disable=HS001\n"
           "# lint: disable=ND001, TL001\n"
           "y = 2\n")
    sup = suppressed_lines(src)
    assert sup[1] == {"HS001"}
    assert sup[2] == {"ND001", "TL001"}
    assert sup[3] == {"ND001", "TL001"}  # comment-only line covers next


def test_inline_suppression_waives_only_named_rule():
    hot = """
    import jax

    @jax.jit
    def f(x):
        return x.item()  # lint: disable=HS001
    """
    assert _lint(hot, rule_cls=HostSyncInHotPath) == []
    wrong = hot.replace("HS001", "ND001")
    assert _ids(_lint(wrong, rule_cls=HostSyncInHotPath)) == ["HS001"]


def test_baseline_matches_and_reports_stale(tmp_path):
    pkg = tmp_path / "apex_tpu" / "serving"
    pkg.mkdir(parents=True)
    f = pkg / "mod.py"
    f.write_text("import time\n\n\ndef now():\n    return time.time()\n")
    baseline = Baseline([
        {"rule": "ND001", "path": "apex_tpu/serving/mod.py",
         "match": "time.time()", "justification": "fixture"},
        {"rule": "ND001", "path": "apex_tpu/serving/mod.py",
         "match": "no_such_line", "justification": "stale fixture"},
    ])
    res = lint_paths([str(f)], baseline=baseline)
    assert res.findings == []
    assert len(res.baselined) == 1
    assert len(res.stale_baseline) == 1
    assert res.stale_baseline[0]["match"] == "no_such_line"


def test_baseline_rejects_missing_justification():
    with pytest.raises(ValueError, match="justification"):
        Baseline([{"rule": "ND001", "path": "a.py", "match": "x"}])


def test_cli_lint_gate_exit_codes(tmp_path, capsys):
    from apex_tpu.analysis.__main__ import main

    pkg = tmp_path / "apex_tpu" / "serving"
    pkg.mkdir(parents=True)
    bad = pkg / "bad.py"
    bad.write_text("import time\nT = time.time()\n")
    rc = main(["lint", str(bad), "--no-baseline", "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert [f["rule"] for f in report["findings"]] == ["ND001"]
    bad.write_text("import time\nT = time.monotonic()\n")
    assert main(["lint", str(bad), "--no-baseline"]) == 0
    assert main(["lint", str(tmp_path / "nope.py")]) == 2


# ---------------------------------------------------------------------------
# HS001 — host sync in a hot path
# ---------------------------------------------------------------------------


def test_hs001_flags_item_in_jit_decorated():
    src = """
    import jax

    @jax.jit
    def f(x):
        return x.item()
    """
    assert _ids(_lint(src, rule_cls=HostSyncInHotPath)) == ["HS001"]


def test_hs001_flags_device_get_in_jitted_by_name():
    src = """
    import jax

    def _step(x):
        jax.device_get(x)
        return x

    fn = jax.jit(_step)
    """
    assert _ids(_lint(src, rule_cls=HostSyncInHotPath)) == ["HS001"]


def test_hs001_flags_aliased_device_get():
    # `import jax as _jax` must not dodge the rule (found the hard way
    # in the train loop's log path on the rule's first run)
    src = """
    import jax as _jax
    import jax

    @jax.jit
    def f(x):
        return _jax.device_get(x)
    """
    assert _ids(_lint(src, rule_cls=HostSyncInHotPath)) == ["HS001"]


def test_hs001_hot_table_covers_named_loops_and_nested_defs():
    src = """
    import numpy as np

    def _decode_batch(rows):
        def fetch(t):
            return np.asarray(t)
        return [fetch(r) for r in rows]
    """
    found = _lint(src, path="apex_tpu/serving/engine.py",
                  rule_cls=HostSyncInHotPath)
    assert _ids(found) == ["HS001"]
    # same code under a path NOT in the hot table: quiet
    assert _lint(src, path="apex_tpu/ops/misc.py",
                 rule_cls=HostSyncInHotPath) == []


def test_hs001_negative_plain_function_quiet():
    src = """
    import jax
    import numpy as np

    def offline_report(x):
        jax.block_until_ready(x)
        return np.asarray(x).item()
    """
    assert _lint(src, rule_cls=HostSyncInHotPath) == []


# ---------------------------------------------------------------------------
# ND001 — unseeded nondeterminism in bitwise-contract modules
# ---------------------------------------------------------------------------


def test_nd001_flags_wall_clock_and_global_rng():
    src = """
    import random
    import time
    import numpy as np

    def jitter():
        return time.time() + random.random() + np.random.uniform()
    """
    found = _lint(src, path="apex_tpu/data/mod.py",
                  rule_cls=UnseededNondeterminism)
    assert _ids(found) == ["ND001", "ND001", "ND001"]


def test_nd001_negative_seeded_generators_and_monotonic():
    src = """
    import random
    import time
    import numpy as np

    def draw(seed):
        rng = np.random.RandomState(seed)
        g = np.random.Generator(np.random.Philox(seed))
        r = random.Random(seed)
        t0 = time.monotonic()
        return rng.uniform() + g.random() + r.random() + t0
    """
    assert _lint(src, path="apex_tpu/serving/mod.py",
                 rule_cls=UnseededNondeterminism) == []


def test_nd001_scoped_to_contract_modules():
    src = "import time\nT = time.time()\n"
    assert _lint(src, path="apex_tpu/ops/mod.py",
                 rule_cls=UnseededNondeterminism) == []
    assert _ids(_lint(src, path="apex_tpu/multi_tensor/mod.py",
                      rule_cls=UnseededNondeterminism)) == ["ND001"]


# ---------------------------------------------------------------------------
# DN001 — pool-sized jit without donation
# ---------------------------------------------------------------------------


def test_dn001_flags_pool_params_without_donate():
    src = """
    import jax

    def step(k_pool, v_pool, tokens):
        return k_pool, v_pool, tokens

    fn = jax.jit(step)
    """
    found = _lint(src, rule_cls=MissingDonation)
    assert _ids(found) == ["DN001"]
    assert "k_pool" in found[0].message and "v_pool" in found[0].message


def test_dn001_negative_donate_kwarg_or_no_pool_params():
    src = """
    import jax

    def step(k_pool, v_pool, tokens):
        return k_pool, v_pool, tokens

    def light(tokens, positions):
        return tokens + positions

    a = jax.jit(step, donate_argnums=(0, 1))
    b = jax.jit(step, donate_argnums=())   # explicit no-donate decision
    c = jax.jit(light)
    """
    assert _lint(src, rule_cls=MissingDonation) == []


# ---------------------------------------------------------------------------
# TL001 — telemetry emit sites vs the schema table
# ---------------------------------------------------------------------------


def test_tl001_flags_unknown_type_unknown_field_int_for_bool():
    src = """
    def report(bus):
        bus.emit("not_an_event", x=1)
        bus.emit("serving_recovery", cause="dl", pool_rebuilt=1,
                 running_restored=0, waiting_restored=0)
        bus.emit("step", bogus_field=3)
    """
    found = _lint(src, rule_cls=TelemetrySchemaDrift)
    msgs = " | ".join(f.message for f in found)
    assert _ids(found) == ["TL001", "TL001", "TL001"]
    assert "unknown telemetry event type 'not_an_event'" in msgs
    assert "int literal `1` for bool field `serving_recovery.pool_rebuilt`" \
        in msgs
    assert "`bogus_field` is not in the schema table" in msgs


def test_tl001_negative_conforming_and_dynamic_sites():
    src = """
    def report(bus, etype, payload):
        bus.emit("ckpt_save", step=3, blocking=True, wall_ms=1.5)
        bus.emit("step", step_ms=2.0, timing="synced")
        bus.emit(etype, **payload)          # dynamic: not checkable
        bus.emit("request_retire", rid=1, reason="eos", new_tokens=2,
                 preemptions=0, deadline_hit=True)
    """
    assert _lint(src, rule_cls=TelemetrySchemaDrift) == []


# ---------------------------------------------------------------------------
# TH001 — lock discipline across thread boundaries
# ---------------------------------------------------------------------------

_TH_TEMPLATE = """
import threading


class Worker:
    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._run)
        self._thread.start()

    def _run(self):
        {worker_store}

    def reset(self):
        {other_store}
"""


def test_th001_flags_unlocked_cross_thread_store():
    src = _TH_TEMPLATE.format(worker_store="self.count = self.count + 1",
                              other_store="self.count = 0")
    found = _lint(src, rule_cls=LockDiscipline)
    assert _ids(found) == ["TH001"]
    assert "self.count" in found[0].message


def test_th001_negative_locked_both_sides():
    src = _TH_TEMPLATE.format(
        worker_store="with self._lock:\n            self.count += 1",
        other_store="with self._lock:\n            self.count = 0")
    assert _lint(src, rule_cls=LockDiscipline) == []


def test_th001_negative_single_side_store():
    # worker-only mutation has no cross-thread writer to race with
    src = _TH_TEMPLATE.format(worker_store="self.count = self.count + 1",
                              other_store="pass")
    assert _lint(src, rule_cls=LockDiscipline) == []


def test_th001_follows_nested_thread_target_and_delegate():
    # Thread(target=<nested def>) + worker delegating to self._fire()
    src = """
    import threading


    class W:
        def __init__(self):
            self.flag = 0

        def submit(self):
            def _job():
                self._fire()
            threading.Thread(target=_job).start()

        def _fire(self):
            self.flag = 1

        def clear(self):
            self.flag = 0
    """
    assert _ids(_lint(src, rule_cls=LockDiscipline)) == ["TH001"]


# ---------------------------------------------------------------------------
# EX001 — exception swallowing in run loops
# ---------------------------------------------------------------------------


def test_ex001_flags_broad_swallow_in_loop():
    src = """
    def run(jobs):
        for job in jobs:
            try:
                job()
            except Exception:
                pass
    """
    assert _ids(_lint(src, rule_cls=ExceptionSwallowing)) == ["EX001"]


def test_ex001_negative_narrow_logged_teardown_or_no_loop():
    src = """
    import logging

    log = logging.getLogger(__name__)


    def run(jobs):
        for job in jobs:
            try:
                job()
            except ValueError:          # narrow: a decision, not a net
                continue
            try:
                job()
            except Exception:
                log.exception("job failed")   # surfaced


    def close(handles):
        for h in handles:
            try:
                h.close()
            except Exception:
                pass                    # teardown: the documented sink


    def once(job):
        try:
            job()
        except Exception:
            pass                        # not in a loop: out of scope
    """
    assert _lint(src, rule_cls=ExceptionSwallowing) == []


# ---------------------------------------------------------------------------
# the schema satellite: one table, no drift
# ---------------------------------------------------------------------------


def test_event_types_derived_from_field_specs():
    from apex_tpu.telemetry import bus, schema

    assert bus.EVENT_TYPES is schema.EVENT_TYPES
    assert schema.EVENT_TYPES == frozenset(schema.EVENT_FIELDS)
    for etype, fields in schema.EVENT_FIELDS.items():
        for name, spec in fields.items():
            assert isinstance(spec.types, tuple) and spec.types, \
                f"{etype}.{name} has no types"
            assert all(isinstance(t, type) for t in spec.types)
            assert isinstance(spec.required, bool)
    # the legacy view stays consistent with the table
    for etype, required in schema.PAYLOAD_REQUIRED.items():
        assert required == {f: s.types
                            for f, s in schema.EVENT_FIELDS[etype].items()
                            if s.required}


def test_emitting_unspecced_type_fails_loudly():
    from apex_tpu.telemetry import (MemorySink, SchemaError, TelemetryBus,
                                    TelemetryError, validate_event)

    bus = TelemetryBus(run_id="drift", sinks=[MemorySink()])
    with pytest.raises(TelemetryError, match="unknown event type"):
        bus.emit("brand_new_event", x=1)
    ev = bus.emit("step", step=1, step_ms=1.0)
    with pytest.raises(SchemaError, match="unknown event type"):
        validate_event(dict(ev, type="brand_new_event"))


def test_optional_fields_typed_when_present():
    from apex_tpu.telemetry import (MemorySink, SchemaError, TelemetryBus,
                                    validate_event)

    bus = TelemetryBus(run_id="opt", sinks=[MemorySink()])
    ev = bus.emit("request_retire", step=1, rid=1, reason="eos",
                  new_tokens=3, preemptions=0, ttft_ms=4.2,
                  deadline_hit=True)
    validate_event(ev)
    with pytest.raises(SchemaError, match="deadline_hit"):
        validate_event(dict(ev, deadline_hit=1))  # int-for-bool
    with pytest.raises(SchemaError, match="ttft_ms"):
        validate_event(dict(ev, ttft_ms="fast"))
    # absent optional stays fine
    ev2 = {k: v for k, v in ev.items()
           if k not in ("ttft_ms", "deadline_hit")}
    validate_event(ev2)


# ---------------------------------------------------------------------------
# the tier-1 gate: the repo lints clean against its committed baseline
# ---------------------------------------------------------------------------


def test_repo_lints_clean_against_committed_baseline():
    # the gate covers every PRODUCT surface: the package and the
    # example entrypoints.  tests/ stay out of scope —
    # they deliberately contain the rules' negative fixtures (unknown
    # event types, undonated jits) as test data
    baseline = Baseline.load(
        os.path.join(REPO_ROOT, "analysis_baseline.json"))
    res = lint_paths([os.path.join(REPO_ROOT, "apex_tpu"),
                      os.path.join(REPO_ROOT, "examples")],
                     baseline=baseline)
    assert res.findings == [], "\n".join(f.format() for f in res.findings)
    assert res.stale_baseline == [], (
        "stale baseline entries — the documented exception no longer "
        f"exists, delete them: {res.stale_baseline}")
    assert res.files > 100  # the walk really covered the package


# ---------------------------------------------------------------------------
# regression pins for the violations the first lint run surfaced
# ---------------------------------------------------------------------------


def test_grad_norm_counts_bf16_and_no_longer_swallows(monkeypatch):
    from apex_tpu.resilience.guards import global_grad_norm

    # the narrow except still takes the legitimate skip/convert paths
    tree = {"a": jnp.full((4,), 1.0, jnp.bfloat16),
            "b": np.arange(3)}           # int leaf: skipped, not normed
    assert global_grad_norm(tree) == pytest.approx(2.0)
    # …but an unexpected failure now surfaces instead of silently
    # under-reporting the norm (EX001 fix)
    monkeypatch.setattr(jax.numpy, "issubdtype",
                        lambda *a: (_ for _ in ()).throw(
                            RuntimeError("issubdtype broke")))
    with pytest.raises(RuntimeError, match="issubdtype broke"):
        global_grad_norm({"a": jnp.full((2,), 1.0, jnp.bfloat16)})


def test_checkpoint_topology_probe_narrowed(tmp_path):
    from apex_tpu.checkpoint import restore_checkpoint, save_checkpoint

    # numpy leaves (no .sharding at all) keep saving — the documented
    # best-effort "no topology" case
    state = {"w": np.arange(6, dtype=np.float32)}
    save_checkpoint(str(tmp_path / "ok"), state, step=1)
    restored, step = restore_checkpoint(
        str(tmp_path / "ok"), {"w": np.zeros(6, np.float32)})
    assert step == 1 and np.array_equal(restored["w"], state["w"])

    # …but a genuinely broken sharding probe now surfaces (EX001 fix:
    # the broad except used to swallow ANY failure here)
    class _Weird(np.ndarray):
        @property
        def sharding(self):
            raise RuntimeError("sharding probe broke")

    arr = np.arange(4, dtype=np.float32).view(_Weird)
    with pytest.raises(RuntimeError, match="sharding probe broke"):
        save_checkpoint(str(tmp_path / "bad"), {"w": arr}, step=1)


# ---------------------------------------------------------------------------
# runtime half: hot_path_guard mechanics
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def warm_jit():
    f = jax.jit(lambda a: a * 2 + 1)
    x = jnp.ones((8,))
    y = f(x)
    jax.block_until_ready(y)
    return f, x, y


def test_guard_steady_state_passes(warm_jit):
    f, x, _ = warm_jit
    with hot_path_guard("steady", transfers=None) as g:
        for _ in range(3):
            y = f(x)
    assert g.recompiles == 0 and g.syncs == []
    assert float(y[0]) == 3.0  # fetch OUTSIDE the region is fine


def test_guard_fires_on_recompile(warm_jit):
    f, _, _ = warm_jit
    x9 = jnp.ones((9,))  # new shape, built outside the region
    with pytest.raises(HotPathViolation, match="XLA compile"):
        with hot_path_guard("recompile-control", transfers=None):
            f(x9)


def test_guard_recompile_budget(warm_jit):
    f, _, _ = warm_jit
    x10 = jnp.ones((10,))
    with hot_path_guard("budgeted", transfers=None,
                        max_recompiles=1) as g:
        f(x10)
    assert g.recompiles == 1


@pytest.mark.parametrize("sync", ["device_get", "block_until_ready",
                                  "item"])
def test_guard_tripwire_fires_on_host_sync(warm_jit, sync):
    _, _, y = warm_jit
    calls = {"device_get": lambda: jax.device_get(y),
             "block_until_ready": lambda: jax.block_until_ready(y),
             "item": lambda: y.sum().item()}
    with pytest.raises(HotPathViolation, match="host sync"):
        with hot_path_guard("sync-control", transfers=None):
            calls[sync]()
    # and the tripwire is fully uninstalled afterwards
    calls[sync]()


def test_guard_records_instead_of_raising_when_asked(warm_jit):
    _, _, y = warm_jit
    with hot_path_guard("recording", transfers=None,
                        raise_on_sync=False) as g:
        jax.device_get(y)
        y.sum().item()
    assert g.syncs == ["jax.device_get", "Array.item"]


def test_guard_body_exception_propagates_and_restores(warm_jit):
    _, _, y = warm_jit
    with pytest.raises(RuntimeError, match="boom"):
        with hot_path_guard("err", transfers=None):
            raise RuntimeError("boom")
    jax.device_get(y)  # tripwire gone


# ---------------------------------------------------------------------------
# the two enforced-by-construction contracts
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving_cfg():
    from apex_tpu.serving.model import ServingModelConfig

    return ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                              num_layers=2, max_position=96)


def _make_engine(cfg):
    from apex_tpu.serving.engine import ServingEngine, SimClock

    return ServingEngine(cfg, num_pages=32, page_size=8, max_batch=4,
                         clock=SimClock(), seed=0)


@pytest.mark.serving
def test_serving_lifetime_zero_compiles_after_warmup(serving_cfg):
    """The PR 8 compiled-shapes contract, enforced by construction:
    warmup compiles all three executables (prefill row, decode step,
    admission scatter) and the whole serving lifetime after it — spans
    admission, growth, retirement — compiles NOTHING."""
    eng = _make_engine(serving_cfg)
    eng.warmup()
    with hot_path_guard("serving lifetime", transfers=None) as g:
        for i, prompt in enumerate([[1, 2, 3], [4, 5, 6, 7], [8, 9],
                                    [10, 11, 12, 13, 14]]):
            eng.submit(prompt, max_new_tokens=3 + i)
        finished = eng.run()
    assert len(finished) == 4
    assert g.recompiles == 0 and g.syncs == []


@pytest.mark.serving
def test_serving_lifetime_zero_compiles_on_any_rung_of_the_prefill_ladder():
    """ISSUE 32: a prefill row is one of a ladder of widths, by the
    request's own length.  warmup launches every rung, and the
    admission scatter at every rung's shape, so a trace whose prompts
    land on each of them compiles NOTHING."""
    from apex_tpu.serving.model import ServingModelConfig

    eng = _make_engine(ServingModelConfig(
        vocab_size=64, hidden_size=32, num_heads=4, num_layers=2,
        max_position=256))
    assert eng.prefill_widths == (128, 256)
    eng.warmup()
    with hot_path_guard("serving lifetime, every rung",
                        transfers=None) as g:
        for n in (3, 128, 129, 150, 193, 60):
            eng.submit([1 + i % 50 for i in range(n)], max_new_tokens=3)
        finished = eng.run()
    assert len(finished) == 6
    assert g.recompiles == 0 and g.syncs == []


@pytest.mark.serving
def test_spec_serving_lifetime_zero_compiles_after_warmup(serving_cfg):
    """ISSUE 12: the compiled-shapes contract over the GROWN executable
    set — warmup also compiles the speculative verify step
    (q_len = k + 1) and the chunked-prefill step, and a trace that
    exercises draft–verify boundaries, chunked prefill, AND pool-
    pressure preemption still compiles NOTHING after warmup."""
    from apex_tpu.serving.engine import ServingEngine, SimClock
    from apex_tpu.serving.spec import SpecConfig

    eng = ServingEngine(serving_cfg, num_pages=13, page_size=8,
                        max_batch=4, clock=SimClock(), seed=0,
                        max_pages_per_request=6,
                        spec=SpecConfig(k=3, chunk_size=16))
    eng.warmup()
    with hot_path_guard("spec serving lifetime", transfers=None) as g:
        # a long prompt (chunked prefill), repetitive prompts (drafts
        # that accept), and enough load on 12 pages to preempt
        reqs = [eng.submit([1, 2] * 12, max_new_tokens=6),
                eng.submit([3, 4, 3, 4, 3], max_new_tokens=8),
                eng.submit(list(range(5, 25)), max_new_tokens=4),
                eng.submit([7, 8] * 5, max_new_tokens=6)]
        finished = eng.run()
    assert len(finished) == 4
    assert g.recompiles == 0 and g.syncs == []
    assert eng.proposer.drafted > 0, "trace was meant to speculate"


@pytest.mark.serving
def test_serving_unwarmed_engine_trips_the_guard(serving_cfg):
    """Control: without warmup the first admission compiles inside the
    guarded region — the guard MUST fire (this is also the pin for the
    warmup gap the guard originally found: the admission scatter was
    the third executable warmup never compiled)."""
    eng = _make_engine(serving_cfg)
    with pytest.raises(HotPathViolation, match="XLA compile"):
        with hot_path_guard("unwarmed serving", transfers=None):
            eng.submit([1, 2, 3], max_new_tokens=2)
            eng.run()


@pytest.fixture(scope="module")
def toy_flagship():
    from apex_tpu.transformer.testing.flagship import (
        build_flagship_train_step, gpt1p3b_config)

    cfg = gpt1p3b_config(num_layers=1, hidden_size=64,
                         num_attention_heads=2, vocab_size=64,
                         max_position_embeddings=16)
    fs = build_flagship_train_step(cfg, plan="bf16_fit", lr=1e-3,
                                   devices=jax.devices()[:2],
                                   donate=False)
    from jax.sharding import NamedSharding, PartitionSpec as P

    k = jax.random.PRNGKey(1)
    tokens = jax.random.randint(k, (2, cfg.max_position_embeddings), 0,
                                cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=-1)
    sharding = NamedSharding(fs.mesh, P("data"))
    tokens = jax.device_put(tokens, sharding)
    labels = jax.device_put(labels, sharding)
    # steady state starts at step 2: step 1 compiles, and its output
    # state lands in the executable's (possibly different) sharding —
    # feeding it back once reaches the sharding fixed point
    p, s, _ = fs.step(fs.params, fs.opt_state, tokens, labels)
    p, s, loss = fs.step(p, s, tokens, labels)
    jax.block_until_ready(loss)
    return fs, p, s, tokens, labels


def test_flagship_steady_state_no_recompile_no_sync(toy_flagship):
    """The flagship train step's steady-state property, enforced by
    construction: with pre-placed inputs and warmed state, N further
    steps do zero compiles, zero host syncs, and zero guarded
    transfers ("disallow" is active inside the region)."""
    fs, p, s, tokens, labels = toy_flagship
    with hot_path_guard("flagship steady state") as g:
        for _ in range(3):
            p, s, loss = fs.step(p, s, tokens, labels)
    assert g.recompiles == 0 and g.syncs == []
    assert np.isfinite(float(loss))  # fetched OUTSIDE the region


def test_flagship_guard_fires_on_seeded_sync(toy_flagship):
    """Control: a mid-loop loss fetch — the exact HS001 anti-pattern —
    trips the guard."""
    fs, p, s, tokens, labels = toy_flagship
    with pytest.raises(HotPathViolation, match="host sync"):
        with hot_path_guard("flagship sync control"):
            _, _, loss = fs.step(p, s, tokens, labels)
            jax.device_get(loss)


def test_flagship_guard_fires_on_unplaced_inputs(toy_flagship):
    """Control: feeding the step an unplaced (differently-sharded)
    batch forces a device-to-device reshard per call — the transfer
    guard half catches it even on CPU (resharding IS guarded there,
    unlike host copies)."""
    fs, p, s, _, _ = toy_flagship
    k = jax.random.PRNGKey(2)
    t2 = jax.random.randint(k, (2, 16), 0, 64)
    l2 = jnp.roll(t2, -1, axis=-1)
    with pytest.raises(Exception, match="[Tt]ransfer"):
        with hot_path_guard("unplaced inputs"):
            fs.step(p, s, t2, l2)

"""ISSUE 13 — compiled-artifact contract checker (`apex_tpu.analysis
hlo`).

Five layers:

1. parser units on SYNTHETIC HLO fixtures — aliasing header entries,
   the anchored collective-opcode discipline (``all-gather-start.3``
   counts once, a pass-named row like ``all-reduce-promotion`` never
   counts), async start/done pairs counted once, while-body
   collectives counted once (the flops-parser caveat, documented),
   shape→bytes, host-op detection;
2. REAL small executables proving the report reads what the compiler
   delivered — donation present/stripped, a deliberately doubled
   psum, an injected host callback;
3. the acceptance controls against the COMMITTED contracts: a
   donate-stripped decode fails the aliasing contract, a
   callback-wrapped decode fails the host-op contract;
4. the tier-1 GATE: every registered executable compiles, reports,
   and passes the committed ``hlo_contracts.json`` with zero
   violations, zero missing entries, zero stale entries;
5. CLI exit-code discipline (0 clean / 1 violations-or-stale / 2
   missing-or-unparseable — the r4 ``parsed:null`` lesson), the
   ``--update`` workflow, the geometry provenance stamp, and the
   serving doc-drift pin (module docstring == docs table ==
   ``SERVING_EXECUTABLES`` == registry).
"""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.analysis import hlo as H
from apex_tpu.analysis import registry as R
from apex_tpu.analysis.__main__ import main as analysis_main
from apex_tpu.analysis.hlo import (check_contract, check_reports,
                                   collective_inventory,
                                   contract_from_report,
                                   executable_report,
                                   host_interaction_ops, load_contracts,
                                   parse_aliases)

pytestmark = pytest.mark.analysis

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
CONTRACTS = os.path.join(REPO_ROOT, "hlo_contracts.json")


# ---------------------------------------------------------------------------
# 1. parser units on synthetic HLO
# ---------------------------------------------------------------------------

SYNTH_HLO = """\
HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (1, {}, may-alias), {1,0}: (2, {}) }, entry_computation_layout={(f32[8,128]{1,0})->f32[8,128]{1,0}}

%add.clone (x.1: f32[], y.1: f32[]) -> f32[] {
  %x.1 = f32[] parameter(0)
  %y.1 = f32[] parameter(1)
  ROOT %add.2 = f32[] add(f32[] %x.1, f32[] %y.1)
}

%while_body (p.1: (s32[], f32[256])) -> (s32[], f32[256]) {
  %p.1 = (s32[], f32[256]{0}) parameter(0)
  %gte.1 = f32[256]{0} get-tuple-element((s32[], f32[256]{0}) %p.1), index=1
  %all-reduce.7 = f32[256]{0} all-reduce(f32[256]{0} %gte.1), replica_groups={}, to_apply=%add.clone
  ROOT %tuple.9 = (s32[], f32[256]{0}) tuple(%gte.1, %all-reduce.7)
}

ENTRY %main.42 (p0.1: f32[8,128]) -> f32[8,128] {
  %p0.1 = f32[8,128]{1,0} parameter(0)
  %all-gather-start.3 = (f32[8,128]{1,0}, f32[16,128]{1,0}) all-gather-start(f32[8,128]{1,0} %p0.1), dimensions={0}
  %all-gather-done.3 = f32[16,128]{1,0} all-gather-done(%all-gather-start.3)
  %reduce-scatter-decomposer = f32[8,128]{1,0} bitcast(f32[8,128]{1,0} %p0.1)
  %pass.1 = f32[8,128]{1,0} all-reduce-promotion(f32[8,128]{1,0} %p0.1)
  %rs.1 = f32[4,128]{1,0} reduce-scatter(f32[8,128]{1,0} %p0.1), dimensions={0}
  %cb.1 = f32[4]{0} custom-call(f32[8,128]{1,0} %p0.1), custom_call_target="xla_python_cpu_callback"
  %pallas.1 = f32[4]{0} custom-call(f32[8,128]{1,0} %p0.1), custom_call_target="tpu_custom_call"
  %of.1 = token[] outfeed(f32[4]{0} %cb.1)
  %send.5 = (f32[4]{0}, u32[], token[]) send(f32[4]{0} %cb.1), channel_id=1
  %send-done.5 = token[] send-done((f32[4]{0}, u32[], token[]) %send.5), channel_id=1
  %w.1 = (s32[], f32[256]{0}) while((s32[], f32[256]{0}) %w.1), condition=%add.clone, body=%while_body
  ROOT %copy.1 = f32[8,128]{1,0} copy(f32[8,128]{1,0} %p0.1)
}
"""


def test_parse_aliases_from_header():
    pairs = parse_aliases(SYNTH_HLO)
    assert [(a.param_number, a.output_index, a.kind) for a in pairs] == [
        (1, "0", "may-alias"), (2, "1,0", "may-alias")]
    # no header entry -> no aliases (the donation-stripped signature)
    assert parse_aliases("HloModule jit_f, is_scheduled=true\n") == []
    # layout braces / buffer_donor entries never parse as aliases
    assert parse_aliases(
        "HloModule j, buffer_donor={ {2} }, entry_computation_layout="
        "{(f32[8,128]{1,0})->f32[8,128]{1,0}}\n") == []


def test_collective_inventory_anchored_async_and_while_once():
    inv = collective_inventory(SYNTH_HLO)
    # all-gather: the -start row counts ONCE under the base opcode;
    # the -done half is skipped
    assert inv["all-gather"]["count"] == 1
    # the while-body all-reduce appears once in the text, so it counts
    # once regardless of trip count — the same stated undercount as
    # the HLO flops parser (hlo.py module docstring)
    assert inv["all-reduce"]["count"] == 1
    assert inv["reduce-scatter"]["count"] == 1
    # anchoring: the bitcast NAMED reduce-scatter-decomposer and the
    # pass-named all-reduce-promotion row contribute nothing
    assert set(inv) == {"all-gather", "all-reduce", "reduce-scatter"}


def test_collective_bytes_from_shapes():
    inv = collective_inventory(SYNTH_HLO)
    # start-row tuple (f32[8,128], f32[16,128]) -> 4096 + 8192
    assert inv["all-gather"]["bytes"] == 12288
    assert inv["all-reduce"]["bytes"] == 256 * 4
    assert inv["reduce-scatter"]["bytes"] == 4 * 128 * 4


def test_host_interaction_ops_detection():
    ops = host_interaction_ops(SYNTH_HLO)
    kinds = [(h.opcode, h.target) for h in ops]
    # callback custom-call, outfeed, send (send-done pairs with it);
    # the Pallas tpu_custom_call is NOT host interaction
    assert ("custom-call", "xla_python_cpu_callback") in kinds
    assert ("outfeed", "") in kinds
    assert ("send", "") in kinds
    assert len(ops) == 3
    assert not any(h.target == "tpu_custom_call" for h in ops)


def test_opcode_histogram_shared_with_profiling():
    from apex_tpu.profiling import opcode_histogram_from_text

    hist = opcode_histogram_from_text(SYNTH_HLO)
    assert hist["all-reduce"] == 1
    assert hist["copy"] == 1
    assert hist["parameter"] >= 2
    # tuple-shaped rows count too (review-found: the old \S+ shape
    # group could not span the space inside a tuple shape, silently
    # dropping every async -start / send / while row)
    assert hist["all-gather-start"] == 1
    assert hist["send"] == 1
    assert hist["while"] == 1


def test_check_contract_directions():
    rep = H.ExecutableReport(
        name="x",
        aliasing=[H.AliasPair("0", 1)],
        collectives={"all-reduce": {"count": 2, "bytes": 64}},
        host_ops=[H.HostOp("custom-call", "cb.1",
                           "xla_python_cpu_callback")],
        opcode_histogram={}, argument_bytes=0, output_bytes=0,
        temp_bytes=100, flops=0.0)
    clean = {"required_aliases": [{"param": 1, "output": "0"}],
             "max_collectives": {"all-reduce": 2},
             "allow_host_ops": ["callback"],
             "max_temp_bytes": 100}
    assert check_contract(rep, clean) == []
    # one-sided: fewer collectives / more aliases / smaller temp pass
    rep2 = H.ExecutableReport("x", [H.AliasPair("0", 1),
                                    H.AliasPair("1", 2)],
                              {}, [], {}, 0, 0, 0, 0.0)
    assert check_contract(rep2, clean) == []
    # each violation class fires
    assert any("aliasing" in v for v in check_contract(
        rep, {**clean, "required_aliases": [{"param": 9, "output": "0"}]}))
    assert any("collectives" in v for v in check_contract(
        rep, {**clean, "max_collectives": {"all-reduce": 1}}))
    assert any("host interaction" in v for v in check_contract(
        rep, {**clean, "allow_host_ops": []}))
    assert any("temp bytes" in v for v in check_contract(
        rep, {**clean, "max_temp_bytes": 99}))
    # review-found: an allow entry naming a host OPCODE must not
    # substring-match custom-call targets — a blessed `send` op must
    # not whitelist a callback whose target merely contains "send"
    sneaky = H.ExecutableReport(
        "x", [], {}, [H.HostOp("custom-call", "cb.2",
                               "host_send_buffer_to_somewhere")],
        {}, 0, 0, 0, 0.0)
    assert any("host interaction" in v for v in check_contract(
        sneaky, {"allow_host_ops": ["send"]}))
    assert check_contract(
        sneaky, {"allow_host_ops": ["host_send_buffer"]}) == []


# ---------------------------------------------------------------------------
# 4. the tier-1 gate (early: warms the registry's report cache for
#    the controls below)
# ---------------------------------------------------------------------------


def test_hlo_contract_gate_zero_violations():
    """THE gate: every registered executable builds, and the committed
    hlo_contracts.json passes with zero violations / missing / stale."""
    reports, errors = R.build_all_reports()
    assert errors == {}, errors
    assert len(reports) >= 8   # 5 serving + flagship + flat adam + reshard
    doc = load_contracts(CONTRACTS)
    res = check_reports(reports, doc,
                        registry_names=R.registered_executables())
    assert res.missing == []
    assert res.stale == []
    assert {k: v for k, v in res.violations.items() if v} == {}
    assert res.exit_code == 0


def test_serving_tp_builders_ignore_ambient_parallel_state():
    """r17 regression pin: the serving_tp_* builders lower the pinned
    tp=2 cpu-toy geometry even when a surrounding process has the
    global model-parallel state registered with a DIFFERENT tensor
    world (the exact leak a module-scoped training fixture can leave
    behind mid-suite).  Without ``uninitialized_scope`` this raises
    ``tp=2 does not match the initialized tensor-parallel world size
    1`` and the gate above reports builder errors."""
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(
        1, 1, devices=jax.devices()[:1])
    R._toy_engine_tp.cache_clear()
    R._serving_tp_lowered.cache_clear()
    try:
        lowered = R._serving_tp_lowered()
        # the sweep lowers all five executables; the registry registers
        # the hot-path subset
        assert set(R.SERVING_TP_EXECUTABLES) <= set(lowered)
        # and the ambient state survived the build untouched
        assert parallel_state.get_tensor_model_parallel_world_size() == 1
    finally:
        parallel_state.destroy_model_parallel()
        R._toy_engine_tp.cache_clear()
        R._serving_tp_lowered.cache_clear()


def test_committed_contracts_pin_the_properties_that_matter():
    """The committed entries encode the real invariants: serving is
    communication-lean and host-silent with the pool donation
    verified; the flagship entry is ROADMAP item 3's measured
    collective baseline."""
    doc = load_contracts(CONTRACTS)
    execs = doc["executables"]
    for name in ("serving_decode", "serving_verify", "serving_chunk",
                 "serving_admission_scatter"):
        e = execs[name]
        # both pool buffers' donation machine-verified (768 MB lesson)
        assert len(e["required_aliases"]) >= 2, name
        assert e["max_collectives"] == {}, name
        assert e["allow_host_ops"] == [], name
    fl = execs["flagship_dp_tp_step"]
    assert fl["max_collectives"].get("all-reduce", 0) >= 1
    assert fl["max_collectives"].get("reduce-scatter", 0) >= 1
    assert fl["required_aliases"]   # donated params + opt state
    assert fl["inventory"]["collective_bytes"]  # the item-3 baseline
    za = execs["zero_flat_adam_update"]
    assert len(za["required_aliases"]) >= 3  # params + both moments
    rs = execs["reshard_stack"]
    assert rs["max_collectives"] == {} and rs["allow_host_ops"] == []


def test_contracts_geometry_stamp():
    """Satellite: the committed file self-declares cpu-toy provenance
    (absolute bytes are gate fixtures, not flagship-scale truth), and
    an unstamped file refuses to load."""
    doc = json.load(open(CONTRACTS))
    assert doc["format"] == 1
    assert doc["geometry"] == "cpu-toy"
    assert "cpu-toy" in doc["comment"]


def test_unstamped_contracts_refuse_to_load(tmp_path):
    p = tmp_path / "c.json"
    p.write_text(json.dumps({"format": 1, "executables": {}}))
    with pytest.raises(H.ContractFileError, match="geometry"):
        load_contracts(str(p))


# ---------------------------------------------------------------------------
# 2. real executables: the report reads what the compiler delivered
# ---------------------------------------------------------------------------


def test_donation_report_on_real_executable():
    def f(pool, tok):
        return pool + tok, tok * 2

    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    rep = executable_report(
        "donated", jax.jit(f, donate_argnums=(0,)).lower(x, x).compile())
    assert [(a.param_number, a.output_index) for a in rep.aliasing] \
        == [(0, "0")]
    stripped = executable_report(
        "stripped", jax.jit(f).lower(x, x).compile())
    assert stripped.aliasing == []
    contract = contract_from_report(rep)
    assert check_contract(rep, contract) == []
    v = check_contract(stripped, contract)
    assert any("donation did not survive" in s for s in v)


def test_doubled_collective_fails_inventory_contract():
    """Acceptance control: a deliberately doubled collective fails the
    committed-style inventory contract built from the single form."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))

    def once(x):
        return jax.lax.psum(x, "x")

    def twice(x):
        return jax.lax.psum(jax.lax.psum(x, "x"), "x")

    def rep_of(fn, name):
        sm = shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P(),
                       check_rep=False)
        arr = jnp.ones((2, 8), jnp.float32)
        return executable_report(name, jax.jit(sm).lower(arr).compile())

    r1 = rep_of(once, "once")
    r2 = rep_of(twice, "twice")
    assert r1.collectives["all-reduce"]["count"] == 1
    assert r2.collectives["all-reduce"]["count"] == 2
    contract = contract_from_report(r1)
    assert check_contract(r1, contract) == []
    v = check_contract(r2, contract)
    assert any("all-reduce x2 exceeds" in s for s in v)


# ---------------------------------------------------------------------------
# 3. acceptance controls against the COMMITTED serving contracts
# ---------------------------------------------------------------------------


def _committed(name):
    return load_contracts(CONTRACTS)["executables"][name]


def test_donate_stripped_decode_fails_aliasing_contract():
    """Acceptance control: strip the decode step's pool donation and
    the committed aliasing contract fails — donation is now a
    machine-checked property, not a trusted kwarg."""
    eng = R._toy_engine()
    low = eng.analysis_executables(donate=False)["decode"]
    rep = executable_report("serving_decode", low.compile())
    v = check_contract(rep, _committed("serving_decode"))
    assert any("donation did not survive" in s for s in v)
    # ... and the shipped (donating) artifact passes the same entry
    ok = R.build_report("serving_decode")
    assert check_contract(ok, _committed("serving_decode")) == []


def test_donate_stripped_scatter_fails_aliasing_contract():
    eng = R._toy_engine()
    low = eng.cache.analysis_executable(eng.prefill_budget, donate=False)
    rep = executable_report("serving_admission_scatter", low.compile())
    v = check_contract(rep, _committed("serving_admission_scatter"))
    assert any("donation did not survive" in s for s in v)


def test_injected_host_callback_fails_host_contract():
    """Acceptance control: wrap the decode step with a host callback
    (the way a stray debug hook would) and the committed host-op
    contract fails — 'zero host interaction' is machine-checked."""
    eng = R._toy_engine()
    fn, _donate = eng._exec_defs["decode"]
    structs = eng._executable_arg_structs()["decode"]

    def with_callback(*args):
        tok, k, v = fn(*args)
        tok = jax.pure_callback(
            lambda t: t, jax.ShapeDtypeStruct(tok.shape, tok.dtype), tok)
        return tok, k, v

    rep = executable_report(
        "decode_cb", jax.jit(with_callback).lower(*structs).compile())
    assert rep.host_ops
    v = check_contract(rep, _committed("serving_decode"))
    assert any("host interaction" in s for s in v)


def test_flat_adam_donation_verified_and_strippable():
    from apex_tpu.optimizers.flat import FlatAdamState, FlatFusedAdam

    opt = FlatFusedAdam()
    buf = jax.ShapeDtypeStruct((R.FLAT_ADAM_N,), jnp.float32)
    st = FlatAdamState(step=jax.ShapeDtypeStruct((), jnp.int32),
                       exp_avg=buf, exp_avg_sq=buf)
    rep = executable_report(
        "zero_flat_adam_update",
        opt.jit_step(donate=False).lower(buf, st, buf).compile())
    v = check_contract(rep, _committed("zero_flat_adam_update"))
    assert any("donation did not survive" in s for s in v)


# ---------------------------------------------------------------------------
# ISSUE 15: the ratcheted bucketed-flagship contract
# ---------------------------------------------------------------------------


def test_serialized_flagship_step_fails_ratcheted_contract():
    """THE ratchet control (ISSUE 15 satellite): the pre-r15 serialized
    construction — per-leaf boundary grad all-reduces feeding one
    monolithic scatter/gather — must FAIL the committed (ratcheted)
    ``flagship_dp_tp_step`` entry on its all-reduce count, while the
    shipped bucketed artifact passes the same entry.  The ratchet is a
    one-way door: the serialized inventory cannot silently come
    back."""
    rep = executable_report(
        "flagship_serialized",
        R.flagship_serialized_lowered().compile())
    contract = _committed("flagship_dp_tp_step")
    # the old inventory really is the committed "before" baseline:
    # 30 all-reduces, one reduce-scatter, one all-gather (PR 13)
    assert rep.collectives["all-reduce"]["count"] == 30
    assert rep.collectives["reduce-scatter"]["count"] == 1
    assert rep.collectives["all-gather"]["count"] == 1
    v = check_contract(rep, contract)
    assert any("all-reduce x30 exceeds" in s for s in v), v
    # ...and the shipped bucketed step passes the entry it ratcheted
    ok = R.build_report("flagship_dp_tp_step")
    assert check_contract(ok, contract) == []


def test_ratcheted_flagship_entry_pins_the_bucketed_inventory():
    """The committed entry proves the tentpole structurally: the
    all-reduce cap dropped WELL below the serialized 30 (only the
    model's tp activation collectives remain), the scatter/gather pair
    became per-bucket (several of each), the all-reduce byte inventory
    collapsed (the replicated-master-grad transfers are gone), and
    end-to-end donation survived (params + opt-state leaves all
    aliased)."""
    fl = _committed("flagship_dp_tp_step")
    caps = fl["max_collectives"]
    assert caps["all-reduce"] < 30, caps
    assert caps["reduce-scatter"] > 1, caps
    assert caps["all-gather"] == caps["reduce-scatter"], caps
    # the grad traffic moved out of all-reduce: remaining AR bytes are
    # activation-sized, an order of magnitude under the old 7.5 MB
    assert fl["inventory"]["collective_bytes"]["all-reduce"] < 2_000_000
    assert len(fl["required_aliases"]) >= 19


def test_bucketed_flat_adam_contract_donates_end_to_end():
    """The new bucketed executable's entry: per-span kernel launches
    still donate params + both moments at the entry boundary (4 alias
    pairs — the concat reassembly did not break XLA's aliasing) with
    zero collectives and zero host interaction."""
    e = _committed("zero_flat_adam_update_bucketed")
    assert len(e["required_aliases"]) >= 4
    assert e["max_collectives"] == {}
    assert e["allow_host_ops"] == []
    ok = R.build_report("zero_flat_adam_update_bucketed")
    assert check_contract(ok, e) == []


# ---------------------------------------------------------------------------
# engine exposure: analysis shapes ARE the served shapes
# ---------------------------------------------------------------------------


def test_analysis_shapes_match_warmup_zero_recompiles():
    """No-drift pin: after warmup(), launching every executable with
    arguments built from _executable_arg_structs compiles NOTHING —
    the analyzed artifacts are the served artifacts, by construction."""
    from apex_tpu.analysis import hot_path_guard

    eng = R._toy_engine()
    eng.warmup()
    structs = eng._executable_arg_structs()
    zeros = {name: tuple(
        jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), a)
        for a in args) for name, args in structs.items()}
    L, S = eng.cfg.num_layers, eng.prefill_budget
    kz = jnp.zeros((L, S, eng.cfg.num_heads, eng.cfg.head_dim),
                   eng.cache.k.dtype)
    iz = np.zeros((S,), np.int32)
    jitted = {"prefill": eng._prefill_fn, "decode": eng._decode_fn,
              "verify": eng._verify_fn, "chunk": eng._chunk_fn}
    with hot_path_guard("analysis-shapes", max_recompiles=0,
                        transfers=None, tripwire=False):
        for name, fn in jitted.items():
            fn(*zeros[name])
        eng.cache.write_tokens(kz, kz, iz, iz)


def test_toy_engine_enables_all_five_executables():
    from apex_tpu.serving.engine import SERVING_EXECUTABLES

    lowered = R._toy_engine().analysis_executables()
    assert tuple(lowered) == SERVING_EXECUTABLES


# ---------------------------------------------------------------------------
# reshard device twin
# ---------------------------------------------------------------------------


def test_reshard_stack_device_matches_host_contract():
    from apex_tpu.multi_tensor.flat import (reshard_stack,
                                            reshard_stack_device)

    val = np.arange(4 * 2 * 8, dtype=np.float32).reshape(4, 2, 8)
    # constant world size: (4, 2, ·) -> (8, ·) C-order merge
    want = (8, 8)
    np.testing.assert_array_equal(
        np.asarray(reshard_stack_device(val, want)),
        reshard_stack(val, 2, want))
    # growth: schema tail zero-fills, same as the host contract
    want2 = (2, 40)
    np.testing.assert_array_equal(
        np.asarray(reshard_stack_device(val, want2)),
        reshard_stack(val, 2, want2))
    # trims are a host-side decision — the device twin refuses
    with pytest.raises(ValueError, match="grows or keeps size"):
        reshard_stack_device(val, (4, 8))


# ---------------------------------------------------------------------------
# 5. CLI exit codes (satellite: 0 / 1 / 2, all self-tested)
# ---------------------------------------------------------------------------


def test_cli_exit_0_clean(capsys):
    rc = analysis_main(["hlo", "--contracts", CONTRACTS,
                        "--only", "reshard_stack"])
    assert rc == 0
    assert "0 violation(s)" in capsys.readouterr().out


def test_cli_exit_1_on_violation(tmp_path, capsys):
    doc = {"format": 1, "geometry": "cpu-toy", "executables": {
        "reshard_stack": {
            "required_aliases": [{"param": 0, "output": "0"}],
            "max_collectives": {}, "allow_host_ops": [],
            "max_temp_bytes": 0}}}
    p = tmp_path / "c.json"
    p.write_text(json.dumps(doc))
    rc = analysis_main(["hlo", "--contracts", str(p),
                        "--only", "reshard_stack"])
    assert rc == 1
    assert "donation did not survive" in capsys.readouterr().out


def test_cli_exit_1_on_stale_entry(tmp_path, capsys):
    """A contract for a deleted executable fails LOUDLY (the PR 11
    stale-baseline discipline) — it cannot ride along green."""
    doc = json.load(open(CONTRACTS))
    doc["executables"]["serving_deleted_step"] = \
        doc["executables"]["reshard_stack"]
    p = tmp_path / "c.json"
    p.write_text(json.dumps(doc))
    rc = analysis_main(["hlo", "--contracts", str(p),
                        "--only", "reshard_stack"])
    assert rc == 1
    assert "stale contract entry" in capsys.readouterr().out


def test_cli_exit_2_missing_file(tmp_path, capsys):
    rc = analysis_main(["hlo", "--contracts",
                        str(tmp_path / "nope.json"),
                        "--only", "reshard_stack"])
    assert rc == 2
    assert "not found" in capsys.readouterr().err


def test_cli_exit_2_unparseable_file(tmp_path, capsys):
    """The r4 parsed:null lesson: an unreadable gate exits 2, never
    green."""
    p = tmp_path / "c.json"
    p.write_text('{"format": 1, "geometry": "cpu-toy", "executab')
    rc = analysis_main(["hlo", "--contracts", str(p),
                        "--only", "reshard_stack"])
    assert rc == 2
    assert "unparseable" in capsys.readouterr().err


def test_cli_exit_2_missing_contract_entry(tmp_path, capsys):
    p = tmp_path / "c.json"
    p.write_text(json.dumps(
        {"format": 1, "geometry": "cpu-toy", "executables": {}}))
    rc = analysis_main(["hlo", "--contracts", str(p),
                        "--only", "reshard_stack"])
    assert rc == 2
    assert "no contract entry" in capsys.readouterr().out


def test_cli_exit_2_unknown_executable(capsys):
    rc = analysis_main(["hlo", "--contracts", CONTRACTS,
                        "--only", "no_such_executable"])
    assert rc == 2
    assert "unknown executable" in capsys.readouterr().err


def test_cli_update_roundtrip(tmp_path, capsys):
    p = tmp_path / "c.json"
    rc = analysis_main(["hlo", "--update", "--contracts", str(p),
                        "--only", "reshard_stack"])
    assert rc == 0
    doc = json.load(open(p))
    assert doc["format"] == 1 and doc["geometry"] == "cpu-toy"
    assert "reshard_stack" in doc["executables"]
    rc = analysis_main(["hlo", "--contracts", str(p),
                        "--only", "reshard_stack"])
    assert rc == 0
    capsys.readouterr()


def test_cli_json_report(capsys):
    rc = analysis_main(["hlo", "--contracts", CONTRACTS,
                        "--only", "reshard_stack", "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["exit_code"] == 0
    assert doc["geometry"] == "cpu-toy"
    assert "reshard_stack" in doc["reports"]


# ---------------------------------------------------------------------------
# doc drift: docstring == docs table == SERVING_EXECUTABLES == registry
# ---------------------------------------------------------------------------

_WORDS = {"one": 1, "two": 2, "three": 3, "four": 4, "five": 5,
          "six": 6, "seven": 7, "eight": 8, "nine": 9, "ten": 10}


def test_serving_docstring_matches_docs_table_and_registry():
    """Satellite: the engine docstring's executable count, the
    docs/serving.md compiled-shapes table, the SERVING_EXECUTABLES
    tuple, and the checker registry's serving entries all agree — the
    ISSUE 12 'two compiled' docstring drift class, made impossible."""
    import apex_tpu.serving.engine as E

    m = re.search(r"fixed set of (\w+) compiled executables",
                  " ".join(E.__doc__.split()))
    assert m, "engine docstring lost its executable-count anchor"
    n = _WORDS[m.group(1)]
    assert n == len(E.SERVING_EXECUTABLES)

    md = open(os.path.join(REPO_ROOT, "docs", "serving.md")).read()
    section = md.split("## The compiled-shapes contract")[1].split("\n## ")[0]
    rows = re.findall(r"^\| \d+ \|", section, re.M)
    assert len(rows) == n

    serving_entries = [x for x in R.registered_executables()
                      if x.startswith("serving_")]
    base = [x for x in serving_entries if not x.startswith("serving_tp_")]
    assert base == [f"serving_{x}" for x in E.SERVING_EXECUTABLES]
    # r17: the tp-sharded serving modes register their own family —
    # every entry names an executable from the SAME compiled set (the
    # tp engine changes sharding and pool dtype, not the shape table)
    tp = [x for x in serving_entries if x.startswith("serving_tp_")]
    from apex_tpu.analysis.registry import SERVING_TP_EXECUTABLES
    assert tp == [f"serving_tp_{x}" for x in SERVING_TP_EXECUTABLES]
    assert set(SERVING_TP_EXECUTABLES) <= set(E.SERVING_EXECUTABLES)

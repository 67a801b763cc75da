"""Win-or-fall-back CI gate: the newest committed bench record must show
every default-on fused path non-losing (ops/kernel_defaults.py).

Record-selection rules (reworked in r5 after the r4 incident — VERDICT
r4 Weak #1/#2, Next #1):

* **Driver records** (``BENCH_rNN.json``, no suffix) are the authority:
  the newest parseable one with ``bench_schema >= 2`` supplies the gate
  values.  Builder-captured records (``BENCH_rNNb_builder.json``) may
  *supplement* — consulted only when no driver record qualifies — but
  never substitute for a qualifying driver record.
* An **unparseable newest driver record is a FAILURE, not a skip**: it
  means the official perf artifact carries no metrics, which is exactly
  the r4 incident (bench.py printed a final line too large for the
  driver's ~2000-char tail capture; ``parsed: null`` landed in-tree).
  ``BENCH_r04.json`` itself is allowlisted as the diagnosed, fixed
  instance (bench.py now routes top-ops to a sidecar and size-guards
  the summary line via ``_emit_record``).
"""
import glob
import json
import os
import re

import pytest

from apex_tpu.ops.kernel_defaults import DEFAULT_GATES

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The one diagnosed incident: r4's summary line embedded full top-ops
# tables and defeated the driver's tail parser.  Named here so the gate
# stays green on the historical artifact while FAILING on any future
# driver record that comes back unparseable.
KNOWN_UNPARSEABLE = {"BENCH_r04.json"}

_DRIVER_NAME = re.compile(r"^BENCH_r(\d+)\.json$")


def _round_key(path):
    """Natural sort on the round number: BENCH_r10 must sort after
    BENCH_r9 (lexicographic sort would silently enforce a stale record
    from round 10 on).  Suffixed builder records (e.g. r03b_builder)
    sort after the same round's driver record via the string tail."""
    name = os.path.basename(path)
    m = re.match(r"BENCH_r(\d+)(.*)\.json$", name)
    if not m:
        return (-1, name)
    return (int(m.group(1)), m.group(2))


def _extras(path, merge_sidecar=False):
    """Parsed extras dict of a record, or None if the record carries no
    parsed metrics (unreadable file, ``parsed: null``, missing extras).

    ``merge_sidecar`` is set only for the record SELECTED as the gate
    authority: sections the bench spilled to the committed sidecar file
    (``spilled_to_sidecar``) are merged back, and a gated section that
    cannot be recovered is a hard failure — never for mere selection
    scans (the sidecar is rewritten each bench run, so it only speaks
    for the newest record; older records' spilled sections rotate out
    and must not be graded against a different run's values)."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except Exception:
        return None
    extras = (rec.get("parsed") or {}).get("extras")
    if not isinstance(extras, dict):
        return None
    spilled = extras.get("spilled_to_sidecar")
    if spilled and merge_sidecar:
        try:
            with open(os.path.join(os.path.dirname(path),
                                   "BENCH_TOPOPS.json")) as f:
                sidecar = json.load(f)
        except Exception:
            sidecar = {}
        missing = []
        for key in spilled:
            if key in sidecar:
                extras.setdefault(key, sidecar[key])
            else:
                missing.append(key)
        gated = {e for e, _, _, _ in DEFAULT_GATES}
        lost = sorted(set(missing) & gated)
        assert not lost, (
            f"{os.path.basename(path)}: gated section(s) {lost} were "
            "spilled to the sidecar but BENCH_TOPOPS.json does not "
            "carry them — the gate would be silently un-enforced "
            "(sidecar write failed or file not committed)")
    return extras


def _latest_record():
    paths = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")),
                   key=_round_key)
    driver = [p for p in paths if _DRIVER_NAME.match(os.path.basename(p))]
    if driver:
        newest = driver[-1]
        name = os.path.basename(newest)
        if _extras(newest) is None and name not in KNOWN_UNPARSEABLE:
            raise AssertionError(
                f"{name}: the newest DRIVER perf record is unparseable "
                "(parsed: null / missing extras) — the official artifact "
                "carries no metrics.  bench.py's summary line must stay "
                "under the driver's tail-capture size (see _emit_record); "
                "builder-captured records cannot substitute.")
    for path in reversed(driver):
        extras = _extras(path)
        if extras is not None and extras.get("bench_schema", 0) >= 2:
            return os.path.basename(path), _extras(path,
                                                   merge_sidecar=True)
    for path in reversed(paths):  # supplement: builder-captured records
        if path in driver:
            continue
        extras = _extras(path)
        if extras is not None and extras.get("bench_schema", 0) >= 2:
            return os.path.basename(path), _extras(path,
                                                   merge_sidecar=True)
    return None, None


def test_every_default_wins_in_latest_record():
    name, extras = _latest_record()
    if extras is None:
        pytest.skip("no bench_schema>=2 record committed yet (enforcement "
                    "begins with the first device-timed record)")
    failures = []
    for entry, field, min_val, guards in DEFAULT_GATES:
        section = extras.get(entry)
        if not isinstance(section, dict) or field not in section:
            continue  # entry lost to a transient bench failure: no verdict
        val = section[field]
        if val < min_val:
            failures.append(
                f"{name}: {entry}.{field} = {val} < {min_val} — losing "
                f"default: {guards}")
    assert not failures, "\n".join(failures)


def test_gate_covers_every_speedup_field():
    """Every *speedup* field the bench emits must be claimed by a gate —
    a new fused path cannot ship default-on without enforcement."""
    name, extras = _latest_record()
    if extras is None:
        pytest.skip("no bench_schema>=2 record committed yet")
    gated = {(e, f) for e, f, _, _ in DEFAULT_GATES}
    ungated = []
    for entry, section in extras.items():
        if not isinstance(section, dict):
            continue
        for field in section:
            if "speedup" in field and (entry, field) not in gated:
                ungated.append(f"{entry}.{field}")
    assert not ungated, (
        f"{name}: speedup fields without a kernel_defaults gate: {ungated}")


def test_sweep_cells_not_losing():
    """Applicability-window sweeps (VERDICT r5 Weak #2, acted on in r7):
    every per-shape cell recorded in the sweep sections must stay above
    the parity floor — a losing cell means the fused formulation is
    worse than naive somewhere inside the window it claims, which the
    single-shape scalar gates cannot see.  Winners (>= SWEEP_WIN_MIN)
    are surfaced by kernel_defaults.sweep_verdict as the per-shape
    evidence behind keeping each default (the r6 demote-or-gate
    decision protocol)."""
    from apex_tpu.ops.kernel_defaults import (
        SWEEP_PARITY_MIN, SWEEP_SECTIONS, sweep_cells, sweep_verdict)

    name, extras = _latest_record()
    if extras is None:
        pytest.skip("no bench_schema>=2 record committed yet")
    # the per-shape tables ride the sidecar (bench.py writes them there
    # directly, not via the spill path) — the sidecar is rewritten each
    # bench run, so it speaks for the newest record, which is exactly
    # the one _latest_record selects for enforcement
    try:
        with open(os.path.join(REPO, "BENCH_TOPOPS.json")) as f:
            sidecar = json.load(f)
    except Exception:
        sidecar = {}
    failures, seen = [], 0
    for entry in SWEEP_SECTIONS:
        section = extras.get(entry, sidecar.get(entry))
        if not isinstance(section, dict):
            continue  # sweep not in this record: no verdict
        seen += 1
        verdict = sweep_verdict(section)
        for cell, ratio in sweep_cells(section):
            if ratio < SWEEP_PARITY_MIN:
                failures.append(
                    f"{name}: {entry}.{cell} ratio {ratio} < "
                    f"{SWEEP_PARITY_MIN} — the fused form LOSES at this "
                    f"shape; demote it for this cell (losers="
                    f"{verdict['losers']})")
    if not seen:
        pytest.skip(f"{name} carries no sweep sections yet (first "
                    "driver run after r6 records them)")
    assert not failures, "\n".join(failures)


def test_sweep_verdict_classifies():
    """The demote-or-gate helper: winners/parity/losers split at the
    documented thresholds, tolerating error cells and scalar tails."""
    from apex_tpu.ops.kernel_defaults import sweep_verdict

    section = {
        "sk512_causal": {"ratio": 1.31},
        "sk1024_causal": {"ratio": 1.0},
        "sk2048_padding": {"ratio": 0.7},
        "sk4096_causal": {"error": "boom"},
        "s384": {"fast_vs_generic": 1.2},
        "min_ratio": 0.7,
    }
    v = sweep_verdict(section)
    assert v["winners"] == ["sk512_causal", "s384"]
    assert v["parity"] == ["sk1024_causal"]
    assert v["losers"] == ["sk2048_padding"]


def test_sweep_gate_fails_on_losing_cell(tmp_path, monkeypatch):
    """A committed record with a below-parity sweep cell must trip the
    sweep gate."""
    import tests.L0.test_kernel_defaults as mod

    rec = {"parsed": {"extras": {
        "bench_schema": 3,
        "fused_softmax_sweep": {"sk2048_padding": {"ratio": 0.5}},
    }}}
    (tmp_path / "BENCH_r97.json").write_text(json.dumps(rec))
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    with pytest.raises(AssertionError, match="sk2048_padding ratio 0.5"):
        mod.test_sweep_cells_not_losing()


def test_gate_fails_on_losing_default(tmp_path, monkeypatch):
    """The failure path: a record showing a losing default must trip the
    gate (the r3 scenario — 0.17x recorded for a default-on path)."""
    import tests.L0.test_kernel_defaults as mod

    rec = {"parsed": {"extras": {
        "bench_schema": 2,
        "layer_norm": {"fwd_speedup": 1.5, "bwd_speedup": 0.17},
    }}}
    p = tmp_path / "BENCH_r99.json"
    p.write_text(json.dumps(rec))
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    with pytest.raises(AssertionError, match="bwd_speedup = 0.17"):
        mod.test_every_default_wins_in_latest_record()


def test_natural_sort_picks_double_digit_rounds(tmp_path, monkeypatch):
    import tests.L0.test_kernel_defaults as mod

    old = {"parsed": {"extras": {"bench_schema": 2,
                                 "xentropy": {"speedup": 0.1}}}}
    newer = {"parsed": {"extras": {"bench_schema": 2,
                                   "xentropy": {"speedup": 1.0}}}}
    (tmp_path / "BENCH_r09.json").write_text(json.dumps(old))
    (tmp_path / "BENCH_r10.json").write_text(json.dumps(newer))
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    name, extras = mod._latest_record()
    assert name == "BENCH_r10.json"
    assert extras["xentropy"]["speedup"] == 1.0


def test_unparseable_newest_driver_record_fails(tmp_path, monkeypatch):
    """The r4 incident class: a fresh driver record with parsed:null must
    FAIL the gate, not silently fall back to self-captured numbers."""
    import tests.L0.test_kernel_defaults as mod

    good = {"parsed": {"extras": {"bench_schema": 2,
                                  "xentropy": {"speedup": 1.0}}}}
    (tmp_path / "BENCH_r06.json").write_text(json.dumps(good))
    (tmp_path / "BENCH_r07.json").write_text(json.dumps({"parsed": None}))
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    with pytest.raises(AssertionError, match="unparseable"):
        mod._latest_record()


def test_known_bad_r04_falls_back_to_builder(tmp_path, monkeypatch):
    """BENCH_r04.json (the diagnosed incident) is allowlisted: selection
    falls through it to the newest parseable schema>=2 record."""
    import tests.L0.test_kernel_defaults as mod

    builder = {"parsed": {"extras": {"bench_schema": 2,
                                     "xentropy": {"speedup": 1.0}}}}
    (tmp_path / "BENCH_r04.json").write_text(json.dumps({"parsed": None}))
    (tmp_path / "BENCH_r03b_builder.json").write_text(json.dumps(builder))
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    name, extras = mod._latest_record()
    assert name == "BENCH_r03b_builder.json"
    assert extras["xentropy"]["speedup"] == 1.0


def test_driver_record_outranks_builder_record(tmp_path, monkeypatch):
    """A qualifying driver record is the authority even when a builder
    record from the same round sorts after it (closes the r4 loophole
    where the gate only ever graded self-captured numbers)."""
    import tests.L0.test_kernel_defaults as mod

    drv = {"parsed": {"extras": {"bench_schema": 2,
                                 "xentropy": {"speedup": 0.97}}}}
    bld = {"parsed": {"extras": {"bench_schema": 2,
                                 "xentropy": {"speedup": 2.0}}}}
    (tmp_path / "BENCH_r08.json").write_text(json.dumps(drv))
    (tmp_path / "BENCH_r08b_builder.json").write_text(json.dumps(bld))
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    name, extras = mod._latest_record()
    assert name == "BENCH_r08.json"
    assert extras["xentropy"]["speedup"] == 0.97


def test_summary_line_always_fits_driver_capture():
    """bench._emit_record must keep the final stdout line under the
    driver's tail-capture size no matter how large extras grow, spilling
    bulk sections to the sidecar (named in spilled_to_sidecar)."""
    import bench

    huge = [{"name": "fusion.%d" % i, "ms": 1.0, "op": "x" * 120}
            for i in range(200)]
    record = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
              "extras": {"bench_schema": 3,
                         "gpt350m_top_ops": huge,
                         "layer_norm": {"fwd_speedup": 1.5},
                         "matmul_roof_tflops": 100.0}}
    line, spilled = bench._emit_record(record)
    assert len(line) <= bench.SUMMARY_LINE_LIMIT
    parsed = json.loads(line)
    assert "gpt350m_top_ops" in spilled
    assert "gpt350m_top_ops" in parsed["extras"]["spilled_to_sidecar"]
    # scalars and small gate sections survive in the line itself
    assert parsed["extras"]["layer_norm"]["fwd_speedup"] == 1.5
    assert parsed["extras"]["matmul_roof_tflops"] == 100.0


def test_spilled_sections_merge_back_from_sidecar(tmp_path, monkeypatch):
    """A record whose gated section was size-spilled to the sidecar must
    still be enforced — the gate merges it back (r5 incident: the grown
    summary line spilled layer_norm and would have un-gated it)."""
    import tests.L0.test_kernel_defaults as mod

    rec = {"parsed": {"extras": {
        "bench_schema": 3,
        "spilled_to_sidecar": ["layer_norm"],
    }}}
    (tmp_path / "BENCH_r42.json").write_text(json.dumps(rec))
    (tmp_path / "BENCH_TOPOPS.json").write_text(json.dumps({
        "layer_norm": {"fwd_speedup": 1.5, "bwd_speedup": 0.17}}))
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    with pytest.raises(AssertionError, match="bwd_speedup = 0.17"):
        mod.test_every_default_wins_in_latest_record()


def test_summary_line_fits_when_extras_are_long_strings():
    """A run where every microbench fails leaves only long *_error
    strings in extras — those must spill too (review finding: strings
    alone recreated the oversized-line incident)."""
    import bench

    extras = {"bench_schema": 3}
    for i in range(12):
        extras[f"bench_{i}_error"] = "RuntimeError(" + "x" * 200 + ")"
    record = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
              "extras": extras}
    line, spilled = bench._emit_record(record)
    assert len(line) <= bench.SUMMARY_LINE_LIMIT
    assert json.loads(line)["extras"]["bench_schema"] == 3
    assert spilled

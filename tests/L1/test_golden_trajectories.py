"""Golden loss-trajectory regression against COMMITTED baselines.

VERDICT r5 "What's missing" #1 / ISSUE 2 satellite: the same-process
bitwise checks in test_cross_product.py catch nondeterminism but not
drift introduced by a code change *between commits* — the reference's
L1 catches exactly that by diffing against dumped baseline files
(/root/reference/tests/L1/common/compare.py:40-64).  Here every
cross-product cell (plus the 1.3B-flagship toy cell) is compared
fp32-bit-exactly against ``tests/L1/baselines/<cell>.json``.

Regeneration protocol (one line) — after an INTENDED numerics change::

    REGEN_BASELINES=1 python -m pytest tests/L1/test_golden_trajectories.py -q

then commit the baseline diff; the changed cells name exactly what
moved.  Baselines are recorded on the tier-1 platform (CPU,
JAX_PLATFORMS=cpu, emulated 8-device mesh); bit-exactness is a
per-platform+jax-version contract, which is the CI environment's.
"""

import os

import jax
import jaxlib
import pytest

from tests.L1.common.harness import (
    RunConfig,
    load_baseline,
    run_bert_trajectory,
    run_flagship_trajectory,
    run_trajectory,
    save_baseline,
)

REGEN = os.environ.get("REGEN_BASELINES", "0") == "1"

# the L1 cross-product cells (test_cross_product.py), abbreviated to the
# determinism-tested opt levels plus both optimizers; steps kept short —
# drift shows up in step 1, not step 12
CELLS = {
    "resnet_o0_adam": RunConfig(model="resnet", opt_level="O0",
                                loss_scale=1.0, steps=6),
    "resnet_o2_adam": RunConfig(model="resnet", opt_level="O2", steps=6),
    "resnet_o2_lamb": RunConfig(model="resnet", opt_level="O2",
                                optimizer="lamb", steps=6),
    "resnet_o3_adam": RunConfig(model="resnet", opt_level="O3",
                                loss_scale=1.0, steps=6),
    "gpt_o0_adam": RunConfig(model="gpt", opt_level="O0", steps=6,
                             lr=5e-3),
    "gpt_o2_adam": RunConfig(model="gpt", opt_level="O2", steps=6,
                             lr=5e-3),
}


def _check(name, traj):
    if REGEN:
        save_baseline(name, traj, meta=(
            f"cell {name}; jax {jax.__version__}, jaxlib "
            f"{jaxlib.__version__}; see module docstring for the "
            "regeneration protocol"))
        pytest.skip(f"baseline {name} regenerated — commit the diff")
    stored = load_baseline(name)
    assert stored is not None, (
        f"no committed baseline for {name}: run REGEN_BASELINES=1 "
        "python -m pytest tests/L1/test_golden_trajectories.py and "
        "commit tests/L1/baselines/")
    mism = [(i, a, b) for i, (a, b) in enumerate(zip(traj, stored))
            if a != b]
    assert len(traj) == len(stored) and not mism, (
        f"{name}: trajectory drifted from the committed baseline at "
        f"{mism[:3]} — if the numerics change is intended, regenerate "
        "(module docstring) and commit the baseline diff")


@pytest.mark.parametrize("name", sorted(CELLS))
def test_golden_trajectory(name):
    _check(name, run_trajectory(CELLS[name]))


def test_golden_trajectory_bert_toy_varlen():
    """Toy BERT MLM over packed varlen inputs (segment ids + restarting
    positions, flash path) — covers the r7 varlen fast path and the
    bert_large bench construction (ISSUE 5 satellite)."""
    _check("bert_toy_varlen", run_bert_trajectory(steps=6))


def test_golden_trajectory_gpt1p3b_toy():
    """The flagship construction (d=128 head geometry, ZeRO bf16_fit
    over the emulated mesh) at toy depth — covers the gpt1p3b bench
    path end-to-end (ISSUE 2 satellite)."""
    _check("gpt1p3b_toy_zero", run_flagship_trajectory(steps=6))


def test_golden_trajectory_gpt1p3b_toy_data(tmp_path):
    """The toy flagship fed by the fault-tolerant record pipeline
    (deterministic checksummed shards → ShardedRecordIterator) — the
    golden the ISSUE 7 exactly-once kill/resume tests replay against:
    any drift here means the data stream, not just the step, changed."""
    from tests.L1.common.harness import run_flagship_data_trajectory

    _check("gpt1p3b_toy_data", run_flagship_data_trajectory(str(tmp_path)))

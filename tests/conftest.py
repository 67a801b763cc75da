"""Test harness: force an 8-device CPU mesh before any test imports jax.

The reference tests multi-GPU behavior only with real GPUs under a launcher
(SURVEY.md §4); JAX lets the whole "distributed" tier run on emulated host
devices, so every test here — including 8-way data/tensor/pipeline-parallel
tests — runs on CPU in CI.

Tests run on the CPU whatever ``JAX_PLATFORMS`` says: the platform is set
with ``jax.config.update``, which also holds when jax was imported before
this file.  ``XLA_FLAGS`` is honored because the CPU backend only parses
it at first backend initialisation, which happens inside the tests.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import shutil  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def chaos_ckpt_dir(tmp_path):
    """Checkpoint dir for fault-injection tests, with crash-proof teardown.

    Chaos tests deliberately leave the checkpoint layer mid-operation
    (simulated preemption, injected write failures).  This fixture
    guarantees that no matter how the test ends: (1) any installed storage
    fault hook is cleared, (2) the background writer is drained with parked
    errors swallowed (one test's injected failure must not surface at the
    next test's fence), and (3) the directory — including ``.tmp`` crash
    artifacts — is removed."""
    d = tmp_path / "ckpt"
    try:
        yield d
    finally:
        from apex_tpu.checkpoint import checkpoint as _ckpt_mod
        from apex_tpu.resilience import async_checkpoint as _async

        _ckpt_mod.set_fault_hook(None)
        _async.drain(ignore_errors=True)
        shutil.rmtree(d, ignore_errors=True)


@pytest.fixture(autouse=True)
def _model_parallel_state_torn_down():
    """No test inherits another's model-parallel mesh: a test that fails
    between ``initialize_model_parallel`` and its own teardown would
    otherwise fail whichever tp test shares its xdist worker."""
    yield
    parallel_state = sys.modules.get("apex_tpu.transformer.parallel_state")
    if parallel_state is not None:
        parallel_state.destroy_model_parallel()

"""These tests live with the benchmark and are not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They run both window drivers end to end at a tiny size on the CPU
(``run.py`` itself refuses a CPU and has no switch for one)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

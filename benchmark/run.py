#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout for the cell, its
configuration (``benchmark/configs/<config>.json``), its traffic mix
(``benchmark/traffic/<traffic>.json``), its limits
(``benchmark/limits/<workload>.json``) and, in a traced run, the
per-layer readers (``benchmark/metrics/<metric>.py``).  The window
driver is ``benchmark/drive_<kind>.py``, by the configuration's
``kind``.  Nothing here is particular to one cell.

It needs the TPU the cell asks for: with another backend, a device the
peaks table does not know, or fewer chips, it exits 2 and prints no
result.  The last line of standard output is the result, one JSON
object; the numbers compared stand beside their limits as the last
lines of standard error and under ``checks``, the result's last key.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import importlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def metrics_of(manifest: dict, group: str, cell: dict) -> list:
    """The metrics of ``group`` this cell reports: those that list it
    under ``workloads``, and of those without the key, the end-to-end
    ones (every cell) and the per-layer ones whose ``moves`` the cell
    reports."""
    e2e = [m["name"] for m in manifest["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if group == "end_to_end":
        return e2e
    return [m for m in manifest["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def require_chip(chips: int, peaks: dict):
    """(devices, peak) or exit 2: the benchmark has no CPU mode."""
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    kind = devices[0].device_kind
    if backend != "tpu" or kind not in peaks or len(devices) < chips:
        print(f"run.py: needs {chips} TPU chip(s) of a kind in peaks.json; "
              f"found backend {backend!r}, {len(devices)} x {kind!r}",
              file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips], peaks[kind]


def read_layer_metric(metric: dict, result, ctx):
    """Import ``metrics/<name>.py`` and call its ``read(result, ctx)``;
    None where it finds nothing to read."""
    path = os.path.join(HERE, "metrics", metric["name"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric["name"].replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(result, ctx)


def result_line(result, manifest, cell, devices, ctx) -> dict:
    import trace_reduce

    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": result.memory_peak_bytes}
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed}
    if ctx.trace:
        metrics = {}
        for metric in metrics_of(manifest, "per_layer", cell):
            value = read_layer_metric(metric, result, ctx)
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
        t0, t1 = result.trace_window_ns
        device["busy_s"] = trace_reduce.busy_seconds(result.trace, t0, t1)
        device["window_s"] = result.trace_window_s
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = trace_reduce.breakdown(result.trace, t0, t1)
    else:
        values = dict(result.end_to_end)
        values["setup_s"] = result.window_start - ctx.t_process
        line["metrics"] = {
            name: {"value": values[name], "unit": units[name]}
            for name in metrics_of(manifest, "end_to_end", cell)}
        line["device"] = device
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in result.checks}
    return line


def open_cell(name: str, *, seed: int, seconds: float, trace: bool = False,
              t_process: float = None):
    """The cell's files, the chip it needs and the compile cache:
    (manifest, devices, Context).  ``run.py``, ``control.py`` and
    ``sweep.py`` all start here."""
    manifest = load_manifest()
    cell = cell_of(manifest, name)
    config = harness.load_json("configs", cell["config"] + ".json")
    if config["chips"] != cell["chips"]:
        raise SystemExit("run.py: the cell and its configuration disagree "
                         "on the number of chips")
    devices, peak = require_chip(cell["chips"],
                                 harness.load_json("peaks.json"))

    import jax
    from apex_tpu.utils import configure_compile_cache

    configure_compile_cache()
    # every program, however quick to compile, is found again by the
    # next run of this checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    ctx = harness.Context(
        workload=cell, config=config,
        mix=harness.load_json("traffic", cell["traffic"] + ".json"),
        limits=harness.load_json("limits", cell["name"] + ".json"),
        peak=peak, seed=seed, seconds=seconds, trace=trace,
        t_process=time.perf_counter() if t_process is None else t_process)
    return manifest, devices, ctx


def main(argv=None) -> int:
    args = parse(argv)
    manifest, devices, ctx = open_cell(
        args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_process=T_PROCESS)
    cell, config = ctx.workload, ctx.config
    driver = importlib.import_module("drive_" + config["kind"])
    result = driver.run(ctx)
    line = result_line(result, manifest, cell, devices, ctx)
    extra = {k: v for k, v in result.counters.items()
             if not isinstance(v, (list, dict))}
    print("run.py: counters " + json.dumps(extra), flush=True)
    for check in result.checks:
        print(f"run.py: compared {check.name} = {check.value!r} "
              f"limit {check.limit!r} {'ok' if check.ok else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

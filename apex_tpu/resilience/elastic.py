"""Elastic-mesh resilience: sharded ZeRO checkpoints, collective
watchdog, and device-loss recovery.

PR 1 made single-process training preemption-safe and the flagship path
made GPT-1.3B ZeRO-sharded over the mesh "data" axis — this module is
where the two meet, the way Megatron-LM's ``--use-dist-ckpt`` sharded
state and TorchElastic's shrink-and-resume semantics meet in the
reference ecosystem (PAPERS.md):

- **sharded checkpoints** — :func:`save_zero_checkpoint` writes each
  data-axis rank's optimizer partition to its own ``shard_<r>.npz``
  with a per-shard CRC32 digest and a topology record in the manifest
  (format 3, :func:`apex_tpu.checkpoint.save_checkpoint` with
  ``shard_axis``); replicated params are stored once;
- **cross-topology restore** — a manifest saved on an N-device mesh
  restores onto an M-device mesh (including the M=1 debug restore):
  :func:`restore_zero_checkpoint` builds the M-topology target from
  the caller's state template and lets
  :func:`~apex_tpu.checkpoint.restore_checkpoint` re-partition the
  flat-buffer stacks (concat N → re-split M; only flat-schema tail
  padding may be trimmed/zero-filled).  The fit-plan dtype story rides
  the existing precision portability: bf16 state is stored as fp32, so
  a ``bf16_fit`` save round-trips any reshard at ≤ 1 bf16 ulp (0 in
  practice — bf16→fp32→bf16 is exact);
- **collective watchdog** — :class:`Watchdog` arms a timeout before
  each collective-bearing train step; on overrun it logs per-device
  last-heartbeat ages and step-duration percentiles (the straggler
  diagnostic) and escalates to the PR 1
  :class:`~apex_tpu.resilience.preemption.GracePeriodHandler`
  save-and-exit path;
- **device-loss recovery** — :func:`run_elastic_training` drives the
  resilient loop; when a step raises
  :class:`~apex_tpu.resilience.chaos.DeviceLossError` (injected
  deterministically by the chaos tier; a real deployment maps device
  failure to the same exception) it rebuilds the ZeRO step on the
  surviving submesh and resumes from the newest *intact* sharded
  checkpoint.

ISSUE 6 generalized all of this from the single "data" axis to the
full dp×tp×pp ``parallel_state`` mesh: format-4 multi-axis sharded
checkpoints (``shard_axes``, shard files keyed by mesh coordinates),
cross-topology restore across any (dp, tp, pp) reshape,
:func:`best_surviving_submesh` recovery (largest-divisor per axis,
shrinking dp before tp before pp), and per-axis watchdog stall
attribution (``Watchdog(mesh=...)`` → ``axis_groups`` in the hang
report).  See docs/resilience.md "3D topologies".

Escalation is cooperative, like everything in the grace-period design:
a watchdog firing flips the handler's stop flag, and the loop (which is
presumed stuck *slow*, not stuck *dead*) saves and exits at the next
step boundary.  A truly wedged collective needs the platform's external
watchdog to SIGTERM the process — which lands in the same
GracePeriodHandler path.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Any, Callable, Optional, Sequence

log = logging.getLogger("apex_tpu.resilience")


class WatchdogTimeout(RuntimeError):
    """A watched step overran its deadline and no escalation target
    (handler / on_hang) was configured to absorb it."""


def _percentiles(durations: Sequence[float]) -> dict:
    if not durations:
        return {}
    s = sorted(durations)
    pick = lambda q: s[min(len(s) - 1, int(q * (len(s) - 1) + 0.5))]
    return {"p50": pick(0.50), "p90": pick(0.90), "p99": pick(0.99),
            "max": s[-1], "n": len(s)}


class Watchdog:
    """Deadline monitor for collective-bearing train steps.

    Arm it around each step::

        wd = Watchdog(timeout=30.0, handler=grace_handler)
        for step, batch in enumerate(batches):
            with wd.step(step):
                state = train_step(state, batch)   # collectives inside

    A single daemon monitor thread checks the armed deadline.  On
    overrun it fires **once** per armed step: builds a diagnostic
    :meth:`report` (per-device last-heartbeat ages — a straggling or
    lost device shows up as the stale one — plus step-duration
    percentiles over the last ``history`` steps), logs it, and
    escalates, in order of availability:

    1. ``on_hang(report)`` callback, if given;
    2. ``handler.request_stop(reason=...)`` — the
       :class:`~apex_tpu.resilience.preemption.GracePeriodHandler`
       grace path: the loop writes a final checkpoint and exits
       cleanly at the next step boundary;
    3. neither configured: :class:`WatchdogTimeout` is raised at the
       next :meth:`step` entry (a hang must never be silent).

    ``timeout`` may be a number (seconds) or a callable
    ``durations -> seconds`` for an adaptive deadline (e.g. ``lambda d:
    10 * max(d[-20:])``); an adaptive deadline is UNARMED (infinite)
    until the first step completes and its duration history exists.

    Heartbeat granularity: the host observes step *completion*, which
    is a whole-mesh barrier — so by default every device in ``devices``
    (default: all local) is stamped together at each successful step,
    and the per-device ages diverge only via :meth:`mark_lost` (stops
    expecting a device, annotating it as gone rather than stale) or
    :meth:`beat` (integrations with a genuine per-device liveness
    signal — e.g. a platform health poller — call it to give the hang
    report real per-device resolution).
    """

    def __init__(self, timeout, *, handler=None,
                 on_hang: Optional[Callable[[dict], None]] = None,
                 devices: Optional[Sequence] = None,
                 history: int = 256, poll_interval: Optional[float] = None,
                 telemetry=None, mesh=None,
                 mesh_axes: Optional[dict] = None,
                 device_coords: Optional[dict] = None):
        self.timeout = timeout
        self.handler = handler
        self.on_hang = on_hang
        # optional TelemetryBus: every fire emits a typed `watchdog`
        # event (the report rides the flight-recorder ring into any
        # postmortem); emitted from the monitor thread — the bus is
        # thread-safe by contract
        self.telemetry = telemetry
        # per-axis attribution (ISSUE 6): give the watchdog the mesh
        # decomposition and its hang report names the dp/tp/pp GROUP
        # that stalled, not just the device.  Either pass ``mesh`` (a
        # jax.sharding.Mesh — axis names and coordinates are derived)
        # or explicit ``mesh_axes`` ({axis: size}, mesh order) +
        # ``device_coords`` ({device id: coordinate tuple}).
        if mesh is not None and mesh_axes is None:
            import numpy as _np

            arr = _np.asarray(mesh.devices)
            mesh_axes = {str(a): int(n)
                         for a, n in zip(mesh.axis_names, arr.shape)}
            device_coords = {
                getattr(arr[idx], "id", arr[idx]): tuple(int(i)
                                                         for i in idx)
                for idx in _np.ndindex(arr.shape)}
            if devices is None:
                devices = list(arr.reshape(-1))
        self.mesh_axes = dict(mesh_axes) if mesh_axes else None
        self.device_coords = dict(device_coords) if device_coords else None
        if devices is None:
            import jax

            devices = jax.devices()
        self.device_ids = [getattr(d, "id", d) for d in devices]
        self.history = int(history)
        self.poll_interval = poll_interval
        self.durations: list = []
        self.last_beat = {d: None for d in self.device_ids}
        self.lost: set = set()
        self.fired_steps: list = []
        self.last_report: Optional[dict] = None
        self._armed_step: Optional[int] = None
        self._deadline: Optional[float] = None
        self._fired_this_arm = False
        self._pending_raise: Optional[dict] = None
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._thread: Optional[threading.Thread] = None

    # -- arming ----------------------------------------------------------

    def _current_timeout(self) -> float:
        if callable(self.timeout):
            if not self.durations:
                # adaptive deadlines have nothing to adapt to before
                # the first completed step: stay unarmed rather than
                # crash the documented `lambda d: 10 * max(d[-20:])`
                return float("inf")
            return float(self.timeout(self.durations))
        return float(self.timeout)

    def step(self, step_index: int):
        """Context manager arming the deadline for one train step."""
        return _ArmedStep(self, int(step_index))

    def _arm(self, step_index: int) -> None:
        with self._lock:
            if self._pending_raise is not None:
                report, self._pending_raise = self._pending_raise, None
                raise WatchdogTimeout(
                    f"step {report['step']} overran the "
                    f"{report['timeout']:.3g}s watchdog deadline "
                    f"(report: {report})")
            self._armed_step = step_index
            self._fired_this_arm = False
            self._deadline = time.monotonic() + self._current_timeout()
        self._ensure_thread()
        self._wake.set()

    def _disarm(self, step_index: int, duration: float, ok: bool) -> None:
        with self._lock:
            self._armed_step = None
            self._deadline = None
            if ok:
                self.durations.append(duration)
                del self.durations[: -self.history]
                now = time.monotonic()
                for d in self.device_ids:
                    if d not in self.lost:
                        self.last_beat[d] = now

    # -- diagnosis -------------------------------------------------------

    def mark_lost(self, device_ids) -> None:
        """Stop expecting heartbeats from ``device_ids`` (they are gone,
        not straggling)."""
        self.lost.update(getattr(d, "id", d) for d in device_ids)

    def beat(self, device_id) -> None:
        """Record a genuine per-device liveness observation (platform
        health poller, per-device completion event).  Without these,
        the host only sees whole-mesh step completion and all live
        devices carry the same age."""
        self.last_beat[getattr(device_id, "id", device_id)] = (
            time.monotonic())

    def step_percentiles(self) -> dict:
        """Duration percentiles over the retained step history."""
        return _percentiles(self.durations)

    def max_heartbeat_age(self) -> Optional[float]:
        """Age in seconds of the STALEST live device's last heartbeat
        (None before any step completes).  The log-line stall signal:
        a climbing age means the mesh stopped completing steps well
        before the deadline escalates."""
        now = time.monotonic()
        ages = [now - t for d, t in self.last_beat.items()
                if t is not None and d not in self.lost]
        return max(ages) if ages else None

    def axis_report(self) -> Optional[dict]:
        """Per-axis stall attribution (requires mesh_axes/device_coords):
        for every mesh axis, each coordinate group's stalest live
        heartbeat age and lost-device list, plus ``suspect`` — per axis,
        the group index holding the overall stalest (or a lost) device.
        A tp group whose collective wedged shows up as ONE suspect
        tensor index with every data index implicated symmetrically —
        the signature that distinguishes a tp-leg fault from a dp
        straggler."""
        if not self.mesh_axes or not self.device_coords:
            return None
        now = time.monotonic()
        axes = list(self.mesh_axes)
        groups: dict = {a: {} for a in axes}
        never = {a: set() for a in axes}
        for d, coords in self.device_coords.items():
            age = None
            t = self.last_beat.get(d)
            if t is not None and d not in self.lost:
                age = round(now - t, 3)
            for ai, a in enumerate(axes):
                g = groups[a].setdefault(int(coords[ai]),
                                         {"max_age_s": None, "lost": []})
                if d in self.lost:
                    g["lost"].append(d)
                elif age is not None and (g["max_age_s"] is None
                                          or age > g["max_age_s"]):
                    g["max_age_s"] = age
                elif age is None:
                    # a live device that NEVER heartbeat is infinitely
                    # stale, not infinitely fresh — score it as such so
                    # a group wedged before its first completed step
                    # cannot make a healthy, freshly-beaten group the
                    # suspect (the report keeps max_age_s None: "no
                    # observation", JSON-safe)
                    never[a].add(int(coords[ai]))
        suspect = {}
        for a in axes:
            scored = [(gi, (len(g["lost"]),
                            float("inf") if gi in never[a]
                            else g["max_age_s"] or 0.0))
                      for gi, g in sorted(groups[a].items())]
            if not scored:
                continue
            worst = max(scored, key=lambda x: x[1])
            best = min(scored, key=lambda x: x[1])
            # only name a suspect when the axis actually DIVERGES —
            # identical ages on every group (the healthy whole-mesh
            # barrier case) implicate nothing
            if worst[1] > best[1]:
                suspect[a] = worst[0]
        return {"mesh_axes": dict(self.mesh_axes),
                "groups": {a: {str(k): v for k, v in sorted(gs.items())}
                           for a, gs in groups.items()},
                "suspect": suspect}

    def report(self) -> dict:
        """Straggler diagnostic: per-device heartbeat age + percentiles
        (+ per-axis group attribution when the mesh decomposition is
        configured)."""
        now = time.monotonic()
        ages = {d: (None if t is None else round(now - t, 3))
                for d, t in self.last_beat.items()}
        out = {
            "step": self._armed_step,
            "timeout": self._current_timeout(),
            "device_heartbeat_age_s": ages,
            "lost_devices": sorted(self.lost),
            "step_duration_percentiles": self.step_percentiles(),
        }
        ax = self.axis_report()
        if ax is not None:
            out["axis_groups"] = ax
        return out

    @property
    def expired(self) -> bool:
        """True once any armed step has overrun its deadline."""
        return bool(self.fired_steps)

    # -- monitor thread --------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="apex-tpu-watchdog", daemon=True)
            self._thread.start()

    def _run(self) -> None:
        while not self._stop:
            with self._lock:
                deadline = self._deadline
                armed = (self._armed_step is not None
                         and not self._fired_this_arm)
            if not armed or deadline is None:
                self._wake.wait(timeout=0.1)
                self._wake.clear()
                continue
            wait = deadline - time.monotonic()
            if wait > 0:
                quantum = self.poll_interval or max(0.005, min(wait, 0.05))
                time.sleep(min(wait, quantum))
                continue
            self._fire()

    def _fire(self) -> None:
        with self._lock:
            if self._fired_this_arm or self._armed_step is None:
                return
            self._fired_this_arm = True
            step = self._armed_step
        report = self.report()
        report["step"] = step
        self.fired_steps.append(step)
        self.last_report = report
        log.error("watchdog: step %d overran its %.3gs deadline — %s",
                  step, report["timeout"], report)
        if self.telemetry is not None:
            try:
                self.telemetry.emit("watchdog", step=step, report=report)
            except Exception:  # pragma: no cover — never break escalation
                log.exception("watchdog telemetry emit failed")
        if self.on_hang is not None:
            self.on_hang(report)
        elif self.handler is not None:
            self.handler.request_stop(
                reason=f"watchdog_timeout(step={step})")
        else:
            with self._lock:
                self._pending_raise = report

    def close(self) -> None:
        self._stop = True
        self._wake.set()

    def __enter__(self) -> "Watchdog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class _ArmedStep:
    def __init__(self, wd: Watchdog, step_index: int):
        self.wd = wd
        self.step_index = step_index
        self.t0 = 0.0

    def __enter__(self):
        self.wd._arm(self.step_index)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, *exc):
        self.wd._disarm(self.step_index, time.monotonic() - self.t0,
                        ok=exc_type is None)


# ---------------------------------------------------------------------------
# Sharded ZeRO checkpoint convenience wrappers
# ---------------------------------------------------------------------------


def save_zero_checkpoint(ckpt_dir: str, state: Any, *, step: int,
                         shardings: Any, shard_axis: Optional[str] = "data",
                         **kw) -> str:
    """Sharded save of a ZeRO train state: leaves whose spec leads with
    ``shard_axis`` (the per-rank optimizer partitions, leading
    ``[n_shards]`` axis) go to per-shard files with per-shard CRC32
    digests; replicated leaves are stored once.  This is the ONE save
    entry that owns the interchange view: a live ``ShardedOptState`` in
    ``state`` (the flagship step's: moments 1-D) is written through
    :func:`~apex_tpu.contrib.optimizers.stacked_zero_state`, which is
    what ``shardings`` describes (``FlagshipSetup.stacked_shardings``)
    and what the manifest records; a state already stacked passes
    through.  The view is a host transfer, so it is taken where
    :func:`apex_tpu.checkpoint.save_checkpoint` takes its own snapshot:
    on the writing process only, and after the fence on an earlier
    async write, so two host copies of the state never coexist.
    Otherwise a thin veneer over ``save_checkpoint`` — all its knobs
    (``blocking``, ``retry``, ``keep``, and the format-4 multi-axis
    ``shard_axes=`` mapping, which supersedes ``shard_axis``) pass
    through."""
    import jax

    from apex_tpu import checkpoint as ckpt
    from apex_tpu.contrib.optimizers import stacked_zero_state
    from apex_tpu.resilience.async_checkpoint import wait_for_save

    if kw.get("shard_axes") is not None:
        shard_axis = None  # multi-axis form supersedes the default axis
    if jax.process_index() != 0:
        return ckpt.step_dir(ckpt_dir, step)
    wait_for_save()
    return ckpt.save_checkpoint(ckpt_dir, stacked_zero_state(state),
                                step=step, shardings=shardings,
                                shard_axis=shard_axis, **kw)


def restore_zero_checkpoint(ckpt_dir: str, target: Any, *, mesh=None,
                            shardings: Any = None,
                            max_fallbacks: Optional[int] = None):
    """Cross-topology resilient restore: the newest *intact* sharded
    checkpoint under ``ckpt_dir``, re-partitioned to ``target``'s
    topology (whatever shard count it carries — build the target with
    the CURRENT mesh's ``build_flagship_train_step`` and an 8-device
    save restores onto 4 devices, or 1).  ``target`` may hold the ZeRO
    state live (moments 1-D: a saved leaf's logical value is the
    C-order flattening of its stack, which IS the live moment) or
    stacked; the result has the target's form.  Walks corrupt
    candidates newest-first exactly like
    :func:`~apex_tpu.resilience.restore_resilient` (it IS that
    function; this alias exists so call sites read as topology-aware)."""
    from apex_tpu.resilience.restore import restore_resilient

    return restore_resilient(ckpt_dir, target, mesh=mesh,
                             shardings=shardings,
                             max_fallbacks=max_fallbacks)


# ---------------------------------------------------------------------------
# Elastic training: shrink the mesh on device loss and keep going
# ---------------------------------------------------------------------------


def largest_divisor_submesh(devices: Sequence, batch_size: int) -> list:
    """The largest prefix of ``devices`` whose length divides
    ``batch_size`` — the standard ``select_devices`` policy for
    :func:`run_elastic_training`: a data-sharded step needs the global
    batch to divide the mesh's data axis, so losing 2 of 8 devices
    (6 survivors) must rebuild on 4, not 6."""
    devices = list(devices)
    for m in range(len(devices), 0, -1):
        if batch_size % m == 0:
            return devices[:m]
    return devices[:1]


def best_surviving_submesh(survivors: Sequence, mesh_shape,
                           *, batch_size: Optional[int] = None):
    """Pick the best (dp, tp, pp) submesh fitting on the survivors — the
    3-D generalization of :func:`largest_divisor_submesh` and the
    default ``select_mesh`` policy of :func:`run_elastic_training`.

    Per axis the candidate sizes are the divisors of the old size
    (largest-divisor policy); the search prefers to **shrink dp before
    tp before pp** — i.e. it keeps the pipeline depth if at all
    possible (a pp change re-maps every stage's layer slices), then the
    tensor width (a tp change re-slices every weight), and takes the
    shrink out of the data axis, whose reshard is pure flat-buffer
    re-partition.  ``batch_size`` additionally requires the chosen dp
    to divide the global batch.  Returns ``(devices, (dp, tp, pp))`` —
    the first dp·tp·pp survivors and the chosen shape."""
    dp, tp, pp = (int(x) for x in mesh_shape)
    survivors = list(survivors)
    n = len(survivors)

    def _divisors_desc(k):
        return [d for d in range(k, 0, -1) if k % d == 0]

    for pp_c in _divisors_desc(pp):
        for tp_c in _divisors_desc(tp):
            for dp_c in _divisors_desc(dp):
                if dp_c * tp_c * pp_c > n:
                    continue
                if batch_size is not None and batch_size % dp_c:
                    continue
                return survivors[: dp_c * tp_c * pp_c], (dp_c, tp_c, pp_c)
    return survivors[:1], (1, 1, 1)


@dataclasses.dataclass
class ElasticResult:
    """Outcome of :func:`run_elastic_training`."""

    state: Any
    step: int
    restarts: int
    devices: list                 # surviving devices at exit
    lost_devices: list            # ids lost along the way
    preempted: bool
    stop_reason: Optional[str]
    loop_results: list            # per-attempt LoopResult
    mesh_shape: Optional[tuple] = None  # (dp, tp, pp) at exit (3-D runs)


def run_elastic_training(
    build: Callable[[Sequence], tuple],
    devices: Sequence,
    batches: Optional[Sequence] = None,
    *,
    data_iter=None,
    ckpt_dir: str,
    save_every: int = 1,
    keep: Optional[int] = None,
    shard_axis: str = "data",
    handler=None,
    watchdog: Optional[Watchdog] = None,
    guard=None,
    max_restarts: int = 3,
    min_devices: int = 1,
    select_devices: Optional[Callable[[list], list]] = None,
    mesh_shape: Optional[Sequence[int]] = None,
    select_mesh: Optional[Callable] = None,
    batch_size: Optional[int] = None,
    start_step: int = 0,
    on_step: Optional[Callable[[int], None]] = None,
    log_every: int = 0,
    log_fn: Optional[Callable[[str], None]] = None,
    telemetry=None,
    telemetry_scalars=None,
    profile_sampler=None,
):
    """Drive ZeRO training across device loss.

    ``build(devices) -> (step_fn, state, shardings)`` constructs the
    train step for a given device set — for the flagship this wraps
    :func:`~apex_tpu.transformer.testing.build_flagship_train_step`
    (whose ZeRO state is live, moments 1-D over ``n_shards`` shards,
    and whose ``shardings`` describe its stacked view and lead with
    ``shard_axis`` for the per-rank partition leaves: the loop's
    sharded saves take that view, :func:`save_zero_checkpoint`).  The
    returned ``state`` doubles as the restore *target*: its topology
    defines the M of any N→M reshard.

    The inner loop is
    :func:`~apex_tpu.transformer.testing.run_resilient_training` with
    sharded saves (``shard_axis``).  When a step (or ``on_step`` hook)
    raises :class:`~apex_tpu.resilience.chaos.DeviceLossError`, the
    harness:

    1. drops the lost devices (``watchdog.mark_lost`` when a watchdog
       is attached — their heartbeats become diagnostic, not noise);
    2. rebuilds via ``build(survivors)`` — a fresh mesh and ZeRO step
       over the shrunken "data" axis;
    3. restores the newest intact sharded checkpoint cross-topology
       into the rebuilt state (N→M re-partition of every flat-buffer
       stack);
    4. resumes from the restored step with the remaining ``batches``
       (which must therefore be a Sequence, not a one-shot iterator).

    ``data_iter`` (instead of ``batches``, ISSUE 7): a checkpointable
    input-pipeline iterator (``state_dict``/``load_state_dict`` — e.g.
    :class:`apex_tpu.data.ShardedRecordIterator`, optionally behind
    :class:`~apex_tpu.data.AsyncPrefetcher`).  Saves then carry the
    iterator position in the checkpoint manifest (``data_state``), and
    the device-loss recovery arc restores it alongside the model state
    — *cross-topology included*: the iterator's slot-cursor state is
    dp-decomposition-independent, so a dp→dp' rebuild re-partitions
    shard ownership by re-slicing while the consumed sample-id stream
    stays bitwise identical to an uninterrupted run (docs/data.md).  A
    plain generator here is rejected (silent replay of training data is
    exactly the failure mode this parameter closes); a recovery that
    finds a checkpoint saved *without* ``data_state`` raises instead of
    guessing the position.

    ``select_devices(survivors) -> devices`` picks the rebuild submesh
    from the raw survivor list — a data-sharded step needs the global
    batch to divide the mesh, so losing 2 of 8 devices usually means
    rebuilding on 4 of the 6 survivors
    (:func:`largest_divisor_submesh` is the standard policy); default
    uses every survivor.

    **3-D meshes** (ISSUE 6): pass ``mesh_shape=(dp, tp, pp)``.  The
    harness then calls ``build(devices, mesh_shape=shape)``, saves
    *format-4* multi-axis sharded checkpoints (``shard_axes`` over the
    full ``parallel_state`` mesh — shard files keyed by (d, p, t)
    coordinates), and on device loss picks the best surviving 3-D
    submesh via ``select_mesh(survivors, mesh_shape) -> (devices,
    shape)`` (default :func:`best_surviving_submesh` with
    ``batch_size`` — largest-divisor per axis, shrinking dp before tp
    before pp) before rebuilding through ``parallel_state`` and
    restoring the multi-axis shard set cross-topology.  A
    ``select_devices`` filter still applies first: the mesh picker
    chooses from the devices the filter allows.  ``device_loss``
    / ``ckpt_restore`` telemetry and the bus mesh stamp then carry the
    full ``mesh_axes`` decomposition, so post-recovery events are
    attributable to the survivor submesh per axis.

    Gives up (re-raises) after ``max_restarts`` rebuilds or when fewer
    than ``min_devices`` survive.  Preemption/watchdog escalation
    behave exactly as in the inner loop: final blocking (sharded) save,
    clean exit with ``preempted=True``.

    ``telemetry`` (:class:`apex_tpu.telemetry.TelemetryBus`): on top of
    the inner loop's events, each recovery emits ``device_loss`` (lost
    ids, survivor count) and ``ckpt_restore`` (resumed step, restore
    wall), books rebuild/restore time against goodput, and re-stamps
    the bus's mesh topology with the survivor submesh so post-recovery
    events are attributable to the shrunken mesh.  The inner loop's
    exception path has already flushed a ``postmortem_*.jsonl`` by the
    time the rebuild starts.  ``profile_sampler`` (ISSUE 9) rides into
    the inner loop unchanged, so phase/collective/HBM attribution keeps
    sampling across rebuilds — post-recovery ``profile`` events carry
    the survivor mesh stamp.
    """
    from apex_tpu.checkpoint.checkpoint import (_complete_steps,
                                                load_data_state)
    from apex_tpu.resilience.chaos import DeviceLossError
    from apex_tpu.transformer.testing import run_resilient_training

    emit = log_fn or (lambda msg: log.info("%s", msg))
    data_initial_state = None
    if data_iter is not None:
        if batches is not None:
            raise ValueError("pass batches OR data_iter, not both")
        if not (hasattr(data_iter, "state_dict")
                and hasattr(data_iter, "load_state_dict")):
            raise TypeError(
                f"data_iter {type(data_iter).__name__} is not "
                "checkpointable (no state_dict/load_state_dict) — an "
                "elastic recovery would silently replay or skip "
                "training data; use apex_tpu.data.ShardedRecordIterator "
                "(or AsyncPrefetcher around it)")
        # a restart before any checkpoint exists must rewind the
        # iterator to where THIS invocation found it, not to zero
        data_initial_state = data_iter.state_dict()
        if (isinstance(data_initial_state, dict)
                and "slots" in data_initial_state
                and len(data_initial_state["slots"])
                != data_initial_state.get("batch_size")):
            # this single-controller loop checkpoints ONE iterator's
            # state; a rank-local (dp_size>1) slot slice would save a
            # partial position that a dp→dp' restore cannot re-slice
            raise ValueError(
                f"data_iter covers only slots "
                f"{data_initial_state['slots']} of the "
                f"{data_initial_state.get('batch_size')}-slot global "
                "batch (a rank-local dp_size>1 iterator).  Drive this "
                "loop with the full-batch iterator (dp_size=1) — "
                "elastic dp→dp' re-partitioning re-slices slot "
                "ownership from the full vector — or merge per-rank "
                "states with apex_tpu.data.merge_data_states in a "
                "multi-process launcher.")
    elif batches is None:
        raise ValueError("run_elastic_training needs batches or data_iter")
    devices = list(devices)
    lost: list = []
    restarts = 0
    loop_results: list = []
    shard_axes = None
    if mesh_shape is not None:
        mesh_shape = tuple(int(x) for x in mesh_shape)

    def _shard_axes(shape):
        dp, tp, pp = shape
        # the parallel_state mesh order — and the stacking order of the
        # flagship opt leaves' stacked view ([dp, pp, tp, shard])
        return {"data": dp, "pipeline": pp, "tensor": tp}

    def _build(devs, shape):
        if shape is None:
            return build(devs)
        return build(devs, mesh_shape=shape)

    if mesh_shape is not None:
        shard_axes = _shard_axes(mesh_shape)
    step_fn, state, shardings = _build(devices, mesh_shape)
    step = start_step

    while True:
        try:
            result = run_resilient_training(
                step_fn, state,
                batches[step - start_step:] if data_iter is None else None,
                data_iter=data_iter,
                ckpt_dir=ckpt_dir, save_every=save_every, keep=keep,
                shardings=shardings,
                shard_axis=None if shard_axes else shard_axis,
                shard_axes=shard_axes,
                handler=handler, guard=guard, watchdog=watchdog,
                start_step=step, on_step=on_step,
                log_every=log_every, log_fn=log_fn,
                telemetry=telemetry, telemetry_scalars=telemetry_scalars,
                profile_sampler=profile_sampler)
            loop_results.append(result)
            return ElasticResult(
                state=result.state, step=result.step, restarts=restarts,
                devices=devices, lost_devices=lost,
                preempted=result.preempted,
                stop_reason=result.stop_reason, loop_results=loop_results,
                mesh_shape=mesh_shape)
        except DeviceLossError as e:
            lost_ids = set(e.device_ids)
            lost.extend(sorted(lost_ids))
            survivors = [d for d in devices
                         if getattr(d, "id", d) not in lost_ids]
            new_shape = mesh_shape
            if mesh_shape is not None:
                if select_devices is not None:
                    # a device-filter policy (exclude known-bad hosts)
                    # composes with the mesh picker: filter the pool
                    # first, then choose the submesh from what the
                    # policy allows — never silently drop the filter
                    survivors = list(select_devices(survivors))
                picker = select_mesh or (
                    lambda s, shape: best_surviving_submesh(
                        s, shape, batch_size=batch_size))
                survivors, new_shape = picker(survivors, mesh_shape)
                survivors = list(survivors)
            elif select_devices is not None:
                survivors = list(select_devices(survivors))
            restarts += 1
            if telemetry is not None:
                # no step stamp: the loss surfaced as an exception, so
                # the exact faulting step lives in the inner loop's
                # postmortem (already flushed), not here
                ev = dict(
                    device_ids=sorted(lost_ids),
                    survivors=len(survivors), restarts=restarts,
                    recoverable=(restarts <= max_restarts
                                 and len(survivors) >= max(1, min_devices)))
                if new_shape is not None:
                    ev["mesh_axes"] = _shard_axes(new_shape)
                telemetry.emit("device_loss", **ev)
            if restarts > max_restarts:
                raise
            if len(survivors) < max(1, min_devices):
                raise DeviceLossError(
                    e.device_ids,
                    detail=f"only {len(survivors)} devices survive, "
                           f"min_devices={min_devices}") from e
            if watchdog is not None:
                watchdog.mark_lost(lost_ids)
            devices = survivors
            mesh_shape = new_shape
            if mesh_shape is not None:
                shard_axes = _shard_axes(mesh_shape)
            emit(f"[elastic] lost device(s) {sorted(lost_ids)} — "
                 f"rebuilding on {len(devices)} survivors"
                 + (f" as (dp, tp, pp)={mesh_shape}"
                    if mesh_shape is not None else "")
                 + f" (restart {restarts}/{max_restarts})")
            t_rebuild = time.monotonic()
            step_fn, state, shardings = _build(devices, mesh_shape)
            if telemetry is not None:
                telemetry.accountant().pause(
                    time.monotonic() - t_rebuild, "rebuild")
                stamp = {
                    "n_devices": len(devices),
                    "platform": getattr(devices[0], "platform", "unknown")
                    if devices else "none",
                    "lost_devices": sorted(lost)}
                if mesh_shape is not None:
                    stamp["mesh_axes"] = _shard_axes(mesh_shape)
                telemetry.set_mesh(stamp)
            if _complete_steps(ckpt_dir):
                t_restore = time.monotonic()
                state, step = restore_zero_checkpoint(ckpt_dir, state)
                if data_iter is not None:
                    # same manifest, same step: the iterator resumes at
                    # exactly the sample the restored weights last saw —
                    # across a dp→dp' reshape too (the state is global,
                    # ownership re-slices)
                    ds = load_data_state(ckpt_dir, step=step)
                    if ds is None:
                        raise RuntimeError(
                            f"checkpoint step {step} carries no "
                            "data_state but this run trains from a "
                            "checkpointable data_iter — resuming would "
                            "replay or skip training data.  The "
                            "checkpoint was saved by a loop without "
                            "data_iter wiring; restart from a caller "
                            "that manages the position.") from e
                    data_iter.load_state_dict(ds)
                if telemetry is not None:
                    telemetry.accountant().pause(
                        time.monotonic() - t_restore, "restore")
                    telemetry.emit(
                        "ckpt_restore", step=step,
                        wall_ms=round((time.monotonic() - t_restore) * 1e3,
                                      3),
                        n_shards=len(devices), reason="device_loss")
                if step < start_step:
                    # the caller only holds batches for steps >=
                    # start_step; a negative batches slice would
                    # silently train on the wrong tail of the window
                    raise RuntimeError(
                        f"elastic restore fell back to step {step}, "
                        f"before this run's start_step={start_step} — "
                        "the batches for that range are not available "
                        "here; restart the job from a caller that "
                        "holds them") from e
                emit(f"[elastic] resumed from sharded checkpoint step "
                     f"{step} on the {len(devices)}-device submesh")
            else:
                step = start_step
                if data_iter is not None:
                    data_iter.load_state_dict(data_initial_state)
                emit("[elastic] no checkpoint yet — restarting from "
                     f"step {step}")

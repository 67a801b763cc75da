"""Sharded, precision-portable checkpoint save/restore.

Replaces the reference's checkpoint story (SURVEY.md §5.4) the TPU way:

- reference examples do plain ``torch.save(state_dict)`` per rank
  (examples/imagenet/main_amp.py:178-193); O2 state dicts are cast to fp32
  via ``O2StateDictHook`` so checkpoints are precision-portable
  (apex/amp/_initialize.py:133-142)
- amp scale state round-trips via ``amp.state_dict()``
  (apex/amp/frontend.py:361-400)
- FP16_Optimizer/DistributedFusedLAMB persist master weights + opt state
  (apex/fp16_utils/fp16_optimizer.py:209-271,
  contrib/optimizers/distributed_fused_lamb.py:140,530)

Here one checkpoint captures the whole train-state pytree at once:

- **Format**: per-step directory ``step_<N>/`` holding ``arrays.npz``
  (flat ``keystr(path) -> ndarray``) + ``manifest.json`` (per-leaf dtype /
  shape / partition spec, mesh axes, step). Atomic via tmp-dir + rename.
- **Precision portability**: half-precision leaves (bf16/fp16) are stored
  as fp32 on disk and restored to the target dtype, so a checkpoint written
  by an O2 run loads into an O0 run and vice versa (O2StateDictHook parity).
- **Topology portability**: leaves are saved as *full* (unsharded) arrays
  with their logical ``PartitionSpec`` recorded; restore takes any ``mesh``
  — including one of a different data-parallel size — and ``device_put``\\ s
  each leaf with ``NamedSharding(mesh, spec)``. This is the "restart on a
  different-size mesh" design SURVEY §5.3/§5.4 calls for, which the
  reference cannot do (its per-rank torch.save pins world size).

Multi-host note: save fetches fully-addressable values, so in a true
multi-host deployment only process 0 writes (guarded below); restores are
per-process and re-shard via device_put.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import shutil
import time
import zlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from apex_tpu.multi_tensor import flat as _flat

_MANIFEST = "manifest.json"
_ARRAYS = "arrays.npz"
_PACK = "arrays.pack"
_LATEST = "latest"
_PACK_ALIGN = 64


def shard_file(rank: int) -> str:
    """On-disk name of one shard's array file in a format-3 (single-axis)
    sharded checkpoint."""
    return f"shard_{int(rank):05d}.npz"


def shard_file_coords(coords) -> str:
    """On-disk name of one mesh coordinate's array file in a format-4
    (multi-axis) sharded checkpoint: ``shard_<c0>_<c1>_..._<ck>.npz``
    with one coordinate per mesh axis, in the manifest ``topology``'s
    ``mesh_axes`` order."""
    return "shard_" + "_".join(str(int(c)) for c in coords) + ".npz"


def _coord_key(coords) -> str:
    """Manifest key of one shard coordinate (per-leaf ``crc32_shards``
    dict): the leaf's own lead-axis coordinates joined with ``_``."""
    return "_".join(str(int(c)) for c in coords)


class CheckpointCorruptionError(RuntimeError):
    """A checkpoint on disk failed integrity verification (missing files,
    unreadable archive, truncated arrays, or CRC32 digest mismatch)."""


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Exponential-backoff retry for storage I/O during save.

    Transient storage faults (GCS 5xx, NFS hiccups, full-but-recovering
    disks) should not kill a training run mid-save; each save attempt
    rewrites its tmp dir from scratch, so retrying is idempotent."""

    max_attempts: int = 3
    base_delay: float = 0.05  # seconds; doubles per attempt
    max_delay: float = 2.0
    retryable: tuple = (OSError,)

    def delay(self, attempt: int) -> float:
        return min(self.base_delay * (2.0 ** attempt), self.max_delay)


# Test-only fault-injection point (see apex_tpu.resilience.chaos). When set,
# called as hook(event, path) at each storage operation; it may raise to
# simulate a write failure or sleep to simulate slow storage.  Events:
# "write_arrays", "write_shard" (once per rank file of a sharded save),
# "write_manifest", "commit", "read_arrays".
_fault_hook: Optional[Callable[[str, str], None]] = None


def set_fault_hook(hook: Optional[Callable[[str, str], None]]):
    """Install (or clear, with None) the storage fault hook. Returns the
    previous hook so tests can restore it."""
    global _fault_hook
    prev, _fault_hook = _fault_hook, hook
    return prev


def _fault(event: str, path: str) -> None:
    if _fault_hook is not None:
        _fault_hook(event, path)

# dtypes stored as fp32 on disk for precision portability (O2StateDictHook
# parity, _initialize.py:133-142)
_HALF_DTYPES = ("bfloat16", "float16")


def _keystr(path) -> str:
    return jax.tree_util.keystr(path)


def _path_parts(path) -> list:
    """Structured path components (dict keys / attr names / indices as
    strings) — stored in the manifest so ``target=None`` restore does not
    have to re-parse ``keystr`` output (which mangles keys containing
    quotes or brackets)."""
    parts = []
    for e in path:
        if isinstance(e, jax.tree_util.DictKey):
            parts.append(str(e.key))
        elif isinstance(e, jax.tree_util.SequenceKey):
            parts.append(str(e.idx))
        elif isinstance(e, jax.tree_util.GetAttrKey):
            parts.append(str(e.name))
        elif isinstance(e, jax.tree_util.FlattenedIndexKey):
            parts.append(str(e.key))
        else:  # unknown key type: best-effort string
            parts.append(str(e))
    return parts


def _is_spec_leaf(x) -> bool:
    return x is None or isinstance(x, (PartitionSpec, NamedSharding))


def _spec_map(shardings, tree) -> dict:
    """Flatten a ``shardings`` pytree that may be a *structure prefix* of
    ``tree`` into ``{structured-path-tuple: PartitionSpec}`` (a prefix
    spec applies to every leaf under its subtree — same broadcast rule
    as pjit in_shardings).  Keyed by structured path, not keystr, so
    spec association survives keystr mangling/collisions."""
    flat_specs: list = []

    def _collect(spec, subtree):
        if isinstance(spec, NamedSharding):
            spec = spec.spec
        n = len(jax.tree_util.tree_leaves(subtree))
        flat_specs.extend([spec] * n)

    jax.tree_util.tree_map(_collect, shardings, tree, is_leaf=_is_spec_leaf)
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    if len(paths) != len(flat_specs):
        raise ValueError("shardings tree is not a structure prefix of the checkpoint tree")
    return {
        tuple(_path_parts(path)): spec
        for (path, _), spec in zip(paths, flat_specs)
        if spec is not None
    }


def _spec_to_json(spec) -> Optional[list]:
    if spec is None:
        return None
    out = []
    for part in spec:
        if part is None:
            out.append(None)
        elif isinstance(part, (tuple, list)):
            out.append(list(part))
        else:
            out.append(part)
    return out


def _spec_from_json(parts) -> PartitionSpec:
    if parts is None:
        return PartitionSpec()
    return PartitionSpec(*[tuple(p) if isinstance(p, list) else p for p in parts])


def step_dir(ckpt_dir: str, step: int) -> str:
    return os.path.join(ckpt_dir, f"step_{int(step):010d}")


def _complete_steps(ckpt_dir: str) -> list:
    """Steps with a complete (renamed, manifest-bearing) directory. Tolerant
    of crash artifacts: ``step_N.tmp`` leftovers and junk names are skipped."""
    steps = []
    if os.path.isdir(ckpt_dir):
        for name in os.listdir(ckpt_dir):
            if not name.startswith("step_") or name.endswith(".tmp"):
                continue
            digits = name[len("step_"):]
            # int() alone is too permissive ("+3", "1_0", " 3" all parse) and
            # str.isdigit alone accepts Unicode digits int() may reject ("³")
            # — only the exact zero-padded ASCII-decimal form
            # save_checkpoint writes counts as a checkpoint
            if not (digits.isascii() and digits.isdecimal()):
                continue
            s = int(digits)
            if os.path.isfile(os.path.join(ckpt_dir, name, _MANIFEST)):
                steps.append(s)
    return sorted(steps)


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Newest complete checkpoint step in ``ckpt_dir``, or None."""
    marker = os.path.join(ckpt_dir, _LATEST)
    if os.path.exists(marker):
        try:
            with open(marker) as f:
                s = int(f.read().strip())
        except ValueError:
            s = None  # truncated marker from a crashed save — fall through
        if s is not None and os.path.exists(
            os.path.join(step_dir(ckpt_dir, s), _MANIFEST)
        ):
            return s
    steps = _complete_steps(ckpt_dir)
    return max(steps) if steps else None


def save_checkpoint(
    ckpt_dir: str,
    tree: Any,
    *,
    step: int,
    shardings: Any = None,
    keep: Optional[int] = None,
    fp32_portable: bool = True,
    packed: bool = False,
    blocking: bool = True,
    retry: Optional[RetryPolicy] = None,
    shard_axis: Optional[str] = None,
    shard_axes: Optional[Any] = None,
    data_state: Optional[dict] = None,
) -> str:
    """Write ``tree`` as checkpoint ``step`` under ``ckpt_dir``.

    ``shardings`` — optional pytree of ``PartitionSpec`` (or leaves carrying
    ``.spec``, e.g. ``NamedSharding``) matching ``tree``'s structure prefix;
    recorded in the manifest so :func:`restore_checkpoint` can re-shard onto
    any mesh. ``keep`` — if set, delete all but the newest ``keep`` steps.
    ``packed`` — store leaves in one flat superblock file gathered by the
    native threaded pack (apex_C-parity host runtime,
    :mod:`apex_tpu._native`) instead of npz zip framing; restore
    auto-detects either format.

    ``blocking=False`` — return as soon as the tree is snapshotted to host
    memory; disk serialization runs on a background writer thread
    (:mod:`apex_tpu.resilience.async_checkpoint`) so the train loop keeps
    stepping during the write (the snapshot means later donation/mutation
    of the device buffers cannot corrupt the save).  Any save — async or
    blocking — first *fences* on a still-in-flight async write, as does
    interpreter exit; a failed background write (after retries) re-raises
    at that fence.  ``retry`` — :class:`RetryPolicy` for transient storage
    errors (each attempt rewrites the tmp dir from scratch).

    Every array's CRC32 digest is recorded in ``manifest.json`` for
    restore-side integrity verification (:func:`verify_checkpoint`).

    ``shard_axis`` — name of the mesh axis ZeRO state is sharded over
    (e.g. ``"data"``).  Leaves whose ``shardings`` spec LEADS with that
    axis are treated as a stack of per-rank partitions along axis 0:
    each rank's slice goes to its own ``shard_<r>.npz`` file with its
    own CRC32 digest (``crc32_shards`` in the manifest), and the
    manifest gains a top-level ``topology`` record (axis name, shard
    count, mesh shape when recoverable).  Restore understands the
    format transparently — including onto a mesh of a *different* shard
    count (see :func:`restore_checkpoint`'s reshard notes).  Replicated
    leaves (spec not led by ``shard_axis``) are stored once, exactly as
    in the unsharded format.  Sharded saves require ``shardings`` and
    are npz-only (``packed=True`` is rejected).

    ``shard_axes`` — the multi-axis generalization (**format 4**): an
    *ordered* mapping of mesh axis name → size (e.g. ``{"data": 4,
    "pipeline": 1, "tensor": 2}``).  Leaves whose spec LEADS with one or
    more of those axis names (one name per leading dim, in dim order)
    are stacks of per-coordinate partitions; each mesh coordinate's
    slice goes to ``shard_<c0>_<c1>_..._<ck>.npz`` (coordinates in
    ``shard_axes`` order; axes a leaf is not sharded over sit at 0) with
    a per-coordinate CRC32 digest (``crc32_shards`` dict keyed by the
    leaf's own lead coordinates).  The manifest's ``topology`` record
    carries the full ``mesh_axes`` shape, and restore re-partitions
    across any N→M reshape of the mesh (``docs/resilience.md`` "3D
    topologies").  Mutually exclusive with ``shard_axis``; format-3
    checkpoints keep restoring through the same path.

    ``data_state`` — optional compact JSON record of the input
    pipeline's position (the checkpointable-iterator protocol's
    ``state_dict()``, docs/data.md).  Stored under the manifest's
    ``data_state`` key — atomically with the arrays, through the async
    writer too — and read back via :func:`load_data_state`, so model
    state and iterator position can never land in different steps.

    Returns the checkpoint directory path.
    """
    # Only process 0 writes; the guard precedes any device_get so non-writing
    # hosts pay no host transfer. (Globally-sharded multi-host arrays would
    # need an all_gather-to-host first — out of scope like the reference's
    # per-rank torch.save, SURVEY §5.4.)
    if jax.process_index() != 0:
        return step_dir(ckpt_dir, step)

    # fence: at most one write in flight; a prior async save must land (or
    # surface its error) before this one starts
    from apex_tpu.resilience import async_checkpoint as _async

    _async.wait_for_save()

    if shard_axis is not None and shard_axes is not None:
        raise ValueError("pass shard_axis (format 3) or shard_axes "
                         "(format 4), not both")
    if (shard_axis is not None or shard_axes is not None) \
            and shardings is None:
        raise ValueError(
            "shard_axis/shard_axes requires shardings: the PartitionSpec "
            "tree is what identifies which leaves are per-rank partitions")
    if (shard_axis is not None or shard_axes is not None) and packed:
        raise ValueError("sharded checkpoints are npz-only (packed=False)")
    if shard_axes is not None:
        shard_axes = {str(a): int(n) for a, n in dict(shard_axes).items()}
        if not shard_axes or any(n < 1 for n in shard_axes.values()):
            raise ValueError(f"invalid shard_axes {shard_axes!r}: need at "
                             "least one axis, every size >= 1")

    if data_state is not None:
        try:
            data_state = json.loads(json.dumps(data_state))
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"data_state must be JSON-serializable (it rides the "
                f"manifest): {e}") from e

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    spec_map = _spec_map(shardings, tree) if shardings is not None else {}

    # _path_parts stringifies key components, so exotic pytrees can alias
    # (DictKey('0') vs SequenceKey(0), int key 0 vs str '0').  An aliased
    # path tuple would silently bind the wrong sharding spec or restore
    # leaf — refuse at save time instead (ADVICE r3).
    seen_paths = {}
    for path, _ in leaves:
        pt = tuple(_path_parts(path))
        if pt in seen_paths:
            raise ValueError(
                f"checkpoint path collision: {_keystr(path)} and "
                f"{seen_paths[pt]} both map to path tuple {pt} — rename "
                "the colliding keys (e.g. avoid int and str keys that "
                "stringify identically)")
        seen_paths[pt] = _keystr(path)

    manifest = {"step": int(step), "format": 1, "leaves": {}}
    arrays = {}
    n_shards: Optional[int] = None
    mesh_shape: Optional[dict] = None
    shard_arrays: list = []
    # format 4: mesh-coordinate tuple (over ALL shard_axes, in order) ->
    # {leaf key: partition}; populated only for multi-axis saves
    shard_maps: dict = {}
    any_multi = False
    for path, leaf in leaves:
        # (None leaves never appear here: tree_flatten treats None as an
        # empty subtree, so None-valued fields are simply absent and
        # reappear from the target's structure on restore)
        key = _keystr(path)
        # keystr can collide for keys containing quotes/brackets; the
        # structured "path" is the identity — disambiguate the flat key
        # (it is only a storage label once "path" exists)
        if key in manifest["leaves"]:
            i = 2
            while f"{key}#{i}" in manifest["leaves"]:
                i += 1
            key = f"{key}#{i}"
        if mesh_shape is None:
            try:  # best-effort topology evidence for the manifest
                mesh_shape = dict(leaf.sharding.mesh.shape)
            except (AttributeError, TypeError):
                # numpy leaves have no .sharding and single-device
                # shardings no .mesh — those are the documented
                # "no topology" cases.  Anything else must surface
                # (EX001: the broad except here would also have
                # swallowed a genuinely broken mesh mid-save)
                pass
        val = np.asarray(jax.device_get(leaf))
        entry = {"kind": "array", "dtype": str(val.dtype),
                 "shape": list(val.shape), "path": _path_parts(path)}
        if str(val.dtype) in _HALF_DTYPES:
            if fp32_portable:
                val = val.astype(np.float32)
                entry["stored_dtype"] = "float32"
            else:
                # npz can't round-trip ml_dtypes natively: store the raw bits
                val = val.view(np.uint16)
                entry["stored_dtype"] = "uint16_bits"
        ptuple = tuple(entry["path"])
        spec = spec_map.get(ptuple)
        if spec is not None:
            entry["spec"] = _spec_to_json(spec)
        if shard_axes is not None:
            lead = _flat.spec_lead_axes(spec, shard_axes)
            if lead:
                if val.ndim < len(lead):
                    raise ValueError(
                        f"leaf {key} has spec leading with {len(lead)} "
                        f"mesh axes {lead} but only {val.ndim} dims to "
                        "partition")
                for i, ax in enumerate(lead):
                    if val.shape[i] != shard_axes[ax]:
                        raise ValueError(
                            f"leaf {key} dim {i} has size {val.shape[i]} "
                            f"but its spec shards it over {ax!r} "
                            f"(size {shard_axes[ax]}); a live ZeRO state "
                            "is saved through resilience."
                            "save_zero_checkpoint, which takes its "
                            "stacked_zero_state")
                entry["shard_axes"] = lead
                entry["replicated_shards"] = _flat.is_replicated_stack(
                    val, len(lead))
                any_multi = True
                for c in itertools.product(
                        *(range(shard_axes[a]) for a in lead)):
                    fullc = _leaf_full_coord(entry, c, shard_axes)
                    shard_maps.setdefault(fullc, {})[key] = val[c]
                manifest["leaves"][key] = entry
                continue
            manifest["leaves"][key] = entry
            arrays[key] = val
            continue
        if shard_axis is not None and _spec_leads_with(spec, shard_axis):
            if val.ndim == 0:
                raise ValueError(
                    f"leaf {key} has spec leading with {shard_axis!r} but "
                    "no leading axis to partition")
            if n_shards is None:
                n_shards = int(val.shape[0])
                shard_arrays = [dict() for _ in range(n_shards)]
            elif val.shape[0] != n_shards:
                raise ValueError(
                    f"inconsistent shard counts in one save: leaf {key} "
                    f"has leading axis {val.shape[0]}, earlier sharded "
                    f"leaves have {n_shards}; a live ZeRO state is saved "
                    "through resilience.save_zero_checkpoint, which takes "
                    "its stacked_zero_state")
            entry["shard_axis"] = shard_axis
            # a per-rank REPLICATED stack must re-broadcast on reshard,
            # not concat.  Only 1-D [n_shards] stacks (per-rank scalars
            # like the broadcast opt step counter) qualify: a >=2-D
            # stack is by contract a flat-buffer partition, even when
            # its content happens to be rank-identical (a fresh ZeRO
            # init's all-zero moments must reshard by concat, and the
            # cheap per-scalar compare keeps the foreground snapshot
            # phase free of O(bytes) work)
            entry["replicated_shards"] = bool(
                val.ndim == 1
                and all(np.array_equal(val[r], val[0])
                        for r in range(1, n_shards)))
            for r in range(n_shards):
                shard_arrays[r][key] = val[r]
        else:
            manifest["leaves"][key] = entry
            arrays[key] = val
            continue
        manifest["leaves"][key] = entry
    if n_shards is not None:
        manifest["format"] = 3
        manifest["topology"] = {"shard_axis": shard_axis,
                                "n_shards": n_shards}
        if mesh_shape is not None:
            manifest["topology"]["mesh_shape"] = mesh_shape
    elif any_multi:
        manifest["format"] = 4
        manifest["topology"] = {"mesh_axes": dict(shard_axes)}
        if mesh_shape is not None:
            manifest["topology"]["mesh_shape"] = mesh_shape
    if data_state is not None:
        manifest["data_state"] = data_state

    # everything below is pure host/disk work on the snapshot — safe to run
    # on the background writer thread
    if blocking:
        _write_checkpoint_files(ckpt_dir, step, manifest, arrays,
                                packed=packed, keep=keep, retry=retry,
                                shard_arrays=shard_arrays,
                                shard_maps=shard_maps,
                                shard_axes=shard_axes)
    else:
        _async.submit_save(
            lambda: _write_checkpoint_files(ckpt_dir, step, manifest, arrays,
                                            packed=packed, keep=keep,
                                            retry=retry,
                                            shard_arrays=shard_arrays,
                                            shard_maps=shard_maps,
                                            shard_axes=shard_axes),
            label=f"{ckpt_dir}:step_{int(step)}")
    return step_dir(ckpt_dir, step)


def _spec_leads_with(spec, axis: str) -> bool:
    """True when PartitionSpec ``spec``'s FIRST dimension entry names
    ``axis`` (directly or inside a tuple) — the test for "this leaf is a
    stack of per-rank partitions along axis 0"."""
    if spec is None or len(spec) == 0:
        return False
    head = spec[0]
    if isinstance(head, (tuple, list)):
        return axis in head
    return head == axis


def _leaf_full_coord(entry: dict, coords, shard_axes: dict) -> tuple:
    """Full mesh coordinate of one leaf shard: the leaf's own lead-axis
    ``coords`` placed at their axes' positions in ``shard_axes`` order,
    zeros elsewhere (the format-4 file-location rule)."""
    lead = entry["shard_axes"]
    return tuple(coords[lead.index(a)] if a in lead else 0
                 for a in shard_axes)


def _write_checkpoint_files(ckpt_dir: str, step: int, manifest: dict,
                            arrays: dict, *, packed: bool,
                            keep: Optional[int],
                            retry: Optional[RetryPolicy],
                            shard_arrays: Optional[list] = None,
                            shard_maps: Optional[dict] = None,
                            shard_axes: Optional[dict] = None) -> str:
    """Disk phase of a save: tmp dir -> arrays + manifest -> atomic rename ->
    latest marker -> keep-GC.  Retries the whole tmp-dir write on transient
    storage errors (each attempt starts from a fresh tmp dir)."""
    # CRC32 digests of the bytes as STORED (what restore-side verification
    # re-hashes off disk).  Hashed here — on the writer thread for async
    # saves — so ``blocking=False`` returns after the device snapshot alone,
    # without a per-leaf hash + tobytes copy stalling the train loop.
    for k, entry in manifest["leaves"].items():
        if k in arrays:
            entry["crc32"] = zlib.crc32(arrays[k].tobytes()) & 0xFFFFFFFF
        elif "shard_axes" in entry:  # format 4: digest per mesh coordinate
            entry["crc32_shards"] = {
                _coord_key(c): zlib.crc32(
                    shard_maps[_leaf_full_coord(entry, c, shard_axes)][k]
                    .tobytes()) & 0xFFFFFFFF
                for c in itertools.product(
                    *(range(shard_axes[a]) for a in entry["shard_axes"]))}
        else:  # format 3: one digest per rank's partition
            entry["crc32_shards"] = [
                zlib.crc32(sh[k].tobytes()) & 0xFFFFFFFF
                for sh in shard_arrays]
    retry = retry or RetryPolicy(max_attempts=1)
    final = step_dir(ckpt_dir, step)
    last_err = None
    for attempt in range(retry.max_attempts):
        try:
            _write_step_dir_once(ckpt_dir, step, manifest, arrays,
                                 packed=packed, shard_arrays=shard_arrays,
                                 shard_maps=shard_maps)
            break
        except retry.retryable as e:
            last_err = e
            shutil.rmtree(final + ".tmp", ignore_errors=True)
            if attempt + 1 >= retry.max_attempts:
                raise
            time.sleep(retry.delay(attempt))
    else:  # pragma: no cover — loop always breaks or raises
        raise last_err

    with open(os.path.join(ckpt_dir, _LATEST), "w") as f:
        f.write(str(int(step)))

    if keep is not None:
        # prune by write recency, never the checkpoint just written — a
        # rollback-resume that saves a *lower* step than what's on disk must
        # not delete its own output
        others = [
            s for s in _complete_steps(ckpt_dir) if s != int(step)
        ]
        others.sort(key=lambda s: os.path.getmtime(step_dir(ckpt_dir, s)))
        for s in others[: max(0, len(others) - (keep - 1))]:
            shutil.rmtree(step_dir(ckpt_dir, s), ignore_errors=True)
    return final


def _write_step_dir_once(ckpt_dir: str, step: int, manifest: dict,
                         arrays: dict, *, packed: bool,
                         shard_arrays: Optional[list] = None,
                         shard_maps: Optional[dict] = None) -> None:
    """One attempt at writing + committing ``step_<N>/``."""
    os.makedirs(ckpt_dir, exist_ok=True)
    final = step_dir(ckpt_dir, step)
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    if shard_arrays:
        # per-rank partition files; each gets its own fault event so the
        # chaos tier can kill a save mid-shard-set (the commit is still
        # atomic: nothing is visible until the rename below)
        for r, sh in enumerate(shard_arrays):
            p = os.path.join(tmp, shard_file(r))
            _fault("write_shard", p)
            np.savez(p, **sh)
    if shard_maps:
        # format 4: per-mesh-coordinate partition files, same fault
        # event and same atomic-commit guarantee
        for coords in sorted(shard_maps):
            p = os.path.join(tmp, shard_file_coords(coords))
            _fault("write_shard", p)
            np.savez(p, **shard_maps[coords])
    if packed:
        from apex_tpu import _native

        manifest["format"] = 2
        names = list(arrays)
        offsets, off = [], 0
        contig = []
        for k in names:
            a = np.ascontiguousarray(arrays[k])
            contig.append(a)
            manifest["leaves"][k]["offset"] = off
            offsets.append(off)
            off += -(-a.nbytes // _PACK_ALIGN) * _PACK_ALIGN
        buf = _native.pack_host(contig, offsets, off)
        _fault("write_arrays", os.path.join(tmp, _PACK))
        buf.tofile(os.path.join(tmp, _PACK))
    else:
        _fault("write_arrays", os.path.join(tmp, _ARRAYS))
        np.savez(os.path.join(tmp, _ARRAYS), **arrays)
    _fault("write_manifest", os.path.join(tmp, _MANIFEST))
    with open(os.path.join(tmp, _MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1)
    _fault("commit", final)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)


def _stored_dtype(entry: dict):
    """On-disk dtype of a manifest leaf (the single owner of the
    stored_dtype decode — the chaos harness reuses it to locate leaf
    bytes)."""
    sd = entry.get("stored_dtype")
    return jnp.dtype(sd if sd == "float32"
                     else "uint16" if sd == "uint16_bits"
                     else entry["dtype"])


def _load_manifest_and_data(d: str, *, verify: bool):
    """Read manifest + raw stored arrays from checkpoint dir ``d``.

    ``verify=True`` treats every read/parse failure as corruption (raising
    :class:`CheckpointCorruptionError`) and checks each array's stored
    bytes against the manifest's CRC32 digest.  ``verify=False`` preserves
    the historical raw exceptions."""
    try:
        _fault("read_arrays", d)
        with open(os.path.join(d, _MANIFEST)) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        if verify:
            raise CheckpointCorruptionError(
                f"unreadable manifest in {d}: {e}") from e
        raise
    pack_path = os.path.join(d, _PACK)
    shard_data: list = []
    coord_maps: dict = {}
    mesh_axes = dict(manifest.get("topology", {}).get("mesh_axes") or {})
    try:
        if os.path.exists(pack_path):  # format 2: flat superblock
            buf = np.fromfile(pack_path, np.uint8)
            data = {}
            for k, e in manifest["leaves"].items():
                cnt = int(np.prod(e["shape"])) if e["shape"] else 1
                data[k] = np.frombuffer(buf, _stored_dtype(e), cnt,
                                        e["offset"]).reshape(e["shape"])
        else:
            data = {}
            if os.path.exists(os.path.join(d, _ARRAYS)):
                with np.load(os.path.join(d, _ARRAYS)) as npz:
                    data = {k: npz[k] for k in npz.files}
            for r in range(manifest.get("topology", {}).get("n_shards", 0)):
                with np.load(os.path.join(d, shard_file(r))) as npz:
                    shard_data.append({k: npz[k] for k in npz.files})
            if mesh_axes:  # format 4: per-mesh-coordinate files
                needed = set()
                for e in manifest["leaves"].values():
                    if "shard_axes" not in e:
                        continue
                    for c in itertools.product(
                            *(range(mesh_axes[a]) for a in e["shard_axes"])):
                        needed.add(_leaf_full_coord(e, c, mesh_axes))
                for fullc in sorted(needed):
                    with np.load(os.path.join(
                            d, shard_file_coords(fullc))) as npz:
                        coord_maps[fullc] = {k: npz[k] for k in npz.files}
    except Exception as e:
        # truncated pack (frombuffer ValueError), truncated/garbled npz
        # (zipfile.BadZipFile, EOFError, OSError, KeyError), missing
        # shard file — with verify, all of these are one condition: a
        # corrupt checkpoint
        if verify:
            raise CheckpointCorruptionError(
                f"unreadable arrays in {d}: {type(e).__name__}: {e}") from e
        raise
    problems = []
    for k, e in manifest["leaves"].items():
        if "shard_axis" not in e:
            continue
        # reassemble the logical [n_shards, ...] stack; per-shard CRC
        # runs while each partition's bytes are in hand
        parts = []
        for r, sh in enumerate(shard_data):
            if k not in sh:
                problems.append(f"missing {k!r} in shard {r}")
                continue
            if verify and "crc32_shards" in e:
                got = zlib.crc32(np.asarray(sh[k]).tobytes()) & 0xFFFFFFFF
                want = e["crc32_shards"][r]
                if got != want:
                    problems.append(
                        f"CRC32 mismatch for {k!r} shard {r}: stored "
                        f"digest {want}, bytes on disk hash to {got}")
            parts.append(sh[k])
        if len(parts) == len(shard_data):
            data[k] = np.stack(parts)
    for k, e in manifest["leaves"].items():
        if "shard_axes" not in e:
            continue
        # format 4: reassemble [n_a, n_b, ..., *content] from the
        # per-coordinate files (coordinates iterate in C-order over the
        # leaf's lead axes, so stack+reshape inverts the save split)
        try:
            lead_shape = tuple(mesh_axes[a] for a in e["shard_axes"])
        except KeyError as exc:
            # valid-JSON but damaged manifest: a leaf names a shard axis
            # absent from topology.mesh_axes — under verify this is a
            # corrupt checkpoint (so restore_resilient's fallback walk
            # can move on to an older intact step), not a raw KeyError
            if verify:
                raise CheckpointCorruptionError(
                    f"checkpoint at {d}: leaf {k!r} is sharded over axis "
                    f"{exc} missing from topology mesh_axes "
                    f"{sorted(mesh_axes)}") from exc
            raise
        parts = []
        for c in itertools.product(*(range(n) for n in lead_shape)):
            sh = coord_maps.get(_leaf_full_coord(e, c, mesh_axes), {})
            if k not in sh:
                problems.append(f"missing {k!r} at mesh coordinate {c}")
                continue
            if verify and "crc32_shards" in e:
                got = zlib.crc32(np.asarray(sh[k]).tobytes()) & 0xFFFFFFFF
                want = e["crc32_shards"].get(_coord_key(c))
                if got != want:
                    problems.append(
                        f"CRC32 mismatch for {k!r} at mesh coordinate "
                        f"{c}: stored digest {want}, bytes on disk hash "
                        f"to {got}")
            parts.append(sh[k])
        if len(parts) == int(np.prod(lead_shape)):
            data[k] = np.stack(parts).reshape(
                lead_shape + tuple(parts[0].shape))
    if verify:
        for k, e in manifest["leaves"].items():
            if k not in data:
                if "shard_axis" not in e and "shard_axes" not in e:
                    problems.append(f"missing stored array {k!r}")
                continue
            want = e.get("crc32")
            if want is None:
                continue  # pre-digest/sharded manifest: checked above
            got = zlib.crc32(np.asarray(data[k]).tobytes()) & 0xFFFFFFFF
            if got != want:
                problems.append(
                    f"CRC32 mismatch for {k!r}: stored digest {want}, "
                    f"bytes on disk hash to {got}")
        if problems:
            raise CheckpointCorruptionError(
                f"checkpoint at {d} failed integrity verification: "
                + "; ".join(problems))
    elif problems:
        raise KeyError(
            f"sharded checkpoint at {d} is incomplete: " + "; ".join(problems))
    return manifest, data


def verify_checkpoint(ckpt_dir: str, step: Optional[int] = None) -> int:
    """Check integrity of checkpoint ``step`` (default: latest) under
    ``ckpt_dir``: files readable, every manifest leaf present, CRC32
    digests match the bytes on disk.  Returns the verified step, or raises
    :class:`CheckpointCorruptionError` / :class:`FileNotFoundError`."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint found under {ckpt_dir}")
    _load_manifest_and_data(step_dir(ckpt_dir, step), verify=True)
    return step


def load_data_state(ckpt_dir: str,
                    step: Optional[int] = None) -> Optional[dict]:
    """The ``data_state`` record saved with checkpoint ``step``
    (default: latest), or None when that checkpoint was saved without
    one.  The restore-side half of exactly-once resume: restore the
    model tree with :func:`restore_checkpoint` / ``restore_resilient``
    at step N, then feed this record to the iterator's
    ``load_state_dict`` — both came from ONE atomic manifest, so they
    cannot disagree about the position."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint found under {ckpt_dir}")
    with open(os.path.join(step_dir(ckpt_dir, step), _MANIFEST)) as f:
        return json.load(f).get("data_state")


def restore_checkpoint(
    ckpt_dir: str,
    target: Any = None,
    *,
    step: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    shardings: Any = None,
    verify: bool = False,
):
    """Restore a checkpoint into (optionally) ``target``'s structure.

    - ``target`` given: every leaf path of ``target`` must exist in the
      checkpoint; restored leaves are cast back to the target leaf's dtype
      (precision portability) and the result has ``target``'s exact treedef
      (NamedTuples, dataclasses, optimizer states all round-trip).
    - ``target=None``: rebuilds a nested dict keyed by path components
      (dict keys / attribute names / sequence indices as strings).
    - ``mesh`` given: each leaf is ``device_put`` with
      ``NamedSharding(mesh, spec)`` where ``spec`` comes from ``shardings``
      (a pytree of PartitionSpec) or, failing that, from the manifest. The
      mesh may differ in size/shape from the one that saved — this is how
      restore-on-a-different-dp-size works.
    - ``verify=True``: re-hash every stored array against the manifest's
      CRC32 digests before materializing, and surface any read failure as
      :class:`CheckpointCorruptionError` (see
      :func:`apex_tpu.resilience.restore_resilient` for automatic fallback
      to the newest intact older checkpoint).

    **Cross-topology reshard**: leaves saved with ``shard_axis`` (see
    :func:`save_checkpoint`) are stacks of per-rank flat-buffer
    partitions.  When the target leaf's leading axis differs from the
    saved shard count (an N-device save restoring onto an M-device mesh,
    including the M=1 debug restore), the stack is re-partitioned by
    flat-buffer semantics: concatenate the N saved partitions, re-split
    into M.  Size differences can come only from the flat schema's
    topology-dependent tail padding (``total_multiple_of = 128·N``), so
    growth zero-fills and shrinkage requires the dropped tail to be all
    zeros (anything else raises — that would silently lose optimizer
    state).  1-D stacks of per-rank scalars recorded as
    ``replicated_shards`` (the broadcast step counter) re-broadcast
    rank 0 instead of concatenating.

    Returns ``(tree, step)``.
    """
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint found under {ckpt_dir}")
    d = step_dir(ckpt_dir, step)
    manifest, data = _load_manifest_and_data(d, verify=verify)

    if shardings is not None and target is not None:
        spec_map = _spec_map(shardings, target)
    elif shardings is not None:
        # no target to broadcast a prefix against: shardings must be
        # leaf-exact here
        spec_map = {
            tuple(_path_parts(path)): (s.spec if isinstance(s, NamedSharding)
                                       else s)
            for path, s in jax.tree_util.tree_flatten_with_path(
                shardings, is_leaf=_is_spec_leaf
            )[0]
            if s is not None
        }
    else:
        spec_map = {}

    def _materialize(key: str, entry: dict, want_dtype=None,
                     want_shape=None):
        val = data[key]
        if (want_shape is not None
                and ("shard_axis" in entry or "shard_axes" in entry)
                and tuple(val.shape) != tuple(want_shape)):
            val = _reshard_stack(val, entry, tuple(want_shape), key)
        if entry.get("stored_dtype") == "uint16_bits":
            val = val.view(jnp.dtype(entry["dtype"]))
        dtype = want_dtype if want_dtype is not None else jnp.dtype(entry["dtype"])
        arr = jnp.asarray(val).astype(dtype)
        if mesh is not None:
            ptuple = (tuple(entry["path"]) if "path" in entry
                      else tuple(_parse_keystr(key)))
            spec = spec_map.get(ptuple)
            if spec is None and entry.get("spec") is not None:
                spec = _spec_from_json(entry["spec"])
            if spec is None:
                spec = PartitionSpec()
            # drop axis names the new mesh doesn't have (e.g. restoring a
            # dp-sharded save onto a single-axis mesh); tuple entries keep
            # whichever of their axes still exist
            spec = PartitionSpec(*[_filter_spec_entry(p, mesh) for p in spec])
            arr = jax.device_put(arr, NamedSharding(mesh, spec))
        return arr

    if target is None:
        nested: dict = {}
        for key, entry in manifest["leaves"].items():
            # manifests carry structured path components (format >= 1 with
            # "path"); older ones fall back to parsing the keystr
            parts = entry.get("path") or _parse_keystr(key)
            node = nested
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = _materialize(key, entry)
        return nested, step

    paths, treedef = jax.tree_util.tree_flatten_with_path(target)
    # primary lookup by structured path (collision-free); keystr is the
    # fallback for manifests written before the "path" field existed
    by_path = {tuple(e["path"]): k for k, e in manifest["leaves"].items()
               if "path" in e}
    # collect ALL missing leaves up front: a target/checkpoint structure
    # mismatch should name everything wrong with it, not die on the first key
    missing = []
    for path, _ in paths:
        key = by_path.get(tuple(_path_parts(path)), _keystr(path))
        if key not in manifest["leaves"]:
            missing.append(key)
    if missing:
        present = sorted(manifest["leaves"])
        shown = ", ".join(repr(k) for k in present[:8])
        if len(present) > 8:
            shown += f", ... ({len(present)} total)"
        raise KeyError(
            f"checkpoint at {d} is missing {len(missing)} leaves required "
            f"by the restore target: {missing} — the checkpoint holds "
            f"[{shown}]. The target's structure does not match what was "
            "saved (wrong checkpoint dir, or the model/optimizer definition "
            "changed since the save).")
    leaves = []
    for path, tleaf in paths:
        key = by_path.get(tuple(_path_parts(path)), _keystr(path))
        want = shape = None
        if tleaf is not None and hasattr(tleaf, "dtype"):
            want = tleaf.dtype
        if tleaf is not None and hasattr(tleaf, "shape"):
            shape = tleaf.shape
        leaves.append(_materialize(key, manifest["leaves"][key],
                                   want_dtype=want, want_shape=shape))
    return jax.tree_util.tree_unflatten(treedef, leaves), step


def _reshard_stack(val: np.ndarray, entry: dict, want_shape: tuple,
                   key: str) -> np.ndarray:
    """Re-partition a sharded leaf's stored stack to the target's layout
    (restore_checkpoint's "cross-topology reshard" contract; operates on
    the STORED dtype, before any precision-portability cast).  Format-3
    leaves carry one lead axis, format-4 leaves one per mesh axis named
    in ``shard_axes``; both route through ONE implementation
    (:func:`apex_tpu.multi_tensor.flat.reshard_stack` — C-order flatten
    + the repartition_flat pad/trim contract, replicated stacks
    re-broadcast coordinate 0), shared with the in-memory
    reshard_zero_state/reshard_tree so on-disk and live semantics
    cannot diverge."""
    n_lead = len(entry["shard_axes"]) if "shard_axes" in entry else 1
    return _flat.reshard_stack(val, n_lead, want_shape,
                               replicated=bool(entry.get("replicated_shards")),
                               label=f"sharded leaf {key!r}")


def _filter_spec_entry(part, mesh: Mesh):
    """Keep only the axis names present in ``mesh`` for one PartitionSpec
    dimension entry (None / name / tuple-of-names)."""
    if part is None:
        return None
    if isinstance(part, (tuple, list)):
        kept = tuple(n for n in part if n in mesh.axis_names)
        return kept if kept else None
    return part if part in mesh.axis_names else None


def _parse_keystr(key: str) -> list:
    """Back-compat path recovery for manifests without structured "path"
    entries: parse ``['a'][0].b`` keystrs.  Best-effort — keys containing
    quotes/brackets need the structured form."""
    import re

    token = re.compile(r"\[\'([^\']*)\'\]|\[(\d+)\]|\.([A-Za-z_][A-Za-z_0-9]*)")
    parts = [m.group(1) or m.group(2) or m.group(3)
             for m in token.finditer(key)]
    return parts or [key]

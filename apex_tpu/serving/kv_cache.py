"""Paged KV cache: a preallocated HBM page pool + host-side page
accounting.

The training stack's KV tensors are per-call slabs; a serving engine
instead holds MANY requests' caches alive at once, each growing by one
token per decode step and dying at unpredictable times.  A slab per
request would fragment HBM and force reallocation-and-copy on growth —
the standard answer (vLLM's PagedAttention, SURVEY-adjacent) is a pool
of fixed-size pages:

* ``k``/``v``: ``[num_layers, num_pages, page_size, num_heads,
  head_dim]`` device arrays, allocated ONCE at engine start.  A
  request's cache is a *page list* — pages need not be contiguous, so
  the pool never fragments and "grow by one token" is at most "append
  one page id to a python list".
* Page 0 is the reserved **scratch page**: it is never allocated, page
  tables pad their rows with it, and packed-prefill scatter routes its
  padding positions there.  Readers never see its content (the decode
  kernel and the XLA baseline both mask columns past ``kv_len``), so
  duplicate pad writes landing in it are harmless by construction.
* Host-side accounting (free list, per-page owner, per-page REFCOUNT)
  is plain python — allocation is LOWEST-INDEX-FIRST so every run of
  the scheduler is bit-reproducible.
* r17 adds two orthogonal pool modes: **prefix sharing** (pages are
  refcounted; N requests whose prompts share a prefix reference the
  same physical pages, a write to a shared page copies it first —
  copy-on-write — and ``free`` only returns a page at refcount zero)
  and a **quantized pool** (``quantize="int8"``/``"fp8"``: the pool
  holds narrow codes plus per-(page, slot, head) fp32 scales;
  quantize-on-write in the scatter, dequantize-on-read in
  ``flash_decode``).

* A model whose layers keep their tokens for two different LIFETIMES
  (ISSUE 29: full-attention layers keep every token, sliding-window
  layers only the last ``window``) gets two pools: the
  :class:`PagedKVCache` of its full layers, and beside it, as
  ``cache.window_pool``, a :class:`WindowPool` of its window layers
  with its own free list, refcounts and page tables.  A request holds
  a page list in each; the window pool gives pages back as the window
  slides (docs/serving.md, "Two page lifetimes").

* A model with LATENT attention (ISSUE 33: DeepSeek-V2's MLA) keeps
  ONE vector a token a layer, shared by every head, that serves as key
  and as value: ``latent_dim=...`` makes the pool one operand ``k``
  ``[num_layers, num_pages, page_size, width]`` (``width`` the vector
  rounded up to whole lane tiles, which is how the device lays it out
  anyway; the padding stays zero) and ``v`` is ``None``.  Allocation,
  refcounts, copy-on-write, the scatter, defrag and the CRCs act on
  page ids and on whichever operands there are (:attr:`PagedKVCache.
  operands`).

* A model with STATE-SPACE layers (ISSUE 35: Mamba-2 mixers beside a
  few attention layers) keeps, a request and a layer, a recurrent state
  of FIXED size instead of K/V that grow: the :class:`StatePool` beside
  the page pool (``cache.state_pool``) holds one SLOT a request, taken
  at admission and given back at retirement and preemption; slot 0 is
  the scratch slot as page 0 is the scratch page.  The page pool then
  holds only the attention layers' K/V (docs/serving.md, "Slots beside
  pages").

The device arrays are functionally updated (``.at[].set``); the cache
object re-binds them, so callers treat ``cache.k``/``cache.v`` (and,
quantized, ``cache.k_scale``/``cache.v_scale``) as the current pool
state (and may thread them through ``jax.jit`` as loop carries).
"""

from __future__ import annotations

import base64
import bisect
import dataclasses
import functools
import zlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _scatter_tokens(k_pool, v_pool, k_new, v_new, pages, offsets):
    return (k_pool.at[:, pages, offsets].set(k_new),
            v_pool.at[:, pages, offsets].set(v_new))


def latent_width(latent_dim: int) -> int:
    """The stored width of a latent vector: whole 128-lane tiles."""
    return -(-latent_dim // 128) * 128


def pad_latent(x, width: int):
    """``x`` ``[..., latent_dim]`` -> ``[..., width]``, zeros after."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _scatter_latent(pool, new, pages, offsets):
    return pool.at[:, pages, offsets].set(pad_latent(new, pool.shape[-1]))


#: qmax per quantization mode: int8 symmetric [-127, 127] (the -128
#: code is unused so the grid is symmetric), fp8 e4m3 saturates at 448.
_QUANT_QMAX = {"int8": 127.0, "fp8": 448.0}


def quant_pool_dtype(mode: str):
    """Device dtype of the quantized pool's code arrays."""
    if mode == "int8":
        return jnp.int8
    if mode == "fp8":
        dt = getattr(jnp, "float8_e4m3fn", None)
        if dt is None:
            raise ValueError(
                "quantize='fp8' needs jnp.float8_e4m3fn, which this "
                "jax build lacks — use quantize='int8'")
        return dt
    raise ValueError(f"unknown quantize mode {mode!r} "
                     f"(expected one of {sorted(_QUANT_QMAX)})")


def quantize_tokens(x: jnp.ndarray, qdtype, qmax: float):
    """``x`` [..., H, D] -> (codes [..., H, D] ``qdtype``, scale
    [..., H] fp32).

    The scale is a PURE per-(token, head) function of that token's own
    values — absmax over D divided by ``qmax``, with absmax 0 mapped to
    scale 1 so zero rows stay exactly zero.  Order independence is the
    point: quantizing a token during incremental decode append and
    re-quantizing it during a bulk rebuild prefill produce
    bitwise-identical pool bytes, which is what lets the KV-rebuild
    recovery contract extend to the quantized pool.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / qmax)
    codes = xf / scale[..., None]
    if np.dtype(qdtype) == np.dtype(np.int8):
        codes = jnp.clip(jnp.round(codes), -qmax, qmax)
    return codes.astype(qdtype), scale


def _scatter_tokens_quant(k_pool, v_pool, ks_pool, vs_pool,
                          k_new, v_new, pages, offsets, *, qmax):
    """Quantize-on-write admission scatter: incoming fp tokens are
    narrowed on device (codes + scales) and scattered in one fused
    update per pool array — the wide values never land in HBM."""
    kq, ks = quantize_tokens(k_new, k_pool.dtype, qmax)
    vq, vs = quantize_tokens(v_new, v_pool.dtype, qmax)
    return (k_pool.at[:, pages, offsets].set(kq),
            v_pool.at[:, pages, offsets].set(vq),
            ks_pool.at[:, pages, offsets].set(ks),
            vs_pool.at[:, pages, offsets].set(vs))


def _copy_page(pool, src, dst):
    """pool[:, dst] = pool[:, src] with traced indices, so every COW
    copy reuses one compiled executable regardless of page ids."""
    page = jax.lax.dynamic_index_in_dim(pool, src, axis=1, keepdims=True)
    return jax.lax.dynamic_update_slice_in_dim(pool, page, dst, axis=1)


def _import_page(pool, page, dst):
    """pool[:, dst] = page (a ``[layers, 1, ...]`` host slice) with a
    traced destination, so importing a SHIPPED page (r18 disaggregation)
    reuses one compiled executable regardless of the landing id."""
    return jax.lax.dynamic_update_slice_in_dim(pool, page, dst, axis=1)


def verify_page_payload(data: Dict[str, int]) -> bool:
    """Host-side CRC check of one shipped-page payload (r18) — pure
    base64/zlib, no device work, so receivers can reject a
    corrupted-in-flight page BEFORE touching their pool.  The digest
    recipe matches :meth:`PagedKVCache._page_digest` exactly (K bytes
    plus — quantized, inferred from the scale keys — the K scale
    bytes), so a payload that verifies here lands with a CRC the
    importing pool's read-back validation will agree with."""
    kb = base64.b64decode(data["k"])
    vb = base64.b64decode(data["v"])
    if "k_scale" in data:
        kb += base64.b64decode(data["k_scale"])
        vb += base64.b64decode(data["v_scale"])
    return (zlib.crc32(kb) == data["crc_k"]
            and zlib.crc32(vb) == data["crc_v"])


class PagePoolExhausted(RuntimeError):
    """No free pages left — the scheduler's cue to preempt, never an
    OOM: the pool size is fixed at construction and allocation failure
    is an ordinary, recoverable scheduling event."""


class PagePoolCorruption(RuntimeError):
    """A pool page's content no longer matches its recorded CRC32 —
    an HBM bit flip / DMA fault stand-in (ISSUE 10).  Recoverable by
    construction: page content is always rebuildable from host-side
    tokens via deterministic re-prefill, so the engine treats this
    like a device loss (rebuild pool + restore) rather than an abort."""


class PagedKVCache:
    """Fixed-size paged KV pool shared by all in-flight requests.

    ``max_pages_per_request`` fixes the page-table width ``p_max`` —
    every decode step sees a static ``[batch, p_max]`` table, so
    admitting or retiring requests never recompiles the step.
    """

    def __init__(self, *, num_layers: int, num_pages: int,
                 page_size: int, num_heads: int, head_dim: int,
                 max_pages_per_request: int,
                 dtype=jnp.float32, crc_pages: bool = False,
                 quantize: Optional[str] = None,
                 latent_dim: Optional[int] = None):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "reserved scratch page)")
        if max_pages_per_request > num_pages - 1:
            raise ValueError(
                f"max_pages_per_request {max_pages_per_request} exceeds "
                f"the {num_pages - 1} allocatable pages")
        self.num_layers = num_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.max_pages_per_request = max_pages_per_request
        #: quantization mode (None / "int8" / "fp8").  ``dtype`` stays
        #: the COMPUTE dtype of the tokens fed to ``write_tokens``;
        #: quantized pools store narrow codes plus fp32 scales.
        self.quantize = quantize
        self.dtype = dtype
        #: numbers a token keeps in a latent pool (None: K and V heads)
        self.latent_dim = latent_dim
        if latent_dim is not None and quantize:
            raise ValueError("a latent pool is not quantized")
        pool_dtype = quant_pool_dtype(quantize) if quantize else dtype
        shape = (num_layers, num_pages, page_size) + (
            (num_heads, head_dim) if latent_dim is None
            else (latent_width(latent_dim),))
        self.k = jnp.zeros(shape, pool_dtype)
        self.v = (jnp.zeros(shape, pool_dtype) if latent_dim is None
                  else None)
        # the prefill scatter donates the old pool on TPU so the
        # update is in-place — two full-pool copies per admission
        # would otherwise sit on the TTFT-critical path
        if quantize:
            self.qmax = _QUANT_QMAX[quantize]
            sshape = (num_layers, num_pages, page_size, num_heads)
            self.k_scale = jnp.zeros(sshape, jnp.float32)
            self.v_scale = jnp.zeros(sshape, jnp.float32)
            donate = (0, 1, 2, 3) if jax.default_backend() == "tpu" else ()
            self._scatter = jax.jit(
                functools.partial(_scatter_tokens_quant, qmax=self.qmax),
                donate_argnums=donate)
        elif latent_dim is not None:
            self.qmax = None
            self.k_scale = self.v_scale = None
            self._scatter = jax.jit(
                _scatter_latent, donate_argnums=(0,)
                if jax.default_backend() == "tpu" else ())
        else:
            self.qmax = None
            self.k_scale = self.v_scale = None
            donate = (0, 1) if jax.default_backend() == "tpu" else ()
            self._scatter = jax.jit(_scatter_tokens, donate_argnums=donate)
        self._copy = jax.jit(
            _copy_page,
            donate_argnums=(0,) if jax.default_backend() == "tpu" else ())
        self._import = jax.jit(
            _import_page,
            donate_argnums=(0,) if jax.default_backend() == "tpu" else ())
        # sorted free list, lowest-first allocation: deterministic
        self._free: List[int] = list(range(1, num_pages))
        self._owner: Dict[int, int] = {}
        # per-page refcount (r17 prefix sharing): every allocated page
        # has exactly one entry; allocate -> 1, share -> +1, free -> -1
        # with the page returning to the free list only at zero
        self._ref: Dict[int, int] = {}
        # opt-in per-page CRC validation (ISSUE 10): every host-visible
        # write records a crc32 of the page's K and V bytes;
        # verify_pages re-reads the device content and raises
        # PagePoolCorruption on mismatch.  Costs a device->host pull
        # per touched page per step — a chaos/debug knob, off by
        # default (docs/serving.md "Failure semantics").
        self.crc_pages = bool(crc_pages)
        self._crc: Dict[int, Tuple[int, int]] = {}
        #: the pool of the layers that keep only a window of tokens,
        #: where the model has such layers (the engine sets it)
        self.window_pool: Optional["WindowPool"] = None
        #: the slots of the state-space layers' recurrent state, where
        #: the model has such layers (the engine sets it)
        self.state_pool: Optional["StatePool"] = None

    # -- slots beside pages (ISSUE 35) -------------------------------------

    @property
    def slots_free(self) -> Optional[int]:
        """State slots a new request could take; None where requests
        own no slot (no state-space layer)."""
        return (None if self.state_pool is None
                else self.state_pool.slots_free)

    def allocate_slot(self, owner: int) -> Optional[int]:
        """The slot an admitted request owns beside its pages (None
        where requests own none); raises :class:`PagePoolExhausted`
        when every slot is held."""
        return (None if self.state_pool is None
                else self.state_pool.allocate(owner))

    def free_slot(self, slot: Optional[int]) -> None:
        if slot is not None:
            self.state_pool.free(slot)

    @property
    def operands(self) -> Tuple[str, ...]:
        """The names of the pool's device arrays, in executable order:
        ``k`` and ``v`` (a latent pool: ``k`` alone), then a quantized
        pool's scale planes."""
        names = ("k",) if self.v is None else ("k", "v")
        if self.quantize:
            names += ("k_scale", "v_scale")
        return names

    def _each_operand(self, fn) -> None:
        """Rebind every operand to ``fn`` of it."""
        for name in self.operands:
            setattr(self, name, fn(getattr(self, name)))

    # -- accounting ------------------------------------------------------

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_used(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def pages_shared(self) -> int:
        """Pages currently referenced by MORE than one reader (live
        requests and/or the prefix index) — the ``pool_shared_pages``
        telemetry count."""
        return sum(1 for r in self._ref.values() if r > 1)

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)  # ceil

    def allocate(self, n: int, owner: int) -> List[int]:
        """Take ``n`` free pages for ``owner`` (a request id) at
        refcount 1; raises :class:`PagePoolExhausted` — with the pool
        untouched — when fewer than ``n`` are free."""
        if n > len(self._free):
            raise PagePoolExhausted(
                f"need {n} pages, {len(self._free)} free "
                f"({self.pages_used}/{self.num_pages - 1} in use)")
        pages, self._free = self._free[:n], self._free[n:]
        for p in pages:
            self._owner[p] = owner
            self._ref[p] = 1
        return pages

    def share(self, pages: Sequence[int]) -> None:
        """Add one reader to each page (prefix sharing): the pages'
        CONTENT becomes immutable until the refcount drops back —
        writers must :meth:`cow` first.  Raises on pages that are not
        currently allocated (sharing a free page would resurrect it)."""
        for p in pages:
            if p == 0 or p not in self._ref:
                raise ValueError(f"share of unallocated page {p}")
        for p in pages:
            self._ref[p] += 1

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def is_shared(self, page: int) -> bool:
        """True while more than one reader references ``page`` — the
        state in which writes (scatter/append), :meth:`free_tail` and
        :meth:`defrag` are forbidden on it (docs/serving.md
        "Prefix sharing")."""
        return self._ref.get(page, 0) > 1

    def cow(self, page: int, owner: int) -> int:
        """Copy-on-write: give ``owner`` a private copy of shared
        ``page`` and drop its own reference to the original.  Returns
        the new page id; the caller swaps it into its page list before
        writing.  Content (K, V and — quantized — the scale planes)
        moves by one compiled dynamic-slice copy per pool array, so
        repeated COWs never recompile.  Raises on an unshared page
        (a private page needs no copy — calling this would leak one)
        and propagates :class:`PagePoolExhausted` when no page is free
        (an ordinary scheduling event, like any allocation failure)."""
        if self._ref.get(page, 0) < 2:
            raise ValueError(f"cow on unshared page {page} "
                             f"(refcount {self._ref.get(page, 0)})")
        [new] = self.allocate(1, owner)
        src = jnp.int32(page)
        dst = jnp.int32(new)
        self._each_operand(lambda a: self._copy(a, src, dst))
        self._ref[page] -= 1
        # content moved verbatim, so the copy inherits the digest
        if page in self._crc:
            self._crc[new] = self._crc[page]
        return new

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; a page returns to the pool
        (retirement or preemption) only when its refcount reaches zero
        — while the prefix index or another request still references
        it, the page stays live.  The freed page's CONTENT is left in
        place — readers mask by ``kv_len``, so stale values are
        unreachable, and skipping the zero-fill keeps retirement
        free."""
        for p in pages:
            if p == 0 or p not in self._ref:
                raise ValueError(f"double free / scratch free: page {p}")
            self._ref[p] -= 1
            if self._ref[p] > 0:
                continue
            del self._ref[p]
            self._owner.pop(p, None)
            self._crc.pop(p, None)
            bisect.insort(self._free, p)

    def free_tail(self, pages: List[int], keep: int) -> None:
        """Free ``pages[keep:]`` IN PLACE — the speculative-verify
        rollback (ISSUE 12): pages grown to hold a draft's K/V whose
        tail rows were rejected are returned to the pool, and the
        request's page list is truncated to the committed footprint.
        A ``keep`` at or past the list length is a no-op (a fully
        accepted draft rolls back nothing).

        FORBIDDEN on shared pages (r17): draft tails only ever live in
        pages the request grew privately past its prompt, so a shared
        page in the tail means the rollback arithmetic is wrong —
        raising beats silently dropping another reader's prefix."""
        if keep < 0:
            raise ValueError(f"free_tail keep={keep} must be >= 0")
        tail = pages[keep:]
        shared = [p for p in tail if self.is_shared(p)]
        if shared:
            raise ValueError(
                f"free_tail would roll back shared page(s) {shared} — "
                "rollback is only defined on a request's private tail")
        if tail:
            self.free(tail)
            del pages[keep:]

    def owner_of(self, page: int) -> Optional[int]:
        return self._owner.get(page)

    # -- device-facing views ---------------------------------------------

    def page_table(self, page_lists: Sequence[Sequence[int]],
                   rows: Optional[int] = None) -> jnp.ndarray:
        """``[rows, max_pages_per_request]`` int32 table, each row a
        request's page list in cache order, padded with the scratch
        page 0 (padding the row with a REPEATED valid index also lets
        the decode kernel's block pipeline elide the dead DMAs)."""
        rows = len(page_lists) if rows is None else rows
        t = np.zeros((rows, self.max_pages_per_request), np.int32)
        for i, pages in enumerate(page_lists):
            if len(pages) > self.max_pages_per_request:
                raise ValueError(
                    f"page list of {len(pages)} exceeds "
                    f"max_pages_per_request={self.max_pages_per_request}")
            t[i, :len(pages)] = pages
        return jnp.asarray(t)

    def write_tokens(self, k_new: jnp.ndarray,
                     v_new: Optional[jnp.ndarray],
                     pages: jnp.ndarray, offsets: jnp.ndarray) -> None:
        """Scatter per-token K/V into the pool (the prefill fill path).

        ``k_new``/``v_new``: ``[num_layers, T, num_heads, head_dim]``
        in the COMPUTE dtype; token t lands in ``(pages[t],
        offsets[t])``.  Padding positions point at the scratch page 0.
        Quantized pools quantize-on-write: codes and per-(slot, head)
        scales are produced on device and scattered together.  A latent
        pool takes ``k_new`` ``[num_layers, T, latent_dim]`` and no
        ``v_new``."""
        touched = ({int(p) for p in np.asarray(pages).ravel()} - {0}
                   if self.crc_pages else ())
        pages = jnp.asarray(pages, jnp.int32)
        offsets = jnp.asarray(offsets, jnp.int32)
        if self.quantize:
            self.k, self.v, self.k_scale, self.v_scale = self._scatter(
                self.k, self.v, self.k_scale, self.v_scale,
                k_new, v_new, pages, offsets)
        elif self.v is None:
            self.k = self._scatter(self.k, k_new, pages, offsets)
        else:
            self.k, self.v = self._scatter(
                self.k, self.v, k_new, v_new, pages, offsets)
        if self.crc_pages:
            self.refresh_page_crcs(touched)

    def warm_copy(self) -> None:
        """Compile the COW page-copy executable (:meth:`cow`'s
        ``_copy_page``) against the live pool shapes — scratch page 0
        copied onto itself, a content no-op no reader ever sees — so
        the first shared-prefix admission's copy-on-write never pays a
        jit compile on the admission path.  Quantized pools warm the
        scale-plane shape too (same function, second specialization).
        Called from ``ServingEngine.warmup`` when prefix sharing is
        on; part of the zero-compiles-after-warmup contract."""
        z = jnp.int32(0)
        self._each_operand(lambda a: self._copy(a, z, z))

    def warm_import(self) -> None:
        """Compile the shipped-page import executable
        (:meth:`import_page_bytes`'s ``_import_page``) against the
        live pool shapes — an all-zero page written into scratch page
        0, a content no-op no reader ever sees — so a decode replica's
        FIRST inbound shipment never pays a jit compile.  Quantized
        pools warm the scale-plane shape too (same function, second
        specialization).  Called from ``ServingEngine.warmup`` when
        ``kv_import`` is on; part of the zero-compiles-after-warmup
        contract."""
        z = jnp.int32(0)
        pshape = (self.num_layers, 1, self.page_size,
                  self.num_heads, self.head_dim)
        self.k = self._import(self.k, jnp.zeros(pshape, self.k.dtype), z)
        self.v = self._import(self.v, jnp.zeros(pshape, self.v.dtype), z)
        if self.quantize:
            sshape = (self.num_layers, 1, self.page_size, self.num_heads)
            zs = jnp.zeros(sshape, jnp.float32)
            self.k_scale = self._import(self.k_scale, zs, z)
            self.v_scale = self._import(self.v_scale, zs, z)

    def warm_export(self) -> None:
        """Compile the page-slice gather :meth:`export_page_bytes`
        reads the pool through (``k[:, page:page+1]`` is a device op)
        by exporting scratch page 0 once and discarding the payload —
        so a prefill replica's FIRST outbound shipment never pays a
        jit compile.  Called from ``ServingEngine.warmup`` when
        ``prefill_only`` is on; the export twin of
        :meth:`warm_import`."""
        self.export_page_bytes(0)

    # -- page shipping (r18 disaggregation) ------------------------------

    def _no_latent_shipping(self) -> None:
        if self.v is None:
            raise ValueError("a latent pool's pages are not exported or "
                             "imported (docs/serving.md, \"The latent "
                             "page\")")

    def export_page_bytes(self, page: int) -> Dict[str, int]:
        """Serialize one page for shipping: C-order K/V page slices
        (quantized: the narrow codes, plus the fp32 scale planes as
        separate keys) as base64 text, with per-page CRCs stamped at
        export using the :meth:`_page_digest` recipe — the receiver
        verifies them host-side (:func:`verify_page_payload`) before
        its pool ever sees the bytes, and records them as the imported
        page's read-back digest."""
        self._no_latent_shipping()
        k = np.ascontiguousarray(np.asarray(self.k[:, page:page + 1]))
        v = np.ascontiguousarray(np.asarray(self.v[:, page:page + 1]))
        kb, vb = k.tobytes(), v.tobytes()
        out = {"k": base64.b64encode(kb).decode("ascii"),
               "v": base64.b64encode(vb).decode("ascii")}
        if self.quantize:
            ksb = np.ascontiguousarray(
                np.asarray(self.k_scale[:, page:page + 1])).tobytes()
            vsb = np.ascontiguousarray(
                np.asarray(self.v_scale[:, page:page + 1])).tobytes()
            out["k_scale"] = base64.b64encode(ksb).decode("ascii")
            out["v_scale"] = base64.b64encode(vsb).decode("ascii")
            kb += ksb
            vb += vsb
        out["crc_k"] = zlib.crc32(kb)
        out["crc_v"] = zlib.crc32(vb)
        return out

    def import_page_bytes(self, page: int, data: Dict[str, int]) -> None:
        """Land one shipped payload in (already allocated) ``page``,
        verbatim: the pool bytes after import are bitwise the source
        pool's bytes — including quantized codes and scale planes — so
        decode over an imported page is indistinguishable from decode
        over a locally prefilled one.  Callers verify the payload
        first (:func:`verify_page_payload`); this method trusts it and
        records the shipped CRCs as the page's read-back digest."""
        self._no_latent_shipping()
        pshape = (self.num_layers, 1, self.page_size,
                  self.num_heads, self.head_dim)
        dst = jnp.int32(page)
        k = np.frombuffer(base64.b64decode(data["k"]),
                          dtype=np.dtype(self.k.dtype)).reshape(pshape)
        v = np.frombuffer(base64.b64decode(data["v"]),
                          dtype=np.dtype(self.v.dtype)).reshape(pshape)
        self.k = self._import(self.k, jnp.asarray(k), dst)
        self.v = self._import(self.v, jnp.asarray(v), dst)
        if self.quantize:
            sshape = (self.num_layers, 1, self.page_size, self.num_heads)
            ks = np.frombuffer(base64.b64decode(data["k_scale"]),
                               dtype=np.float32).reshape(sshape)
            vs = np.frombuffer(base64.b64decode(data["v_scale"]),
                               dtype=np.float32).reshape(sshape)
            self.k_scale = self._import(self.k_scale, jnp.asarray(ks), dst)
            self.v_scale = self._import(self.v_scale, jnp.asarray(vs), dst)
        if self.crc_pages:
            # shipped bytes land verbatim, so the export digest IS the
            # imported page's digest — no device read-back needed
            self._crc[page] = (data["crc_k"], data["crc_v"])

    def analysis_executable(self, n_tokens: int, *, donate: bool = True):
        """``jax.stages.Lowered`` of the :meth:`write_tokens` scatter
        at an ``n_tokens``-row fill width, with the TPU pool donation
        forced on regardless of backend — the ISSUE 13 contract
        checker verifies the donation the shipped engine relies on (an
        undonated scatter copies BOTH full pools per admission on the
        TTFT-critical path: the PR 8 768 MB lesson).  ``donate=False``
        is the checker's negative control.  A quantized cache lowers
        the quantize-on-write variant with the scale planes donated
        too (params 0-3 alias outputs 0-3)."""
        sds = jax.ShapeDtypeStruct
        pool = sds(self.k.shape, self.k.dtype)
        idx = sds((n_tokens,), jnp.int32)
        if self.v is None:
            new = sds((self.num_layers, n_tokens, self.latent_dim),
                      self.dtype)
            return jax.jit(_scatter_latent, donate_argnums=(0,)
                           if donate else ()).lower(pool, new, idx, idx)
        new = sds((self.num_layers, n_tokens, self.num_heads,
                   self.head_dim), self.dtype)
        if self.quantize:
            scale = sds(self.k_scale.shape, jnp.float32)
            jitted = jax.jit(
                functools.partial(_scatter_tokens_quant, qmax=self.qmax),
                donate_argnums=(0, 1, 2, 3) if donate else ())
            return jitted.lower(pool, pool, scale, scale, new, new,
                                idx, idx)
        jitted = jax.jit(_scatter_tokens,
                         donate_argnums=(0, 1) if donate else ())
        return jitted.lower(pool, pool, new, new, idx, idx)

    # -- per-page CRC validation (ISSUE 10, opt-in) ----------------------

    def _page_digest(self, page: int) -> Tuple[int, int]:
        """crc32 of page ``page``'s K and V bytes across all layers
        (quantized: codes AND scale planes — content identity includes
        the scales, or a flipped scale bit would read back clean)."""
        k = np.ascontiguousarray(np.asarray(self.k[:, page]))
        kb = k.tobytes()
        if self.v is None:      # a latent page is key and value at once
            return (zlib.crc32(kb), 0)
        vb = np.ascontiguousarray(np.asarray(self.v[:, page])).tobytes()
        if self.quantize:
            # same sanctioned read-back as the code planes above —
            # device ``.tobytes()`` pulls the scale slice directly
            kb += self.k_scale[:, page].tobytes()
            vb += self.v_scale[:, page].tobytes()
        return (zlib.crc32(kb), zlib.crc32(vb))

    def refresh_page_crcs(self, pages: Sequence[int]) -> None:
        """Re-record CRCs after a host-visible write (prefill scatter /
        the decode step's per-row append).  No-op unless ``crc_pages``."""
        if not self.crc_pages:
            return
        for p in sorted({int(p) for p in pages} - {0}):
            self._crc[p] = self._page_digest(p)

    def verify_pages(self, page_lists: Sequence[Sequence[int]]) -> None:
        """Read-back validation: recompute each live page's digest and
        compare against the recorded CRC; raises
        :class:`PagePoolCorruption` naming the damaged page.  Pages
        with no recorded CRC (never written through a CRC-tracking
        path) are skipped — absence of a record is not corruption."""
        if not self.crc_pages:
            return
        for p in sorted({int(p) for lst in page_lists for p in lst} - {0}):
            want = self._crc.get(p)
            if want is None:
                continue
            if self._page_digest(p) != want:
                raise PagePoolCorruption(
                    f"page {p} failed CRC read-back "
                    f"(owner rid {self._owner.get(p)})")

    # -- defrag ----------------------------------------------------------

    def defrag(self, page_lists: Sequence[List[int]]) -> Dict[int, int]:
        """Compact live pages to the lowest pool indices.

        A long-running pool ends up with live pages scattered across
        the index space; compaction restores the dense prefix layout a
        fresh pool has (locality for the pool DMAs, and a cheap
        "occupancy == high-water-mark" invariant).  ``page_lists`` are
        the page lists of every live request, IN PLACE — they are
        rewritten to the new ids.  Returns the old→new mapping.
        Content moves by one device gather per pool array (quantized:
        the scale planes gather with the codes).

        FORBIDDEN while any page is shared (r17): under prefix sharing
        one physical page legitimately appears in several page lists,
        which breaks both the overlap check below (duplicates are no
        longer proof of corruption) and the dense-renumber arithmetic
        (a shared page would need ONE new id visible to every reader,
        including the prefix index's entries, which this method never
        sees).  Callers drain sharing first — evict the prefix index
        and wait for multi-reader pages to drop to refcount 1 — or
        skip the compaction; a pool with live sharing is by definition
        not fragmented enough to need it."""
        shared = sorted(p for p, r in self._ref.items() if r > 1)
        if shared:
            raise ValueError(
                f"defrag forbidden while page(s) {shared} are shared "
                "(refcount > 1) — evict the prefix index / let readers "
                "retire first")
        live: List[int] = []
        for pages in page_lists:
            live.extend(pages)
        if len(set(live)) != len(live):
            raise ValueError("page lists overlap — pool corruption")
        mapping = {old: new for new, old in enumerate(live, start=1)}
        src = np.arange(self.num_pages)
        for old, new in mapping.items():
            src[new] = old
        # pages outside the live prefix keep whatever content the
        # gather assigns them — they are free, nothing reads them
        src_j = jnp.asarray(src, jnp.int32)
        self._each_operand(lambda a: a[:, src_j])
        self._owner = {mapping[p]: o for p, o in self._owner.items()
                       if p in mapping}
        self._ref = {mapping[p]: r for p, r in self._ref.items()
                     if p in mapping}
        # content moves verbatim with the ids, so digests remap too
        self._crc = {mapping[p]: c for p, c in self._crc.items()
                     if p in mapping}
        self._free = list(range(len(live) + 1, self.num_pages))
        for pages in page_lists:
            pages[:] = [mapping[p] for p in pages]
        return mapping


@dataclasses.dataclass
class WindowPages:
    """What one request holds in a :class:`WindowPool`: the pages of
    the logical slots ``base, base + 1, ...`` (slot ``s`` is positions
    ``[s * page_size, (s + 1) * page_size)``).  Slots below ``base``
    have slid out of the window and gone back to the pool."""

    base: int = 0
    pages: List[int] = dataclasses.field(default_factory=list)


class WindowPool(PagedKVCache):
    """The pool of a model's sliding-window layers: a page is held only
    while some query still to come can see a token in it.

    A query at position ``p`` sees keys ``(p - window, p]``, so once the
    next query of a request is at ``p`` every slot that ends at or
    before ``p - window`` is dead: :meth:`slide` gives those pages back
    (after every decode step and prefill chunk), :meth:`grow` takes the
    pages of the positions about to be written.  Allocation, refcounts,
    the scatter and :meth:`defrag` are the parent's, over this pool's
    own free list; a request's table (:meth:`tables`) is COMPACT, the
    pages it still holds and the position of the first of them, so its
    width is what a request can hold at once
    (:meth:`pages_per_request`), not its whole length."""

    def __init__(self, *, window: int, **kw):
        super().__init__(**kw)
        if window < 1:
            raise ValueError(f"window {window} must be >= 1")
        self.window = int(window)

    @staticmethod
    def pages_per_request(window: int, launch: int, page_size: int) -> int:
        """The most pages a request holds at once when its widest launch
        writes ``launch`` tokens (a prefill chunk; 1 for decode): the
        window before the launch's first query, the launch, and one
        more where the two do not end on a page boundary."""
        return -(-(window - 1) // page_size) + -(-launch // page_size) + 1

    def first_slot(self, next_query: int) -> int:
        """The oldest slot a query at ``next_query`` (or later) sees."""
        return max(0, next_query - self.window + 1) // self.page_size

    def slide(self, held: WindowPages, next_query: int) -> int:
        """Give back the pages no query at or after ``next_query`` can
        see; returns how many went."""
        n = min(self.first_slot(next_query) - held.base, len(held.pages))
        if n <= 0:
            return 0
        self.free(held.pages[:n])
        del held.pages[:n]
        held.base += n
        return n

    def grow(self, held: WindowPages, first_query: int, end: int,
             owner: int) -> None:
        """Make ``held`` cover every slot a launch needs that writes
        positions up to ``end`` (exclusive) and whose first query is at
        ``first_query``.  A request that holds nothing yet starts at
        the window's first slot: what lies before it is never read, so
        a whole-row prefill of a long context keeps only its tail.
        Raises :class:`PagePoolExhausted` with nothing taken."""
        if not held.pages:
            held.base = max(held.base, self.first_slot(first_query))
        need = self.pages_needed(end) - held.base - len(held.pages)
        if need > 0:
            held.pages.extend(self.allocate(need, owner))

    def release(self, held: WindowPages) -> None:
        self.free(held.pages)
        held.pages.clear()
        held.base = 0

    def tables(self, helds: Sequence[WindowPages],
               rows: Optional[int] = None):
        """(pages ``[rows, max_pages_per_request]``, start ``[rows]``):
        each request's held pages, and the absolute position of the
        first of them (``flash_decode``'s ``kv_start``)."""
        rows = len(helds) if rows is None else rows
        start = np.zeros((rows,), np.int32)
        for i, held in enumerate(helds):
            start[i] = held.base * self.page_size
        return (self.page_table([held.pages for held in helds], rows),
                jnp.asarray(start))

    def write_targets(self, held: WindowPages, positions: np.ndarray):
        """Host-side (pages, offsets) of ``positions`` for the scatter;
        a position before the held slots goes to the scratch page."""
        slot = positions // self.page_size - held.base
        pages = np.asarray(held.pages + [0], np.int32)[
            np.where(slot >= 0, slot, len(held.pages))]
        return pages, (positions % self.page_size).astype(np.int32)


def _write_slot(ssm, conv, ssm_new, conv_new, slot):
    """pool[:, slot] = new for both arrays, the slot traced: every
    admission reuses one compiled executable."""
    return (jax.lax.dynamic_update_slice_in_dim(
                ssm, ssm_new[:, None].astype(ssm.dtype), slot, axis=1),
            jax.lax.dynamic_update_slice_in_dim(
                conv, conv_new[:, None].astype(conv.dtype), slot, axis=1))


class StatePool:
    """The recurrent state of a model's state-space layers: one SLOT a
    request, of fixed size, where attention layers have pages that grow.

    ``ssm`` ``[num_layers, num_slots, N, H * P]`` (float32 as served:
    the state is updated every token, and rounding it to bfloat16 each
    step drifts) and ``conv`` ``[num_layers, num_slots, taps - 1, ch]``
    (the convolution's tail, in the activations' type: a copy, nothing
    accumulates), allocated once.  Slot 0 is the SCRATCH slot, never
    handed out: idle decode rows and warm-up launches name it.
    Allocation is lowest-first, like pages.  A slot is NOT zeroed when
    it changes hands: a whole-row prefill overwrites it, and a chunked
    prefill's first chunk is told to start from zero
    (``PagedDecoder.extend``'s ``fresh``), so nothing of the last owner
    is ever read."""

    def __init__(self, *, num_layers: int, num_slots: int,
                 state_shape: Tuple[int, int], tail_shape: Tuple[int, int],
                 dtype=jnp.float32, state_dtype=jnp.float32):
        if num_slots < 2:
            raise ValueError("num_slots must be >= 2 (slot 0 is the "
                             "reserved scratch slot)")
        self.num_layers = num_layers
        self.num_slots = num_slots
        self.ssm = jnp.zeros((num_layers, num_slots) + tuple(state_shape),
                             state_dtype)
        self.conv = jnp.zeros((num_layers, num_slots) + tuple(tail_shape),
                              dtype)
        self._write = jax.jit(
            _write_slot, donate_argnums=(0, 1)
            if jax.default_backend() == "tpu" else ())
        self._free: List[int] = list(range(1, num_slots))
        self._owner: Dict[int, int] = {}

    @property
    def slots_free(self) -> int:
        return len(self._free)

    @property
    def slots_used(self) -> int:
        return (self.num_slots - 1) - len(self._free)

    def allocate(self, owner: int) -> int:
        if not self._free:
            raise PagePoolExhausted(
                f"no state slot free ({self.slots_used}/"
                f"{self.num_slots - 1} in use)")
        slot = self._free.pop(0)
        self._owner[slot] = owner
        return slot

    def free(self, slot: int) -> None:
        if slot == 0 or slot not in self._owner:
            raise ValueError(f"double free / scratch free: slot {slot}")
        del self._owner[slot]
        bisect.insort(self._free, slot)

    def owner_of(self, slot: int) -> Optional[int]:
        return self._owner.get(slot)

    def table(self, slots: Sequence[Optional[int]],
              rows: Optional[int] = None) -> jnp.ndarray:
        """``[rows]`` int32: each request's slot, the scratch slot for
        the rows past them."""
        rows = len(slots) if rows is None else rows
        t = np.zeros((rows,), np.int32)
        t[:len(slots)] = slots
        return jnp.asarray(t)

    def write(self, slot: int, ssm_new: jnp.ndarray,
              conv_new: jnp.ndarray) -> None:
        """Put a whole-row prefill's final state ``[num_layers, N, H *
        P]`` and tail ``[num_layers, taps - 1, ch]`` into ``slot`` (in
        place on the TPU: the pools are donated)."""
        self.ssm, self.conv = self._write(
            self.ssm, self.conv, ssm_new, conv_new, np.int32(slot))


class PrefixIndex:
    """Prompt-prefix registry backing page sharing (r17).

    Maps a previously prefilled context (token tuple) to the pages
    holding its K/V, taking its OWN +1 refcount on every registered
    page (``PagedKVCache.share``) so an entry outlives the request
    that built it — a popular system prompt stays warm in the pool
    after every request using it has retired.

    Admission asks :meth:`lookup` for the longest registered prefix of
    a new request's context; on a hit the scheduler shares those pages
    (prefill for the covered tokens is SKIPPED — the new request
    chunk-prefills only its suffix against the shared pages).  The
    shared coverage is capped at ``len(context) - 1`` tokens so every
    admitted request still computes at least its final prompt token —
    that chunk is what yields the first-token logits.

    Capacity is bounded (``max_entries``); eviction is OLDEST-FIRST
    (insertion order — deterministic, like every other scheduling
    decision here) and only drops the INDEX's reference: a page some
    live request still reads keeps a nonzero refcount and never
    returns to the free list (pinned by the r17 eviction test).
    """

    def __init__(self, cache: PagedKVCache, *, max_entries: int = 8):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.cache = cache
        self.max_entries = int(max_entries)
        # key -> (the entry's pages, its tokens as one bytes key a page)
        self._entries: "OrderedDict[Tuple[int, ...], Tuple[List[int], List[bytes]]]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def entries(self) -> List[Tuple[int, ...]]:
        return list(self._entries)

    def _blocks(self, tokens: Sequence[int]) -> List[bytes]:
        """``tokens``' whole pages, each page's tokens as one key."""
        ps = self.cache.page_size
        arr = np.asarray(tokens, np.int64)
        return [arr[i:i + ps].tobytes()
                for i in range(0, len(arr) - ps + 1, ps)]

    def register(self, tokens: Sequence[int],
                 pages: Sequence[int]) -> bool:
        """Register a completed prefill's context -> page-list mapping
        (the request KEEPS its own references; the index adds one per
        page).  Rejects contexts shorter than one page (nothing to
        share) and duplicate keys; enforces that ``pages`` is exactly
        the context's page footprint, no more — registering a
        request's decode-grown tail would share pages it is still
        writing."""
        key = tuple(int(t) for t in tokens)
        if len(key) < self.cache.page_size or key in self._entries:
            return False
        if len(pages) != self.cache.pages_needed(len(key)):
            raise ValueError(
                f"register: {len(pages)} pages for a {len(key)}-token "
                f"context (expected {self.cache.pages_needed(len(key))})")
        self.cache.share(pages)
        self._entries[key] = (list(pages), self._blocks(key))
        while len(self._entries) > self.max_entries:
            self.evict_one()
        return True

    def lookup(self, tokens: Sequence[int]) -> Tuple[int, List[int]]:
        """Longest usable shared prefix for ``tokens``: returns
        ``(m, pages)`` where the first ``m`` context tokens are covered
        by ``pages`` (the entry's leading ``ceil(m / page_size)``
        pages), or ``(0, [])`` on a miss.  ``m`` is capped at
        ``len(tokens) - 1`` (see class docstring) and hits below one
        full page are ignored.  When ``m`` ends mid-page the last
        shared page also holds the ENTRY's diverging tokens past ``m``
        — safe, because readers mask by their own ``kv_len`` and the
        new reader's first write into that page copies it first
        (copy-on-write).

        Entries are compared a PAGE at a time (a page's tokens are one
        key), and token by token only inside the page where an entry
        parts from the context: ``entries x pages`` comparisons, where a
        walk over tokens made a 16k-token document tens of milliseconds
        of every admission."""
        ps = self.cache.page_size
        ctx = [int(t) for t in tokens]
        blocks = self._blocks(ctx)
        best_m, best_pages = 0, []
        for key, (pages, key_blocks) in self._entries.items():
            lim = min(len(key), len(ctx) - 1)
            p, whole = 0, lim // ps
            while p < whole and key_blocks[p] == blocks[p]:
                p += 1
            m = p * ps
            while m < lim and key[m] == ctx[m]:
                m += 1
            if m >= ps and m > best_m:
                best_m = m
                best_pages = pages[:self.cache.pages_needed(m)]
        return best_m, list(best_pages)

    def evict_one(self) -> int:
        """Drop the oldest entry, releasing the index's reference on
        its pages; returns how many pages actually went back to the
        free list (pages another reader still holds stay live — the
        index can never free a page out from under a request)."""
        if not self._entries:
            return 0
        _, (pages, _) = self._entries.popitem(last=False)
        before = self.cache.pages_free
        self.cache.free(pages)
        return self.cache.pages_free - before

    def clear(self) -> int:
        """Evict every entry; returns pages returned to the pool."""
        freed = 0
        while self._entries:
            freed += self.evict_one()
        return freed

"""Inference serving engine (ISSUE 8): flash-decode kernel, paged
KV-cache, and continuous-batching scheduler.

Three composable layers, bottom-up:

* :func:`apex_tpu.ops.flash_decode` — decode-mode attention over a
  paged KV cache (the kernel lives with its training siblings in
  ``ops/attention.py``; routing via
  :func:`~apex_tpu.ops.flash_decode_route`, forceable with
  ``routing_override(decode=...)``).
* :class:`PagedKVCache` — fixed-size pages in a preallocated HBM pool,
  per-request page lists, deterministic lowest-first allocation,
  :meth:`~PagedKVCache.defrag` compaction.
* :class:`ContinuousBatchingScheduler` + :class:`ServingEngine` —
  admission/growth/preemption/retirement policy, and the engine that
  turns it into a fixed set of compiled device functions (prefill
  row, decode step, admission scatter — plus, with
  :class:`SpecConfig`, the speculative verify step and the
  chunked-prefill step).
* :mod:`apex_tpu.serving.spec` (ISSUE 12) — the draft–verify
  subsystem: pluggable :class:`Proposer` drafts
  (:class:`NgramProposer` suffix-cache baseline), exact greedy
  verify-accept at ``q_len = k + 1``, chunked prefill.
* r17 serving-perf modes, all ``ServingEngine`` knobs: ``tp`` (decode
  sharded over the parallel_state tensor axis), ``kv_quant``
  (int8/fp8 pool codes + fp32 scales, quantize-on-write /
  dequantize-in-kernel), ``prefix_sharing`` (:class:`PrefixIndex` —
  refcounted copy-on-write pages; repeated prompts pay prefill once).
* ISSUE 29: a second architecture behind the same engine —
  :class:`AfmoeConfig` / ``AfmoeBlock`` (sliding-window and full
  grouped-query layers, a dropless top-k expert layer that holds a
  share of the experts: :mod:`apex_tpu.serving.experts`) over two page
  lifetimes (:class:`WindowPool` beside the :class:`PagedKVCache`).
* ISSUE 33: a third — :class:`DeepseekV2Config` / ``DeepseekV2Block``
  (multi-head latent attention over a one-operand latent page,
  expanded at prefill and absorbed at decode; a group-limited softmax
  router over the same expert layer), which carries ``prefix_sharing``.

See docs/serving.md for the page-table layout, the admission policy,
decode routing, speculative decoding, prefix sharing, the quantized
parity bar, and the bench methodology.
"""

from apex_tpu.serving.engine import (  # noqa: F401
    ServingEngine,
    SimClock,
    poisson_trace,
    set_fault_hook,
)
from apex_tpu.serving.kv_cache import (  # noqa: F401
    PagedKVCache,
    PagePoolCorruption,
    PagePoolExhausted,
    PrefixIndex,
    StatePool,
    WindowPages,
    WindowPool,
    quantize_tokens,
)
from apex_tpu.serving.model import (  # noqa: F401
    AfmoeConfig,
    DeepseekV2Config,
    GraniteHybridConfig,
    PagedDecoder,
    ServingModelConfig,
    init_params,
    shard_params_tp,
)
from apex_tpu.serving.scheduler import (  # noqa: F401
    FINISHED,
    RUNNING,
    WAITING,
    ContinuousBatchingScheduler,
    QueueFullError,
    Request,
)
from apex_tpu.serving.spec import (  # noqa: F401
    NgramProposer,
    Proposer,
    SpecConfig,
)

__all__ = [
    "SpecConfig",
    "Proposer",
    "NgramProposer",
    "ServingEngine",
    "SimClock",
    "poisson_trace",
    "set_fault_hook",
    "PagedKVCache",
    "PagePoolCorruption",
    "PagePoolExhausted",
    "PrefixIndex",
    "StatePool",
    "WindowPages",
    "WindowPool",
    "quantize_tokens",
    "AfmoeConfig",
    "DeepseekV2Config",
    "GraniteHybridConfig",
    "PagedDecoder",
    "ServingModelConfig",
    "init_params",
    "shard_params_tp",
    "ContinuousBatchingScheduler",
    "QueueFullError",
    "Request",
    "WAITING",
    "RUNNING",
    "FINISHED",
]

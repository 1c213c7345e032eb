"""Unified quantized-attention engine — the single public attention API.

One integer attention pipeline (int8 Q·Kᵀ → requant onto the ε-grid →
shift-only streaming softmax → int A·V), many implementations, one front
door:

    from repro import attention as ATT

    spec = ATT.AttentionSpec(mode="decode", impl="ita", causal=True,
                             window=0, q_len=1)
    scales = ATT.QuantScales.per_tensor(0.05, s_out=0.02)
    out = ATT.dispatch(q, k, v, spec=spec, scales=scales,
                       q_offset=off, kv_len=n)

    ATT.list_backends(spec)          # eligible backends, priority order
    ATT.backend_reasons(spec)        # every backend's verdict
    ATT.dispatch(..., backend="ita_onepass_pallas")   # explicit override

Pieces:

- ``AttentionSpec``: frozen, hashable description of the computation
  (mode/impl/causal/window/softcap/query-scale/softmax/layout/GQA).
- ``QuantScales``: pytree of the s_q/s_k/s_v/s_out quantization scales
  (per-tensor scalars or per-head vectors).
- ``KVCacheState``: typed int8 KV ring-buffer state (replaces the plain
  cache dicts).
- ``PagedKVState``: the continuous-batching allocator — one shared
  head-major ``(num_pages, G, page_size, hd)`` arena, per-sequence page
  tables, an
  on-device free stack and per-page refcounts (prefix sharing +
  copy-on-write); logical ring semantics, O(live tokens) memory.
  Served by the fused kernels through the ``bhsd_paged`` layout +
  ``dispatch(..., page_table=...)``.
- ``PrefixIndex``: host-side chain-hash map from page-aligned prompt
  chunks to the physical pages already holding their bytes — the lookup
  structure behind serve-time KV prefix sharing.
- Backend registry: each implementation declares ``supports(spec)``;
  ``dispatch`` runs the first eligible backend (or an explicit
  ``backend=`` override). Adding a kernel = one ``register_backend``
  call, not another branch in a model if-ladder.
"""

from repro.attention.registry import (Backend, BackendUnsupported,  # noqa: F401
                                      all_backends, backend_reasons,
                                      dispatch, get_backend, list_backends,
                                      register_backend)
from repro.attention.spec import AttentionSpec, QuantScales  # noqa: F401
from repro.attention.state import (KVCacheState, PagedKVState,  # noqa: F401
                                   PrefixIndex)

# Importing the module registers the built-in backends.
from repro.attention import backends as _backends  # noqa: F401,E402

__all__ = [
    "AttentionSpec", "QuantScales", "KVCacheState", "PagedKVState",
    "PrefixIndex",
    "Backend", "BackendUnsupported", "dispatch", "list_backends",
    "backend_reasons", "register_backend", "get_backend", "all_backends",
]

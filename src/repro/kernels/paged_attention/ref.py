"""Pure-jnp oracle for paged decode attention.

One decode token per request slot attends over that request's KV history,
which lives scattered across a global page pool and is addressed through a
per-request block table — the inference-side analogue of the survey's
virtualized tensor memory (vDNN-style paging).

Layouts (match ``repro.models.attention`` conventions):
  q        (B, Kv, G, hd)   pre-scaled by hd^-0.5, roped at position L-1
  k_pages  (N, Kv, page, hd) global pool, head-major (one kv head's page is
                            a contiguous tile); page 0 is the reserved null
                            page. A layer-stacked (L, N, Kv, page, hd) pool
                            is read at ``layer``.
  v_pages  (N, Kv, page, hd)
  tables   (B, P) int32      page ids per request (padding entries -> 0)
  lengths  (B,) int32        valid tokens per request (incl. current token)

The oracle gathers the full (B, P*page) key band and masks by absolute
position, so it is exact for non-page-multiple lengths and sliding windows.

Quantized pools (``kv_dtype`` int8/fp8): ``k_scale``/``v_scale``
(N, Kv, page) f32 ride along and are gathered through the same block
table, dequantizing the band in f32 right at the gather — the oracle
counterpart of the kernels' fused in-gather dequant (no dequantized pool
is materialized beyond the gathered band this oracle builds anyway).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gather_band(pages, tables, S, scale, layer):
    """([L,] N, Kv, page, hd) pool -> (B, S, Kv, hd) f32 band via the block
    table (at ``layer`` of a stacked pool), dequantized when ``scale``
    ([L,] N, Kv, page) is present."""
    B = tables.shape[0]
    Kv, hd = pages.shape[-3], pages.shape[-1]
    idx = tables if layer is None else (layer, tables)
    band = pages[idx].swapaxes(2, 3).reshape(B, S, Kv, hd)
    band = band.astype(jnp.float32)
    if scale is not None:
        sc = scale[idx].swapaxes(2, 3).reshape(B, S, Kv, 1)
        band = band * sc.astype(jnp.float32)
    return band


def paged_attention_ref(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    window: int = 0,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """Returns (B, Kv, G, hd). Query position is ``lengths - 1`` per slot."""
    B, Kv, G, hd = q.shape
    page = k_pages.shape[-2]
    P = tables.shape[1]

    k = _gather_band(k_pages, tables, P * page, k_scale, layer)
    v = _gather_band(v_pages, tables, P * page, v_scale, layer)

    scores = jnp.einsum(
        "bkgh,bskh->bkgs", q.astype(jnp.float32), k,
        preferred_element_type=jnp.float32,
    )
    kpos = jnp.arange(P * page, dtype=jnp.int32)[None, :]          # (1, S)
    t = (lengths - 1)[:, None]                                     # query pos
    valid = kpos <= t
    if window > 0:
        valid = valid & (kpos > t - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w.astype(v.dtype), v)
    return out.astype(q.dtype)


def paged_prefill_attention_ref(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    start: jax.Array,
    q_len: jax.Array,
    *,
    window: int = 0,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """Chunked-prefill sibling of :func:`paged_attention_ref`.

    A *chunk* of queries per request attends over that request's pool pages
    — which, by the engine's write-then-attend contract, already hold the
    chunk's own KV — so in-chunk causality and attention to the cached
    prefix are the same absolute-position mask ``kpos <= qpos``.

      q      (B, T, Kv, G, hd)  pre-scaled, roped at start + t
      start  (B,) int32         absolute position of the chunk's first row
      q_len  (B,) int32         valid rows (1..T); rows >= q_len are padding
                                (their output is garbage by contract — the
                                caller masks it; see attention_paged)

    Exactness notes: only keys at ``kpos <= qpos`` are read, and every such
    position was written (cached prefix or earlier-in-chunk), so stale data
    in allocated-but-unwritten pages is never attended by a valid row.
    """
    B, T, Kv, G, hd = q.shape
    page = k_pages.shape[-2]
    P = tables.shape[1]

    k = _gather_band(k_pages, tables, P * page, k_scale, layer)
    v = _gather_band(v_pages, tables, P * page, v_scale, layer)
    scores = jnp.einsum(
        "btkgh,bskh->bkgts", q.astype(jnp.float32), k,
        preferred_element_type=jnp.float32,
    )                                                     # (B, Kv, G, T, S)
    kpos = jnp.arange(P * page, dtype=jnp.int32)[None, None, :]    # (1,1,S)
    qpos = (start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :])[
        :, :, None
    ]                                                              # (B,T,1)
    valid = kpos <= qpos
    if window > 0:
        valid = valid & (kpos > qpos - window)
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", w.astype(v.dtype), v)
    return out.astype(q.dtype)

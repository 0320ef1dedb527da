"""Pallas TPU paged attention: block-table-gathered K/V pages.

Pool layout is head-major, ``(N, Kv, page, hd)`` per layer, stacked over a
model's layers as ``(L, N, Kv, page, hd)``: one kv head's page is a
contiguous ``(page, hd)`` tile, so the k/v BlockSpec ``(1, 1, 1, page, hd)``
keeps the TPU's (sublane, lane) block rule — its last two dims are whole
array dims — and each grid step DMAs one contiguous tile.

Grid = (B, Kv, n_pages): one program row per (request slot, kv head), the
innermost page axis executed sequentially per core carrying the online-
softmax state (m, l, acc) in VMEM scratch — the paged sibling of the flash
forward kernel (``kernels/flash_attention/kernel.py``).

The page gather is the point of the kernel: ``tables`` (B, P) rides in as a
scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``), so the k/v
BlockSpec index_maps address pool page ``tables[b, j]`` directly — each
(page, hd) tile is DMA'd straight out of the global pool in HBM without ever
materializing a gathered (B, S, hd) key band.

One kernel serves chunked prefill and decode: T query rows per request
(flattened with the G query heads of a kv head into one (T*G, page) score
tile) attend to the block-table pages with per-row causal masking by
absolute position; decode is the T=1 call. The chunk's own KV is written
to the pool before the kernel runs, so in-chunk causality needs no separate
path. Per-request ``q_len`` masks the tail and — combined with ``window`` —
the sliding-window band, via explicit mask selects (fully-masked pages
contribute exact zeros, never NaNs).

Quantized pools (``kv_dtype`` int8/fp8): per-(page-slot, kv-head) f32
scales ([L,] N, Kv, page) enter as two extra operands whose index_map is the
same ``tables[b, j]`` page select. The whole (Kv, page) scale tile of the
page is DMA'd (a one-head block would break the block rule), the kernel's
head is selected from it, and the scale multiplies the (T*G, page) score
and probability tiles column-wise — ``q·(c·s) = (q·c)·s`` — so no
dequantized pool is ever materialized in HBM (kernels/paged_attention/
quant.py has the write side).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _head_scale(s_ref, k):
    """(1, 1, Kv, page) scale tile -> (1, page) row of kv head ``k``."""
    rows = s_ref[0, 0]
    sel = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == k
    return jnp.sum(jnp.where(sel, rows, 0.0), axis=0, keepdims=True)


def _paged_kernel(
    layer_ref,    # scalar prefetch (1,) int32 — layer of the stacked pool
    tables_ref,   # scalar prefetch (B, P) int32
    starts_ref,   # scalar prefetch (B,) int32 — chunk's first absolute pos
    qlens_ref,    # scalar prefetch (B,) int32 — valid rows in the chunk
    q_ref,        # (1, 1, T*G, hd)
    k_ref,        # (1, 1, 1, page, hd) — pool page selected by index_map
    v_ref,
    *rest,        # [ks_ref, vs_ref (1, 1, Kv, page) f32,] o_ref, m/l/acc scratch
    page: int, n_pages: int, window: int, G: int, quantized: bool,
):
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = starts_ref[b]
    qlen = qlens_ref[b]                       # valid rows (>= 1)

    # Pages entirely beyond the last VALID row's position contribute
    # nothing to any row the caller keeps; skip them. (Padding rows t >=
    # qlen may see fewer pages than their kpos<=qpos mask admits — their
    # output is garbage by contract.)
    @pl.when(j * page < start + qlen)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)              # (T*G, hd)
        k = k_ref[0, 0, 0].astype(jnp.float32)           # (page, hd)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        rows = q.shape[0]

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                # (T*G, page)
        if quantized:
            scores = scores * _head_scale(ks_ref, h)

        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0) // G
        mask = kpos <= qpos
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)

        scores = jnp.where(mask, scores, NEG_INF)
        m_prev = m_scr[...]                              # (T*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * _head_scale(vs_ref, h)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_prefill_attention_kernel(
    q: jax.Array,        # (B, T, Kv, G, hd) pre-scaled, roped at start + t
    k_pages: jax.Array,  # ([L,] N, Kv, page, hd)
    v_pages: jax.Array,
    tables: jax.Array,   # (B, P) int32, padding entries 0 (null page)
    start: jax.Array,    # (B,) int32 absolute position of row 0
    q_len: jax.Array,    # (B,) int32 valid rows per request (1..T)
    *,
    layer=None,          # int32 scalar: layer of an (L, N, ...) stacked pool
    window: int = 0,
    interpret=None,
    k_scale=None,        # ([L,] N, Kv, page) f32 when the pool is quantized
    v_scale=None,
) -> jax.Array:
    """Returns (B, T, Kv, G, hd); see the module docstring for the tiling.

    A stacked pool is read in place at ``layer`` (a scalar-prefetch
    operand of the index_maps): a layer scan never slices, and so never
    copies, its layer's pool.
    """
    interpret = resolve_interpret(interpret)
    B, T, Kv, G, hd = q.shape
    if layer is None:                     # one layer's pool: a stack of one
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    page = k_pages.shape[3]
    P = tables.shape[1]
    quantized = k_scale is not None
    # (B, Kv, T*G, hd): each kv head's query rows are one lane-dense tile
    qh = q.transpose(0, 2, 1, 3, 4).reshape(B, Kv, T * G, hd)

    kernel = functools.partial(
        _paged_kernel, page=page, n_pages=P, window=window, G=G,
        quantized=quantized,
    )
    pool_spec = pl.BlockSpec(
        (1, 1, 1, page, hd),
        lambda b, k, j, ly, tbl, st, ln: (ly[0], tbl[b, j], k, 0, 0),
    )
    head_spec = pl.BlockSpec(
        (1, 1, T * G, hd), lambda b, k, j, ly, tbl, st, ln: (b, k, 0, 0)
    )
    in_specs = [head_spec, pool_spec, pool_spec]
    operands = [qh, k_pages, v_pages]
    if quantized:
        # scale tiles ride the same block-table index_map as their pages
        scale_spec = pl.BlockSpec(
            (1, 1, Kv, page),
            lambda b, k, j, ly, tbl, st, ln: (ly[0], tbl[b, j], 0, 0),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Kv, P),
        in_specs=in_specs,
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, T * G, hd), q.dtype),
        name="paged_prefill_attention_kernel",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
        start.astype(jnp.int32), q_len.astype(jnp.int32), *operands,
    )
    return out.reshape(B, Kv, T, G, hd).transpose(0, 2, 1, 3, 4)

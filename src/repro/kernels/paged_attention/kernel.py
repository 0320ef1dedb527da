"""Pallas TPU paged attention: block-table-gathered K/V pages.

Pool layout is head-major, ``(N, Kv, page, hd)`` per layer, stacked over a
model's layers as ``(L, N, Kv, page, hd)``: a page's kv heads are
contiguous ``(page, hd)`` tiles, so one DMA copies a run of them whole.

Grid = (B, Kv / hg, T*G / rows): one program per (request slot, group of
``hg`` kv heads, tile of ``rows`` query rows). Each program walks only its
slot's live KV range, in blocks of ``block_pages`` pages: a
``lax.fori_loop`` over blocks ``[lo, hi)``, where
``hi`` is the block holding the chunk's last valid position
(``start + q_len - 1``) and ``lo`` is 0, or with ``window > 0`` the block
holding ``start - window + 1``. An empty range (``q_len`` 0) walks nothing.
The pools stay in HBM (``memory_space=pl.ANY``); each live page of a block
is one DMA per pool, of the group's ``hg`` heads (contiguous in the
head-major page), by its block-table id straight out of the global pool
into a double-buffered VMEM scratch, block ``i + 1``'s copies started
before block ``i`` is computed, so no gathered (B, S, hd) key band is ever
materialized. Only pages holding live keys are copied: the dead pages past
``hi`` (or inside the last block, past the live range) are never read, and
the keys of the buffer slots they would fill are masked and their values
zeroed. The online-softmax state (m, l, acc) of each head lives in VMEM
scratch across the blocks — the paged sibling of the flash forward kernel
(``kernels/flash_attention/kernel.py``).

The sizes adapt to the call's shape. ``block_pages``: at least 128 keys
where the horizon has them, so the (T*G, keys) score tile is lane-dense,
and as many more as the f32 score and per-head K/V tiles' VMEM budgets
allow — 512 keys for decode (T*G = G rows), 128 for a 256-token prefill
chunk at G = 4 (1024 rows). ``group_heads``: as many kv heads as keep a
program's query rows within 1024 and its double-buffered K and V blocks
within 4 MiB of VMEM — all 8 of granite-8b's for decode, where a page's DMA
is otherwise only 4 KiB, 8 of phi-3-vision's 32, one for a 256-token chunk.
``row_tile``: a head's T*G rows split into tiles of at most 1024
(granite-34b's MQA, G = 48, has 12,288 in a 256-token chunk), each tile
walking the slot's blocks. Mosaic lays a page's (page, hd) tile out in whole
128-lane tiles in HBM and slices no narrower extent, so with hd 96 or 64 the
DMAs copy the whole 128-lane tiles; the lanes past hd are zeroed in K and
dropped from the output.

One kernel serves chunked prefill and decode: T query rows per request
(flattened with the G query heads of a kv head into one (T*G, keys) score
tile) attend to the block-table pages with per-row causal masking by
absolute position; decode is the T=1 call. The chunk's own KV is written
to the pool before the kernel runs, so in-chunk causality needs no separate
path. Per-request ``q_len`` masks the tail and — combined with ``window`` —
the sliding-window band, via explicit mask selects (fully-masked blocks
contribute exact zeros, never NaNs).

Quantized pools (``kv_dtype`` int8/fp8): the codes ride the same page
DMAs. The per-(page-slot, kv-head) f32 scales ([L,] N, Kv, page) are a page
16 lanes wide, narrower than any HBM slice Mosaic will DMA, so before the
call they are gathered through the block table into lane-dense rows, one
(keys,) row per (slot, block, kv head), left in HBM; the kernel copies a
live block's rows for its heads beside the block's pages (the gather covers
the whole horizon, the copies only live blocks). Block ``i``'s row
multiplies the (T*G, keys) score and probability tiles column-wise —
``q·(c·s) = (q·c)·s`` — so no dequantized pool is ever materialized in HBM
(kernels/paged_attention/quant.py has the write side).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30
# VMEM budgets of one program. A KV block is never narrower than a vreg's 128
# lanes while the horizon has that many keys, and no wider than its f32
# (rows, keys) score tile and each head's f32 (keys, hd) K and V tiles allow.
# A program takes as many kv heads as keep its query rows within those of one
# 256-token chunk at G = 4 and its double-buffered K and V blocks within
# _KV_BUFFER_BYTES (v5e's default scoped VMEM is 16 MiB).
_SCORE_TILE_BYTES = 512 * 1024
_KV_TILE_BYTES = 256 * 1024
_KV_BUFFER_BYTES = 4 * 1024 * 1024
_MIN_BLOCK_KEYS = 128
_GROUP_ROWS = 1024


def _vmem_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) tile: whole (32 / itemsize, 128) vreg
    tiles, so a 16-key int8 page takes as much room as a bf16 one."""
    sub = 32 // itemsize
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def row_tile(rows: int) -> int:
    """Query rows of one program: all ``rows`` (= T*G) of a kv head up to
    1024, else the largest divisor of them under that which is a whole
    number of sublane tiles (a 256-token chunk at G = 48 is 12,288 rows)."""
    if rows <= _GROUP_ROWS:
        return rows
    return max((d for d in range(8, _GROUP_ROWS + 1, 8) if rows % d == 0),
               default=rows)


def block_pages(page: int, n_pages: int, rows: int, hd: int) -> int:
    """Pages per KV block of a call with ``rows`` (= T*G) query rows per kv
    head and a ``n_pages``-page horizon: the most whole 128-key lane tiles
    the score and K/V tile budgets allow, and at least one page."""
    keys = min(_SCORE_TILE_BYTES // (4 * row_tile(rows)),
               _KV_TILE_BYTES // (4 * -(-hd // 128) * 128))
    keys = max(keys // _MIN_BLOCK_KEYS, 1) * _MIN_BLOCK_KEYS
    return max(1, min(n_pages, keys // page))


def group_heads(Kv: int, rows: int, ppb: int, page: int, hd: int,
                itemsize: int) -> int:
    """KV heads one program takes (one DMA copies them out of a page): the
    most that divide ``Kv`` within the row budget, ``rows`` a head, and the
    K/V buffer budget, two ``ppb``-page blocks of K and of V a head."""
    per_head = 4 * ppb * _vmem_bytes(page, hd, itemsize)
    return max(d for d in range(1, Kv + 1) if Kv % d == 0 and (
        d == 1 or (d * rows <= _GROUP_ROWS
                   and d * per_head <= _KV_BUFFER_BYTES)))


def walk_blocks(start, q_len, *, block: int, n_keys: int, window: int, xp=np):
    """The keys ``[key_lo, key_hi)`` a chunk's valid rows can see and the
    blocks ``[lo, hi)`` of ``block`` keys that hold them, for a chunk of
    ``q_len`` rows from absolute position ``start`` over a ``n_keys``-key
    horizon. ``xp`` is ``np`` (the serve engine's host-side count) or
    ``jnp`` (the kernel); an empty range has ``hi == lo``."""
    key_hi = xp.minimum(start + q_len, n_keys)
    key_lo = xp.maximum(start - window + 1, 0) if window > 0 else 0
    lo = key_lo // block
    hi = xp.where(q_len > 0, xp.maximum((key_hi + block - 1) // block, lo), lo)
    return key_lo, key_hi, lo, hi


def walked_blocks(start, q_len, *, rows: int, hd: int, page: int,
                  n_pages: int, window: int) -> tuple[int, int]:
    """Host-side count for a call of ``rows`` (= T*G) query rows a kv head:
    the KV blocks its slots walk (chunks of ``q_len`` rows from ``start``),
    and those a walk over each slot's whole ``n_pages``-page horizon would
    cover, in the kernel's own blocks."""
    block = block_pages(page, n_pages, rows, hd) * page
    n_keys = n_pages * page
    _, _, lo, hi = walk_blocks(
        np.asarray(start), np.asarray(q_len), block=block, n_keys=n_keys,
        window=window,
    )
    return int((hi - lo).sum()), np.size(start) * -(-n_keys // block)


def _scale_band(scale, layer, tables, n_blocks: int, ppb: int):
    """(L, N, Kv, page) scales -> (B, n_blocks, Kv, 1, ppb*page): row ``k``
    of slot b's block ``blk`` holds kv head k's scales of the block's keys
    (the table padded with the null page to whole blocks). Mosaic DMAs no
    HBM slice narrower than a (8, 128) tile, and a page's scales are
    ``page`` lanes, so they are gathered into lane-dense block rows here, a
    row to a head so a group of heads is a slice of untiled dims, and the
    kernel copies a live block's rows beside its pages."""
    B, P = tables.shape
    Kv, page = scale.shape[2:]
    tables = jnp.pad(tables, ((0, 0), (0, n_blocks * ppb - P)))
    band = scale[layer, tables].reshape(B, n_blocks, ppb, Kv, page)
    band = band.transpose(0, 1, 3, 2, 4)
    return band.reshape(B, n_blocks, Kv, 1, ppb * page)


def _paged_kernel(
    layer_ref,    # scalar prefetch (1,) int32 — layer of the stacked pool
    tables_ref,   # scalar prefetch (B, P) int32
    starts_ref,   # scalar prefetch (B,) int32 — chunk's first absolute pos
    qlens_ref,    # scalar prefetch (B,) int32 — valid rows in the chunk
    q_ref,        # (1, hg, rows, hd): a tile of T*G query rows
    k_hbm,        # (L, N, Kv, page, hd) in HBM
    v_hbm,
    *rest,        # [ks_hbm, vs_hbm (B, n_blocks, Kv, 1, keys) f32 in HBM,]
                  # o_ref, m/l/acc scratch, k/v block buffers, [ks/vs
                  # block buffers,] DMA semaphores
    page: int, ppb: int, hg: int, n_pages: int, window: int, G: int, hd: int,
    quantized: bool,
):
    if quantized:
        ks_hbm, vs_hbm, o_ref, m_scr, l_scr, acc_scr, *bufs, sems = rest
    else:
        o_ref, m_scr, l_scr, acc_scr, *bufs, sems = rest
    k_buf, v_buf = bufs[:2]
    b = pl.program_id(0)
    h0 = pl.program_id(1) * hg                    # first kv head of the group
    rows, lanes = q_ref.shape[2:]
    row0 = pl.program_id(2) * rows                # first query row of the tile
    layer = layer_ref[0]
    start = starts_ref[b]
    bk = ppb * page
    key_lo, key_hi, lo, hi = walk_blocks(
        start, qlens_ref[b], block=bk, n_keys=n_pages * page, window=window,
        xp=jnp,
    )
    page_lo, page_hi = key_lo // page, (key_hi + page - 1) // page

    def block_dmas(blk, slot, wait: bool):
        """Start (or wait for) the DMAs of block ``blk``'s live pages, each
        the group's ``hg`` heads of one page, into buffer ``slot``; its
        other pages are never touched. A quantized pool's scale rows of the
        block (``hg`` rows of ``keys``) come with them."""
        def run(dma):
            if wait:
                dma.wait()
            else:
                dma.start()

        def page_dma(i, carry):
            pid = tables_ref[b, blk * ppb + i]
            for kv, (pool, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                run(pltpu.make_async_copy(
                    pool.at[layer, pid, pl.ds(h0, hg), :, pl.ds(0, lanes)],
                    buf.at[slot, i],
                    sems.at[kv, slot],
                ))
            return carry

        jax.lax.fori_loop(
            jnp.maximum(page_lo - blk * ppb, 0),
            jnp.minimum(page_hi - blk * ppb, ppb), page_dma, 0,
        )
        if quantized:
            for kv, (band, buf) in enumerate(((ks_hbm, bufs[2]),
                                              (vs_hbm, bufs[3]))):
                run(pltpu.make_async_copy(
                    band.at[b, blk, pl.ds(h0, hg)], buf.at[slot],
                    sems.at[2 + kv, slot],
                ))

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(hi > lo)
    def _first():
        block_dmas(lo, 0, wait=False)

    def body(blk, carry):
        slot = jax.lax.rem(blk - lo, 2)

        @pl.when(blk + 1 < hi)
        def _next():
            block_dmas(blk + 1, 1 - slot, wait=False)

        block_dmas(blk, slot, wait=True)
        kpos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        qpos = start + (
            row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        ) // G
        # live keys only: buffer slots of uncopied pages hold stale VMEM
        # (padding rows t >= q_len, whose output is garbage by contract,
        # are kept to the live keys too)
        mask = (kpos >= key_lo) & (kpos < key_hi) & (kpos <= qpos)
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        vpos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, lanes), 0)
        v_live = (vpos >= key_lo) & (vpos < key_hi)
        # the lanes past hd of a 128-lane HBM tile (hd 96, 64) hold whatever
        # the tile's padding holds: K's are zeroed, V's only fill the output
        # lanes the wrapper drops
        k_lane = jax.lax.broadcasted_iota(jnp.int32, (bk, lanes), 1) < hd

        for j in range(hg):                       # the group's kv heads
            q = q_ref[0, j].astype(jnp.float32)                  # (T*G, hd)
            k = k_buf[slot, :, j].astype(jnp.float32).reshape(bk, lanes)
            if lanes > hd:
                k = jnp.where(k_lane, k, 0.0)
            v = v_buf[slot, :, j].astype(jnp.float32).reshape(bk, lanes)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                    # (T*G, keys)
            if quantized:
                scores = scores * bufs[2][slot, j]             # (1, keys)
            scores = jnp.where(mask, scores, NEG_INF)
            m_prev = m_scr[j]                                    # (T*G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[j] = l_scr[j] * alpha + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = jnp.where(mask, p * bufs[3][slot, j], 0.0)
            acc_scr[j] = acc_scr[j] * alpha + jax.lax.dot(
                p, jnp.where(v_live, v, 0.0),
                preferred_element_type=jnp.float32,
            )
            m_scr[j] = m_new
        return carry

    jax.lax.fori_loop(lo, hi, body, 0)
    denom = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_prefill_attention_kernel(
    q: jax.Array,        # (B, T, Kv, G, hd) pre-scaled, roped at start + t
    k_pages: jax.Array,  # ([L,] N, Kv, page, hd)
    v_pages: jax.Array,
    tables: jax.Array,   # (B, P) int32, padding entries 0 (null page)
    start: jax.Array,    # (B,) int32 absolute position of row 0
    q_len: jax.Array,    # (B,) int32 valid rows per request (0..T)
    *,
    layer=None,          # int32 scalar: layer of an (L, N, ...) stacked pool
    window: int = 0,
    interpret=None,
    k_scale=None,        # ([L,] N, Kv, page) f32 when the pool is quantized
    v_scale=None,
) -> jax.Array:
    """Returns (B, T, Kv, G, hd); see the module docstring for the walk.

    A stacked pool is read in place at ``layer`` (a scalar-prefetch
    operand the DMAs index by): a layer scan never slices, and so never
    copies, its layer's pool. A slot with ``q_len`` 0 walks nothing and
    reads zeros.
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
    ppb = block_pages(page, P, T * G, hd)
    rows = row_tile(T * G)
    hg = group_heads(Kv, rows, ppb, page, hd, k_pages.dtype.itemsize)
    # Mosaic lays a page's (page, hd) tile out in whole 128-lane tiles in HBM
    # and slices no narrower extent of it, so the DMAs copy whole tiles: q
    # and the output carry ``lanes`` lanes, zero past hd (interpret mode
    # reads the array as it is)
    lanes = hd if interpret else -(-hd // 128) * 128
    # (B, Kv, T*G, lanes): each kv head's query rows are one lane-dense tile
    qh = q.transpose(0, 2, 1, 3, 4).reshape(B, Kv, T * G, hd)
    qh = jnp.pad(qh, ((0, 0),) * 3 + ((0, lanes - hd),))

    kernel = functools.partial(
        _paged_kernel, page=page, ppb=ppb, hg=hg, n_pages=P, window=window,
        G=G, hd=hd, quantized=quantized,
    )
    head_spec = pl.BlockSpec(
        (1, hg, rows, lanes), lambda b, g, r, ly, tbl, st, ln: (b, g, r, 0)
    )
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [head_spec, hbm, hbm]
    operands = [qh, k_pages, v_pages]
    scratch = [
        pltpu.VMEM((hg, rows, 1), jnp.float32),
        pltpu.VMEM((hg, rows, 1), jnp.float32),
        pltpu.VMEM((hg, rows, lanes), jnp.float32),
        pltpu.VMEM((2, ppb, hg, page, lanes), k_pages.dtype),
        pltpu.VMEM((2, ppb, hg, page, lanes), v_pages.dtype),
    ]
    if quantized:
        # each slot's per-key scale rows, gathered through its block table
        # and left in HBM; the kernel copies a live block's rows
        n_blocks = -(-P // ppb)
        in_specs += [hbm, hbm]
        operands += [
            _scale_band(sc, layer, tables, n_blocks, ppb)
            for sc in (k_scale, v_scale)
        ]
        scratch += [pltpu.VMEM((2, hg, 1, ppb * page), jnp.float32)] * 2
    # (K | V [| K scales | V scales], buffer)
    scratch.append(pltpu.SemaphoreType.DMA((4 if quantized else 2, 2)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Kv // hg, T * G // rows),
        in_specs=in_specs,
        out_specs=head_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, T * G, lanes), q.dtype),
        name="paged_prefill_attention_kernel",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
        start.astype(jnp.int32), q_len.astype(jnp.int32), *operands,
    )
    out = out[..., :hd].reshape(B, Kv, T, G, hd)
    return out.transpose(0, 2, 1, 3, 4)

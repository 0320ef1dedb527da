"""GQA attention: chunked (flash-structured) full-sequence path + KV-cache decode.

Design notes
------------
* The full-sequence path scans over query chunks so the (S, S) score matrix is
  never materialized — this is the pure-JAX baseline of flash attention; the
  Pallas kernel in ``repro.kernels.flash_attention`` is its TPU-tiled version.
  ``use_kernel=True`` is kernel-fused in both directions: the custom_vjp
  backward runs the Pallas dq/dkv kernels from saved (lse) stats.
* ``window > 0`` means sliding-window (local) attention; the chunked path then
  only reads the (window + chunk) key band per query chunk, so local-attention
  prefill is O(S * window) not O(S^2).
* Decode keeps a ring-buffer cache of size ``cache_len`` (= window for local
  layers) with an explicit position array, so sliding-window decode at 500k
  context holds only ``window`` entries.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense_init, rope_apply, subkey

NEG_INF = -1e30
INVALID_POS = -(2**30)


def init_attention(
    key: jax.Array, d: int, n_heads: int, n_kv: int, head_dim: int, cross: bool = False
) -> Params:
    p = {
        "wq": dense_init(subkey(key, "wq"), d, n_heads * head_dim),
        "wk": dense_init(subkey(key, "wk"), d, n_kv * head_dim),
        "wv": dense_init(subkey(key, "wv"), d, n_kv * head_dim),
        "wo": dense_init(subkey(key, "wo"), n_heads * head_dim, d),
    }
    return p


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (n, hd))


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,Sq,Kv,G,hd), k: (B,Sk,Kv,hd) -> (B,Kv,G,Sq,Sk) in f32."""
    return jnp.einsum("bskgh,btkh->bkgst", q, k, preferred_element_type=jnp.float32)


def _gqa_combine(w: jax.Array, v: jax.Array, dtype) -> jax.Array:
    """w: (B,Kv,G,Sq,Sk), v: (B,Sk,Kv,hd) -> (B,Sq,Kv,G,hd)."""
    return jnp.einsum("bkgst,btkh->bskgh", w.astype(dtype), v)


def _softmax(scores: jax.Array, mask: jax.Array) -> jax.Array:
    scores = jnp.where(mask, scores, NEG_INF)
    return jax.nn.softmax(scores, axis=-1)


def attention_apply(
    p: Params,
    x: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    theta: float,
    window: int = 0,
    causal: bool = True,
    positions: Optional[jax.Array] = None,
    memory: Optional[jax.Array] = None,
    memory_positions: Optional[jax.Array] = None,
    chunk_q: int = 512,
    collect_kv: bool = False,
    use_kernel: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full-sequence attention (train / prefill).

    x: (B, S, d). memory: (B, T, d) enables cross-attention (no causal mask,
    no rope on q/k per enc-dec convention here we do rope self-attn only).
    Returns (out (B,S,d), kv or None) where kv = roped k/v for cache prefill.
    """
    B, S, _ = x.shape
    dtype = x.dtype
    G = n_heads // n_kv
    q = _split_heads(x @ p["wq"].astype(dtype), n_heads, head_dim)
    if memory is None:
        src = x
    else:
        src = memory
    k = _split_heads(src @ p["wk"].astype(dtype), n_kv, head_dim)
    v = _split_heads(src @ p["wv"].astype(dtype), n_kv, head_dim)

    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    if memory is None:
        q = rope_apply(q, positions, theta)
        k = rope_apply(k, positions, theta)
        k_positions = positions
    else:
        if memory_positions is None:
            k_positions = jnp.arange(src.shape[1], dtype=jnp.int32)[None, :].repeat(B, 0)
        else:
            k_positions = memory_positions

    q = q.reshape(B, S, n_kv, G, head_dim) * (head_dim ** -0.5)

    if use_kernel and memory is None:
        from repro.kernels.flash_attention import ops as fa_ops

        out = fa_ops.flash_attention(q, k, v, causal, window)
        out = out.reshape(B, S, n_heads * head_dim)
        kv = {"k": k, "v": v} if collect_kv else None
        return out @ p["wo"].astype(dtype), kv

    def chunk_attn(q_chunk: jax.Array, qpos: jax.Array) -> jax.Array:
        # q_chunk: (B, C, Kv, G, hd); qpos: (B, C)
        if window > 0 and memory is None:
            # only the trailing (window + C) key band can be visible
            Sq = q_chunk.shape[1]
            band = min(k.shape[1], window + Sq)
            start = jnp.clip(qpos[0, 0] + Sq - band, 0, k.shape[1] - band)
            k_band = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            v_band = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            kpos = jax.lax.dynamic_slice_in_dim(k_positions, start, band, axis=1)
        else:
            k_band, v_band, kpos = k, v, k_positions
        scores = _gqa_scores(q_chunk, k_band)
        mask = jnp.ones(scores.shape[-2:], bool)[None, None, None]
        if causal and memory is None:
            mask = qpos[:, None, None, :, None] >= kpos[:, None, None, None, :]
        if window > 0 and memory is None:
            in_win = qpos[:, None, None, :, None] - kpos[:, None, None, None, :] < window
            mask = jnp.logical_and(mask, in_win)
        w = _softmax(scores, mask)
        return _gqa_combine(w, v_band, dtype)

    n_chunks = S // chunk_q if (S % chunk_q == 0 and S > chunk_q) else 1
    if n_chunks > 1:
        qs = q.reshape(B, n_chunks, chunk_q, n_kv, G, head_dim).swapaxes(0, 1)
        ps = positions.reshape(B, n_chunks, chunk_q).swapaxes(0, 1)
        out = jax.lax.map(lambda args: chunk_attn(*args), (qs, ps))
        out = out.swapaxes(0, 1).reshape(B, S, n_heads * head_dim)
    else:
        out = chunk_attn(q, positions).reshape(B, S, n_heads * head_dim)

    kv = {"k": k, "v": v} if collect_kv else None
    return out @ p["wo"].astype(dtype), kv


# ------------------------------------------------------------------- caching
def init_kv_cache(
    B: int, cache_len: int, n_kv: int, head_dim: int, dtype
) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros((B, cache_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((B, cache_len, n_kv, head_dim), dtype),
        "pos": jnp.full((cache_len,), INVALID_POS, jnp.int32),
    }


def fill_kv_cache(
    cache: Dict[str, jax.Array], k: jax.Array, v: jax.Array, positions: jax.Array
) -> Dict[str, jax.Array]:
    """Populate a cache from prefill kv (keeps the trailing ``cache_len``)."""
    S_c = cache["k"].shape[1]
    S = k.shape[1]
    if S >= S_c:
        sel = slice(S - S_c, S)
        return {"k": k[:, sel], "v": v[:, sel], "pos": positions[0, sel]}
    pad = S_c - S
    return {
        "k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
        "pos": jnp.concatenate(
            [positions[0], jnp.full((pad,), INVALID_POS, jnp.int32)]
        ),
    }


def attention_decode(
    p: Params,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    t: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    theta: float,
    window: int = 0,
    memory: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x: (B, 1, d); t: scalar int32 position of this token.

    Self-attention writes the roped k/v into the ring slot ``t % cache_len``
    and attends over all valid cache entries (window-masked via the explicit
    position array). Cross-attention (memory != None) attends over the full
    encoder output and leaves the cache untouched.
    """
    B = x.shape[0]
    dtype = x.dtype
    G = n_heads // n_kv
    q = _split_heads(x @ p["wq"].astype(dtype), n_heads, head_dim)

    if memory is not None:
        k = _split_heads(memory @ p["wk"].astype(dtype), n_kv, head_dim)
        v = _split_heads(memory @ p["wv"].astype(dtype), n_kv, head_dim)
        q = q.reshape(B, 1, n_kv, G, head_dim)[:, 0] * (head_dim ** -0.5)
        scores = jnp.einsum("bkgh,bskh->bkgs", q, k, preferred_element_type=jnp.float32)
        w = jax.nn.softmax(scores, axis=-1).astype(dtype)
        out = jnp.einsum("bkgs,bskh->bkgh", w, v).reshape(B, 1, n_heads * head_dim)
        return out @ p["wo"].astype(dtype), cache

    pos = jnp.full((B, 1), t, jnp.int32)
    q = rope_apply(q, pos, theta)
    k_new = rope_apply(_split_heads(x @ p["wk"].astype(dtype), n_kv, head_dim), pos, theta)
    v_new = _split_heads(x @ p["wv"].astype(dtype), n_kv, head_dim)

    S_c = cache["k"].shape[1]
    slot = (t % S_c).astype(jnp.int32)
    k_c = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
    v_c = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
    pos_c = jax.lax.dynamic_update_slice(cache["pos"], t[None].astype(jnp.int32), (slot,))

    q = q.reshape(B, n_kv, G, head_dim) * (head_dim ** -0.5)
    scores = jnp.einsum("bkgh,bskh->bkgs", q, k_c, preferred_element_type=jnp.float32)
    valid = (pos_c >= 0) & (pos_c <= t)
    if window > 0:
        valid = valid & (pos_c > t - window)
    w = _softmax(scores, valid[None, None, None, :]).astype(dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", w, v_c).reshape(B, 1, n_heads * head_dim)
    return out @ p["wo"].astype(dtype), {"k": k_c, "v": v_c, "pos": pos_c}


# ------------------------------------------------------------- paged caching
def init_paged_kv_cache(
    num_pages: int, page_size: int, n_kv: int, head_dim: int, dtype,
    kv_dtype: str = "",
) -> Dict[str, jax.Array]:
    """Per-layer KV page pool. Page 0 is the reserved null/trash page: block
    table padding and inactive-slot writes are routed there, and reads of it
    are always masked (or discarded with the slot's output).

    A quantized ``kv_dtype`` ("int8"/"fp8") stores the pools in the storage
    dtype and adds per-(page-slot, kv-head) f32 scale buffers ``ksc``/``vsc``
    (see ``kernels.paged_attention.quant``); the zero-initialized scales
    dequantize the null page to exact zeros.

    Layout is head-major, (N, Kv, page, hd) and scales (N, Kv, page): the
    paged kernels read one kv head's page as a contiguous tile
    (``kernels/paged_attention/kernel.py``). Writers index
    ``pool.at[page_id, :, slot]``."""
    from repro.kernels.paged_attention import quant

    store = quant.kv_storage_dtype(kv_dtype, dtype)
    pool = {
        "kp": jnp.zeros((num_pages, n_kv, page_size, head_dim), store),
        "vp": jnp.zeros((num_pages, n_kv, page_size, head_dim), store),
    }
    if quant.is_quantized(kv_dtype):
        pool["ksc"] = jnp.zeros((num_pages, n_kv, page_size), jnp.float32)
        pool["vsc"] = jnp.zeros((num_pages, n_kv, page_size), jnp.float32)
    return pool


def pool_write(pool: jax.Array, pid: jax.Array, slot: jax.Array,
               rows: jax.Array, layer=None) -> jax.Array:
    """Write one token row per kv head into a head-major pool leaf.

    pool ([L,] N, Kv, page, ...), written at ``layer`` when stacked;
    pid/slot (...) page id and in-page slot of each token; rows
    (..., Kv, ...) its per-head values. The head index is an explicit
    index of the scatter, so each written window is one trailing row and
    XLA keeps the pool in its default layout — a ``pool.at[pid, :, slot]``
    window spanning Kv and hd makes it relayout (copy) the whole pool
    around every write.
    """
    idx = (pid[..., None],
           jnp.arange(pool.shape[1 if layer is None else 2], dtype=jnp.int32),
           slot[..., None])
    if layer is not None:
        idx = (layer,) + idx
    return pool.at[idx].set(rows.astype(pool.dtype))


def attention_paged(
    p: Params,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    tables: jax.Array,
    start: jax.Array,
    q_len: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    theta: float,
    window: int = 0,
    use_kernel: bool = False,
    mesh=None,
    layer=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """T rows per slot against the paged KV pool: a prefill chunk, a
    speculative verify pass (T = k + 1) or a decode step (T = 1).

    x: (B, T, d) hidden states; cache: {"kp","vp"} (N, Kv, page, hd),
    or all layers' pools stacked (L, N, Kv, page, hd) with ``layer`` the
    one to write and read (in place: the stack is never sliced);
    tables: (B, P) int32; start: (B,) absolute position of x[:, 0]; q_len:
    (B,) valid rows (rows >= q_len are right-padding: their KV goes to the
    null page and their output is zeroed so downstream per-row compute
    stays deterministic). A decode step is the call with T = 1,
    ``start = lengths`` and ``q_len = active``: an inactive slot writes the
    null page and the kernel walks none of its pages.

    Write-then-attend: the rows' roped K/V land in their block-table
    pages first, then every row attends with the absolute-position causal
    mask ``kpos <= start + t`` — which covers both the cached prefix (pages
    adopted from the radix cache or written by earlier chunks) and
    earlier-in-chunk positions, and never reads allocated-but-unwritten
    pages. Unlike ``attention_decode``'s ring buffer, every slot has its
    own position, so continuous batching mixes requests at different
    depths. With ``mesh`` set the attention runs shard_map'd over the
    ``model`` axis on per-shard head slices (``kernels.paged_attention``)
    — the tensor-parallel serving path.
    """
    from repro.kernels.paged_attention import paged_prefill_attention

    B, T, _ = x.shape
    dtype = x.dtype
    G = n_heads // n_kv
    page = cache["kp"].shape[-2]

    pos = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B, T)
    q = rope_apply(_split_heads(x @ p["wq"].astype(dtype), n_heads, head_dim),
                   pos, theta)
    k_new = rope_apply(_split_heads(x @ p["wk"].astype(dtype), n_kv, head_dim),
                       pos, theta)
    v_new = _split_heads(x @ p["wv"].astype(dtype), n_kv, head_dim)

    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < q_len[:, None]
    page_idx = pos // page
    ok = valid & (page_idx < tables.shape[1])
    pid = jnp.where(
        ok,
        jnp.take_along_axis(
            tables, jnp.clip(page_idx, 0, tables.shape[1] - 1), axis=1
        ),
        0,
    )
    slot = jnp.where(ok, pos % page, 0)
    new_cache = dict(cache)
    if "ksc" in cache:
        # quantize-once at write time: each token row's codes + scale depend
        # only on its own values, so pool bytes are batch-independent
        from repro.kernels.paged_attention import quant

        k_codes, k_sc = quant.kv_quantize(k_new, cache["kp"].dtype)
        v_codes, v_sc = quant.kv_quantize(v_new, cache["vp"].dtype)
        new_cache["kp"] = pool_write(cache["kp"], pid, slot, k_codes, layer)
        new_cache["vp"] = pool_write(cache["vp"], pid, slot, v_codes, layer)
        new_cache["ksc"] = pool_write(cache["ksc"], pid, slot, k_sc, layer)
        new_cache["vsc"] = pool_write(cache["vsc"], pid, slot, v_sc, layer)
        scales = {"k_scale": new_cache["ksc"], "v_scale": new_cache["vsc"]}
    else:
        new_cache["kp"] = pool_write(cache["kp"], pid, slot, k_new, layer)
        new_cache["vp"] = pool_write(cache["vp"], pid, slot, v_new, layer)
        scales = {}

    q = q.reshape(B, T, n_kv, G, head_dim) * (head_dim ** -0.5)
    out = paged_prefill_attention(
        q, new_cache["kp"], new_cache["vp"], tables, start, q_len,
        window=window, use_kernel=use_kernel, mesh=mesh, layer=layer,
        **scales,
    )
    out = jnp.where(valid[:, :, None, None, None], out, 0)
    out = out.astype(dtype).reshape(B, T, n_heads * head_dim)
    return out @ p["wo"].astype(dtype), new_cache

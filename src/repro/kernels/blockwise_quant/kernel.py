"""Pallas TPU kernel: blockwise dynamic quantization / dequantization.

TPU adaptation of the CUDA kernel in bitsandbytes (Dettmers'21): the GPU
version binary-searches the code map per thread; on TPU we keep the whole
256-entry map resident in VMEM and use fully vectorized VPU compares:

  quantize tile:  absmax-reduce over the quant block axis, normalize, then
                  idx = #(midpoints <= value) via a (tile, 255) broadcast
                  compare-sum (no divergent control flow).
  dequantize:     code lookup as a (tile, 256) one-hot select-sum.

Tiling: values are reshaped (n_blocks, block); the grid walks TILE_ROWS
quant-blocks per program; block = 256 keeps the lane dimension MXU/VPU
aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blockwise_quant.ref import BLOCK, dynamic_map
from repro.kernels.runtime import resolve_interpret

TILE_ROWS = 64


def _quant_kernel(x_ref, codes_ref, out_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)                  # (TILE_ROWS, BLOCK)
    codes = codes_ref[...]                              # (1, 256)
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # (TILE_ROWS, 1)
    safe = jnp.where(scale > 0, scale, 1.0)
    normed = x / safe
    mid = (codes[0, 1:] + codes[0, :-1]) * 0.5          # (255,)
    # idx = number of midpoints strictly below the value (searchsorted right)
    idx = jnp.sum(
        (normed[:, :, None] >= mid[None, None, :]).astype(jnp.int32), axis=-1
    )
    out_ref[...] = idx.astype(jnp.uint8)
    scale_ref[...] = scale


def _dequant_kernel(idx_ref, scale_ref, codes_ref, out_ref):
    idx = idx_ref[...].astype(jnp.int32)                # (TILE_ROWS, BLOCK)
    codes = codes_ref[...]                              # (1, 256)
    onehot = (idx[:, :, None] == jnp.arange(256)[None, None, :]).astype(
        jnp.float32
    )
    vals = jnp.sum(onehot * codes[0][None, None, :], axis=-1)
    out_ref[...] = vals * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantize_pallas(x: jax.Array, block: int = BLOCK, interpret=None):
    interpret = resolve_interpret(interpret)
    n = x.size
    assert n % block == 0, (n, block)
    rows = n // block
    assert rows % TILE_ROWS == 0, (rows, TILE_ROWS)
    xb = x.reshape(rows, block)
    codes = jnp.asarray(dynamic_map())[None, :]

    grid = (rows // TILE_ROWS,)
    out, scale = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((1, 256), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, block), jnp.uint8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        name="quantize_pallas",
        interpret=interpret,
    )(xb, codes)
    return out.reshape(-1), scale[:, 0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dequantize_pallas(
    idx: jax.Array, scale: jax.Array, block: int = BLOCK, interpret=None
):
    interpret = resolve_interpret(interpret)
    rows = idx.size // block
    assert rows % TILE_ROWS == 0, (rows, TILE_ROWS)
    codes = jnp.asarray(dynamic_map())[None, :]
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(rows // TILE_ROWS,),
        in_specs=[
            pl.BlockSpec((TILE_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 256), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block), jnp.float32),
        name="dequantize_pallas",
        interpret=interpret,
    )(idx.reshape(rows, block), scale[:, None], codes)
    return out.reshape(-1)


# ------------------------------------------------- symmetric stash codec
# Fused kernels for the activation-stash codec (ops.stash_quantize /
# stash_dequantize): per 256-elem block, scale = absmax / code_max, codes
# round-to-int8 or cast-to-fp8-e4m3. Arithmetic order matches
# kernels.paged_attention.quant.kv_quantize op-for-op in f32, so codes and
# scales are BITWISE identical to the jnp reference — PR 9's grad-accuracy
# suite transfers unchanged to the fused path. Rows (= flat blocks) are
# padded to the tile multiple inside the wrapper; pad rows quantize to
# scale-0 / code-0 and are sliced off.
STASH_TILE_ROWS = 32   # int8/fp8 min sublane tile on TPU


def _stash_quant_kernel(x_ref, codes_ref, scale_ref, *, cmax, int_codes):
    xf = x_ref[...].astype(jnp.float32)                 # (TILE, block)
    absmax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
    scale = absmax / cmax
    safe = jnp.where(scale > 0, scale, 1.0)
    scaled = jnp.clip(xf / safe, -cmax, cmax)
    if int_codes:
        codes_ref[...] = jnp.round(scaled).astype(jnp.int8)
    else:
        codes_ref[...] = scaled.astype(codes_ref.dtype)
    scale_ref[...] = scale


def _stash_dequant_kernel(codes_ref, scale_ref, out_ref):
    x = codes_ref[...].astype(jnp.float32) * scale_ref[...].astype(jnp.float32)
    out_ref[...] = x.astype(out_ref.dtype)


def _pad_rows(a: jax.Array, rows: int) -> jax.Array:
    pad = (-rows) % STASH_TILE_ROWS
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a


@functools.partial(jax.jit, static_argnames=("storage", "block", "interpret"))
def stash_quantize_pallas(
    xb: jax.Array, storage: str = "int8", block: int = BLOCK, interpret=None
):
    """(rows, block) flat blocks -> (codes (rows, block) int8/fp8,
    scales (rows,) f32), bitwise-equal to kv_quantize on the same blocks."""
    from repro.kernels.paged_attention.quant import _QUANT

    interpret = resolve_interpret(interpret)
    sdt, cmax = _QUANT[storage]
    rows, b = xb.shape
    assert b == block, (xb.shape, block)
    xp = _pad_rows(xb, rows)
    prows = xp.shape[0]
    codes, scale = pl.pallas_call(
        functools.partial(
            _stash_quant_kernel, cmax=cmax,
            int_codes=jnp.dtype(sdt) == jnp.dtype(jnp.int8),
        ),
        grid=(prows // STASH_TILE_ROWS,),
        in_specs=[pl.BlockSpec((STASH_TILE_ROWS, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((STASH_TILE_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((STASH_TILE_ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((prows, block), sdt),
            jax.ShapeDtypeStruct((prows, 1), jnp.float32),
        ],
        name="stash_quantize_pallas",
        interpret=interpret,
    )(xp)
    return codes[:rows], scale[:rows, 0]


@functools.partial(jax.jit, static_argnames=("dtype", "block", "interpret"))
def stash_dequantize_pallas(
    codes: jax.Array,
    scales: jax.Array,
    dtype=jnp.float32,
    block: int = BLOCK,
    interpret=None,
):
    """(rows, block) codes + (rows,) scales -> (rows, block) ``dtype``,
    bitwise-equal to kv_dequantize (f32 multiply, then one cast)."""
    interpret = resolve_interpret(interpret)
    rows, b = codes.shape
    assert b == block, (codes.shape, block)
    cp = _pad_rows(codes, rows)
    sp = _pad_rows(scales[:, None], rows)
    prows = cp.shape[0]
    out = pl.pallas_call(
        _stash_dequant_kernel,
        grid=(prows // STASH_TILE_ROWS,),
        in_specs=[
            pl.BlockSpec((STASH_TILE_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((STASH_TILE_ROWS, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((STASH_TILE_ROWS, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((prows, block), jnp.dtype(dtype)),
        name="stash_dequantize_pallas",
        interpret=interpret,
    )(cp, sp)
    return out[:rows]

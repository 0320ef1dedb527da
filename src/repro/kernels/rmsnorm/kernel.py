"""Pallas TPU fused RMSNorm, forward and backward.

Forward — one pass per row tile: mean-of-squares reduce, rsqrt, scale —
fused so the row is read from HBM once (XLA emits separate reduce + multiply
kernels when the norm is unfused at the boundary of a remat block). Rows tile
over the grid; the feature dim stays whole in VMEM, and the row tile
shrinks with ``d`` so an f32 tile stays at TILE_BYTES (256 rows at d=512,
32 rows at d=4096): the backward holds several such tiles at once, and at
256 rows x 4096 they overflow the chip's scoped VMEM.

Backward — fused dx/dscale in the same row tiling. With xhat = x * inv and
gs = g * scale:

  dx     = inv * (gs - xhat * mean(gs * xhat))     per row
  dscale = sum_rows g * xhat                       cross-row reduce

The dscale reduce accumulates into a single (1, d) output block revisited by
every sequential grid step (init at step 0), so x and g are read from HBM
once for BOTH cotangents — the unfused backward reads x twice (once per
cotangent) and re-derives inv both times.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

TILE_ROWS = 256
TILE_BYTES = 512 * 1024     # one f32 (rows, d) tile


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # (rows, d)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    o_ref[...] = (x * inv * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_bwd_kernel(x_ref, s_ref, g_ref, dx_ref, ds_ref, *, eps: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    x = x_ref[...].astype(jnp.float32)                 # (rows, d)
    g = g_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)                 # (1, d)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    xhat = x * inv
    gs = g * s
    rowmean = jnp.mean(gs * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (inv * (gs - xhat * rowmean)).astype(dx_ref.dtype)
    ds_ref[...] += jnp.sum(g * xhat, axis=0, keepdims=True)


def _tile(rows: int, d: int) -> Tuple[int, int]:
    cap = max(8, min(TILE_ROWS, TILE_BYTES // (4 * d)) // 8 * 8)
    tile = min(cap, rows)
    pad = (-rows) % tile
    return tile, pad


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm_pallas(
    x: jax.Array, scale: jax.Array, eps: float = 1e-6, interpret=None
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = x.size // d
    xr = x.reshape(rows, d)
    tile, pad = _tile(rows, d)
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=((rows + pad) // tile,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(((rows + pad), d), x.dtype),
        name="rmsnorm_pallas",
        interpret=interpret,
    )(xr, scale[None, :])
    return out[:rows].reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm_bwd_pallas(
    x: jax.Array, scale: jax.Array, g: jax.Array, eps: float = 1e-6,
    interpret=None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused backward. Returns (dx with x's shape/dtype, dscale (d,) f32)."""
    interpret = resolve_interpret(interpret)
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = x.size // d
    xr = x.reshape(rows, d)
    gr = g.reshape(rows, d)
    tile, pad = _tile(rows, d)
    if pad:
        # zero rows contribute exact zeros to both dx (sliced off) and dscale
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
        gr = jnp.pad(gr, ((0, pad), (0, 0)))
    dx, dscale = pl.pallas_call(
        functools.partial(_rmsnorm_bwd_kernel, eps=eps),
        grid=((rows + pad) // tile,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(((rows + pad), d), x.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        name="rmsnorm_bwd_pallas",
        interpret=interpret,
    )(xr, scale[None, :], gr)
    return dx[:rows].reshape(orig_shape), dscale[0]

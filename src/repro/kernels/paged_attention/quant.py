"""Pool-dtype quantization for the paged KV cache.

The serving pool stores K/V pages in a reduced ``kv_dtype`` — int8
(symmetric linear) or fp8 (e4m3, scaled) — with one f32 scale per
(page, slot, kv-head), i.e. per written token-row per head: the absmax of
that row's ``head_dim`` block. This is the only granularity compatible
with the engine's write-once invariant: a page fills one token at a time
(decode appends) or one chunk at a time (chunked prefill), and a token's
stored bytes must never depend on what was written later or on batch
composition — so each row is quantized from its own values exactly once,
at write time. COW page copies and prefix-cache adoption move the codes
and scales together, byte-identical (zero re-quantization FLOPs).

Dequantization is fused into the consumers' page gather: the Pallas paged
decode/prefill kernels read the (page,) scale tile selected by the same
block-table index_map as the page itself, and the jnp oracles gather
scales through ``tables`` alongside the pools — no dequantized pool is
ever materialized.

Scale layout: pools (N, Kv, page, hd) carry scales (N, Kv, page) f32.
Per-token bytes go from ``2 * Kv * hd * itemsize(native)`` to
``2 * Kv * (hd + 4)`` — ~0.53x at bf16/hd=64, i.e. ~1.9x resident
requests per device at an equal pool-byte budget.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# storage dtype and max representable code magnitude per pool dtype
_QUANT = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}
KV_DTYPES = ("bf16", "int8", "fp8")
SCALE_BYTES = 4  # one f32 scale per (page-slot, kv-head)


def normalize_kv_dtype(kv_dtype: str) -> str:
    """Canonical pool-dtype name: '' means native (pool stored at the
    runtime compute dtype — the unquantized baseline; 'bf16' is its CLI
    spelling)."""
    if kv_dtype in ("", "native", "bf16"):
        return ""
    if kv_dtype not in _QUANT:
        raise ValueError(
            f"kv_dtype {kv_dtype!r} not in {('bf16',) + tuple(_QUANT)}"
        )
    return kv_dtype


def is_quantized(kv_dtype: str) -> bool:
    return normalize_kv_dtype(kv_dtype) != ""


def kv_storage_dtype(kv_dtype: str, native) -> jnp.dtype:
    kv_dtype = normalize_kv_dtype(kv_dtype)
    return jnp.dtype(_QUANT[kv_dtype][0]) if kv_dtype else jnp.dtype(native)


def _code_max(storage_dtype) -> float:
    for dt, cmax in _QUANT.values():
        if jnp.dtype(dt) == jnp.dtype(storage_dtype):
            return cmax
    raise ValueError(f"not a quantized pool dtype: {storage_dtype}")


def kv_quantize(x: jax.Array, storage_dtype) -> Tuple[jax.Array, jax.Array]:
    """Quantize K/V rows to the pool dtype.

    x: (..., hd) native-dtype rows -> (codes (..., hd) storage_dtype,
    scales (...,) f32) with ``scale = absmax / code_max`` per row, so
    dequantization is ``codes * scale``. All-zero rows get scale 0 and
    codes 0 (dequantizes to exact zeros — null-page semantics preserved).
    """
    cmax = _code_max(storage_dtype)
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = absmax / cmax
    safe = jnp.where(scale > 0, scale, 1.0)[..., None]
    scaled = jnp.clip(xf / safe, -cmax, cmax)
    if jnp.dtype(storage_dtype) == jnp.dtype(jnp.int8):
        codes = jnp.round(scaled).astype(jnp.int8)
    else:
        codes = scaled.astype(storage_dtype)
    return codes, scale


def kv_dequantize(codes: jax.Array, scales: jax.Array, dtype=jnp.float32):
    """codes (..., hd) pool dtype, scales (...,) f32 -> (..., hd) ``dtype``."""
    return (codes.astype(jnp.float32) * scales[..., None].astype(jnp.float32)
            ).astype(dtype)


def kv_token_bytes(n_kv: int, head_dim: int, kv_dtype: str,
                   native_itemsize: int = 2) -> int:
    """Pool bytes per cached token (K + V + scales) at ``kv_dtype``;
    ``native_itemsize`` prices the unquantized baseline (2 = bf16)."""
    if is_quantized(kv_dtype):
        itemsize = kv_storage_dtype(kv_dtype, None).itemsize
        return 2 * n_kv * (head_dim * itemsize + SCALE_BYTES)
    return 2 * n_kv * head_dim * native_itemsize

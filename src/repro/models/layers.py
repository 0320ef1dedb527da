"""Shared primitive layers: init helpers, norms, RoPE, MLPs.

All modules are pure functions: ``init_*`` returns a params pytree (f32
masters), ``*_apply`` consumes params + activations. Compute dtype is passed
explicitly (mixed-precision policy lives in ``repro.core.precision``).

The regions of the train step run under fixed ``jax.named_scope`` names:
``embed`` and ``norm`` here, ``attn`` and ``mlp`` in ``models.stack``,
``lm_head`` in ``models.lm``, ``optimizer`` in ``train.loop``. They are op
metadata only, and the chip benchmark reads device time per region by them
(``benchmarks/chip/chipbench/scopes.py``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# --------------------------------------------------------------------- init
def dense_init(key: jax.Array, d_in: int, d_out: int, scale: float = 1.0) -> jax.Array:
    """Truncated-normal fan-in init (f32 master weights)."""
    std = scale / np.sqrt(d_in)
    return std * jax.random.truncated_normal(key, -3.0, 3.0, (d_in, d_out), jnp.float32)


def subkey(key: jax.Array, tag: str) -> jax.Array:
    """Deterministic named subkey (stable across processes — crc32, not hash())."""
    import zlib

    return jax.random.fold_in(key, zlib.crc32(tag.encode()) % (2**31))


# --------------------------------------------------------------------- norms
def init_norm(norm: str, d: int) -> Params:
    p: Params = {"scale": jnp.ones((d,), jnp.float32)}
    if norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


@jax.named_scope("norm")
def norm_apply(
    p: Params, x: jax.Array, norm: str, eps: float = 1e-6, fused: bool = False
) -> jax.Array:
    """RMSNorm / LayerNorm in f32, result cast back to x.dtype.

    ``fused=True`` routes RMSNorm through the Pallas kernel whose custom_vjp
    computes dx/dscale in one fused pass (repro.kernels.rmsnorm); LayerNorm
    has no fused path and falls through to the jnp implementation.
    """
    if fused and norm == "rmsnorm":
        from repro.kernels.rmsnorm import rmsnorm

        return rmsnorm(x, p["scale"], eps)
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if norm == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(var + eps)
    out = x * p["scale"]
    if "bias" in p:
        out = out + p["bias"]
    return out.astype(dtype)


# ---------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def rope_apply(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                              # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                     # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------- MLP
def init_mlp(key: jax.Array, d: int, hidden: int, gated: bool) -> Params:
    p: Params = {
        "w_up": dense_init(subkey(key, "up"), d, hidden),
        "w_down": dense_init(subkey(key, "down"), hidden, d),
    }
    if gated:
        p["w_gate"] = dense_init(subkey(key, "gate"), d, hidden)
    return p


def mlp_apply(p: Params, x: jax.Array, gated: bool, act: str = "silu") -> jax.Array:
    dtype = x.dtype
    up = x @ p["w_up"].astype(dtype)
    a = getattr(jax.nn, act)
    if gated:
        h = a(x @ p["w_gate"].astype(dtype)) * up
    else:
        h = a(up)
    return h @ p["w_down"].astype(dtype)


# ----------------------------------------------------------------- embedding
def init_embed(key: jax.Array, vocab: int, d: int) -> Params:
    return {"table": 0.02 * jax.random.normal(subkey(key, "embed"), (vocab, d), jnp.float32)}


@jax.named_scope("embed")
def embed_apply(p: Params, ids: jax.Array, dtype: jnp.dtype) -> jax.Array:
    return jnp.take(p["table"].astype(dtype), ids, axis=0)


def logits_apply(
    head: Optional[Params], embed: Params, x: jax.Array, tied: bool
) -> jax.Array:
    """Final projection to (padded) vocab. Logits in f32 for a stable softmax."""
    xf = x.astype(jnp.float32)
    if tied:
        return xf @ embed["table"].astype(jnp.float32).T
    assert head is not None
    return xf @ head["w"].astype(jnp.float32)

"""RG-LRU recurrent block (Griffin / RecurrentGemma).

Block structure (the Griffin "recurrent block"):
    u    = x @ w_in            (width w)
    gate = gelu(x @ w_gate)
    u    = causal_conv(u)
    h_t  = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * u_t)       (RG-LRU)
    out  = (h * gate) @ w_out
with input gate i_t = sigmoid(Wx u_t), recurrence gate r_t = sigmoid(Wa u_t),
a_t = exp(-c * softplus(Lambda) * r_t), c = 8. Wa/Wx are block-diagonal
(``n_blocks`` blocks) as in Griffin.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models.layers import Params, dense_init, subkey
from repro.models.ssm import _causal_conv

RG_C = 8.0
N_BLOCKS = 8


def init_rglru(key: jax.Array, d: int, width: int, conv: int) -> Params:
    nb = N_BLOCKS
    bs = width // nb
    # Lambda init so that a in [0.9, 0.999] at r=1 (Griffin appendix)
    lam = jax.random.uniform(subkey(key, "lam"), (width,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(jnp.expm1(-jnp.log(lam) / RG_C))  # softplus^-1(-log(a)/c)
    blk = lambda tag: (bs**-0.5) * jax.random.normal(
        subkey(key, tag), (nb, bs, bs), jnp.float32
    )
    return {
        "w_in": dense_init(subkey(key, "in"), d, width),
        "w_gate": dense_init(subkey(key, "gate"), d, width),
        "conv_w": 0.1 * jax.random.normal(subkey(key, "cw"), (conv, width), jnp.float32),
        "conv_b": jnp.zeros((width,), jnp.float32),
        "gate_a": blk("ga"),
        "bias_a": jnp.zeros((width,), jnp.float32),
        "gate_x": blk("gx"),
        "bias_x": jnp.zeros((width,), jnp.float32),
        "lam": lam,
        "w_out": dense_init(subkey(key, "out"), width, d),
    }


def _block_diag(u: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """u: (..., width) x block-diagonal w: (nb, bs, bs) -> (..., width)."""
    nb, bs, _ = w.shape
    ub = u.reshape(u.shape[:-1] + (nb, bs))
    out = jnp.einsum("...nc,ncd->...nd", ub, w.astype(u.dtype))
    return out.reshape(u.shape) + b.astype(u.dtype)


def _gates(p: Params, u: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(a_t, gated input) in f32. u: (..., w)."""
    r = jax.nn.sigmoid(_block_diag(u, p["gate_a"], p["bias_a"]).astype(jnp.float32))
    i = jax.nn.sigmoid(_block_diag(u, p["gate_x"], p["bias_x"]).astype(jnp.float32))
    log_a = -RG_C * jax.nn.softplus(p["lam"]) * r
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    return a, beta * i * u.astype(jnp.float32)


def rglru_apply(p: Params, x: jax.Array, *, collect_state: bool = False):
    """Full-sequence recurrent block. x: (B, S, d)."""
    dtype = x.dtype
    u = x @ p["w_in"].astype(dtype)
    gate = jax.nn.gelu(x @ p["w_gate"].astype(dtype))
    u = _causal_conv(u, p["conv_w"].astype(dtype), p["conv_b"])

    a, b = _gates(p, u)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    out = (h.astype(dtype) * gate) @ p["w_out"].astype(dtype)
    if collect_state:
        K = p["conv_w"].shape[0]
        S = x.shape[1]
        xi = x @ p["w_in"].astype(dtype)
        if S >= K - 1:
            conv_state = xi[:, S - (K - 1) :, :]
        else:
            conv_state = jnp.pad(xi, ((0, 0), (K - 1 - S, 0), (0, 0)))
        return out, {"conv": conv_state, "h": h[:, -1]}
    return out


def rglru_apply_seqpar(
    p: Params,
    x: jax.Array,
    *,
    mesh,
    batch_axes,
    axis: str = "model",
):
    """Sequence-parallel RG-LRU: distribute the linear recurrence over
    ``axis`` (same chunk-summary construction as
    ``repro.models.ssm.mamba_apply_seqpar``; the RG-LRU recurrence is the
    same first-order affine scan with elementwise (B, width) state)."""
    import jax.sharding as jsh

    P = jsh.PartitionSpec
    bspec = tuple(batch_axes) if batch_axes else None
    xspec = P(bspec, axis, None)
    pspec = jax.tree.map(lambda _: P(), p)

    def inner(p_, x_):
        dtype = x_.dtype
        n = jax.lax.axis_size(axis)
        idx = jax.lax.axis_index(axis)
        u = x_ @ p_["w_in"].astype(dtype)
        gate = jax.nn.gelu(x_ @ p_["w_gate"].astype(dtype))

        K = p_["conv_w"].shape[0]
        tail = u[:, -(K - 1) :, :]
        halo = jax.lax.ppermute(tail, axis, [(i, (i + 1) % n) for i in range(n)])
        halo = jnp.where(idx == 0, jnp.zeros_like(halo), halo)
        u_ext = jnp.concatenate([halo, u], axis=1)
        u = _causal_conv(u_ext, p_["conv_w"].astype(dtype), p_["conv_b"])[
            :, K - 1 :, :
        ]

        a, b = _gates(p_, u)

        def combine(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, a2 * b1 + b2

        A_cum, h_loc = jax.lax.associative_scan(combine, (a, b), axis=1)
        all_A = jax.lax.all_gather(A_cum[:, -1], axis)
        all_h = jax.lax.all_gather(h_loc[:, -1], axis)
        _, h_pref = jax.lax.associative_scan(combine, (all_A, all_h), axis=0)
        h0 = jnp.take(h_pref, jnp.maximum(idx - 1, 0), axis=0)
        h0 = jnp.where(idx == 0, jnp.zeros_like(h0), h0)
        h = h_loc + A_cum * h0[:, None]
        return (h.astype(dtype) * gate) @ p_["w_out"].astype(dtype)

    fn = shard_map(inner, mesh=mesh, in_specs=(pspec, xspec), out_specs=xspec)
    return fn(p, x)


def init_rglru_state(p: Params, B: int, dtype) -> Dict[str, jax.Array]:
    width = p["w_in"].shape[1]
    K = p["conv_w"].shape[0]
    return {
        "conv": jnp.zeros((B, K - 1, width), dtype),
        "h": jnp.zeros((B, width), jnp.float32),
    }


def rglru_decode(
    p: Params, x: jax.Array, cache: Dict[str, jax.Array]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token step. x: (B, 1, d)."""
    dtype = x.dtype
    u_new = x[:, 0] @ p["w_in"].astype(dtype)                 # (B, w)
    gate = jax.nn.gelu(x[:, 0] @ p["w_gate"].astype(dtype))

    w = p["conv_w"].astype(dtype)
    hist = jnp.concatenate([cache["conv"], u_new[:, None]], axis=1)
    u = jnp.einsum("bkd,kd->bd", hist, w) + p["conv_b"].astype(dtype)

    a, b = _gates(p, u)
    h = a * cache["h"] + b
    out = (h.astype(dtype) * gate) @ p["w_out"].astype(dtype)
    return out[:, None], {"conv": hist[:, 1:], "h": h}

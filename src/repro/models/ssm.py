"""Mamba-1 selective SSM block (Falcon-Mamba architecture).

Train/prefill path uses an associative scan over time (O(log S) depth) or a
chunked scan (sequential over chunks, associative within — lower peak memory);
decode is a single recurrent step on an O(1) state.

State-space recurrence (per channel c, state s):
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * x_t
    y_t = C_t . h_t + D * x_t
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models.layers import Params, dense_init, subkey


def dt_rank(d_inner: int) -> int:
    return max(1, d_inner // 16)


def init_mamba(key: jax.Array, d: int, d_inner: int, state: int, conv: int) -> Params:
    r = dt_rank(d_inner)
    # S4D-real A init: A[c, s] = -(s + 1)
    A = jnp.tile(jnp.arange(1, state + 1, dtype=jnp.float32)[None, :], (d_inner, 1))
    dt_init = jax.random.uniform(
        subkey(key, "dtb"), (d_inner,), jnp.float32, 1e-3, 1e-1
    )
    return {
        "in_proj": dense_init(subkey(key, "in"), d, 2 * d_inner),
        "conv_w": 0.1 * jax.random.normal(subkey(key, "cw"), (conv, d_inner), jnp.float32),
        "conv_b": jnp.zeros((d_inner,), jnp.float32),
        "x_proj": dense_init(subkey(key, "xp"), d_inner, r + 2 * state),
        "dt_proj": dense_init(subkey(key, "dtp"), r, d_inner, scale=r**0.5),
        "dt_bias": jnp.log(jnp.expm1(dt_init)),  # softplus^-1
        "A_log": jnp.log(A),
        "D": jnp.ones((d_inner,), jnp.float32),
        "out_proj": dense_init(subkey(key, "out"), d_inner, d),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, S, C); w: (K, C)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(K):  # K is tiny (4); unrolled adds beat a grouped conv here
        out = out + xp[:, i : i + x.shape[1], :] * w[i]
    return out + b.astype(x.dtype)


def _ssm_coeffs(p: Params, x_conv: jax.Array) -> Tuple[jax.Array, ...]:
    """Input-dependent discretized (a, b) and readout C for the recurrence."""
    dtype = x_conv.dtype
    r = p["dt_proj"].shape[0]
    state = (p["x_proj"].shape[1] - r) // 2
    dbc = x_conv @ p["x_proj"].astype(dtype)
    dt_lo, B_ssm, C_ssm = jnp.split(dbc, [r, r + state], axis=-1)
    dt = jax.nn.softplus(
        (dt_lo @ p["dt_proj"].astype(dtype)).astype(jnp.float32) + p["dt_bias"]
    )                                                        # (B,S,di) f32
    A = -jnp.exp(p["A_log"])                                  # (di, s) f32
    a = jnp.exp(dt[..., None] * A)                            # (B,S,di,s)
    b = (dt * x_conv.astype(jnp.float32))[..., None] * B_ssm.astype(jnp.float32)[
        ..., None, :
    ]                                                         # (B,S,di,s)
    return a, b, C_ssm


def _readout(p: Params, h: jax.Array, C_ssm: jax.Array, x_conv: jax.Array) -> jax.Array:
    y = jnp.einsum("...ds,...s->...d", h, C_ssm.astype(jnp.float32))
    return (y + p["D"] * x_conv.astype(jnp.float32)).astype(x_conv.dtype)


def mamba_apply(
    p: Params,
    x: jax.Array,
    *,
    scan_mode: str = "assoc",
    chunk: int = 256,
    collect_state: bool = False,
):
    """Full-sequence Mamba. x: (B, S, d) -> (B, S, d) [+ final (conv,ssm) state]."""
    dtype = x.dtype
    d_inner = p["in_proj"].shape[1] // 2
    xz = x @ p["in_proj"].astype(dtype)
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_conv = jax.nn.silu(_causal_conv(x_in, p["conv_w"].astype(dtype), p["conv_b"]))

    a, b, C_ssm = _ssm_coeffs(p, x_conv)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    if scan_mode == "assoc":
        _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    else:
        B, S = a.shape[:2]
        n = S // chunk
        assert S % chunk == 0, (S, chunk)
        ar = a.reshape(B, n, chunk, *a.shape[2:]).swapaxes(0, 1)
        br = b.reshape(B, n, chunk, *b.shape[2:]).swapaxes(0, 1)

        def step(h0, ab):
            ac, bc = ab
            A_c, Bh = jax.lax.associative_scan(combine, (ac, bc), axis=1)
            h_chunk = Bh + A_c * h0[:, None]
            return h_chunk[:, -1], h_chunk

        h0 = jnp.zeros((B,) + a.shape[2:], a.dtype)
        _, hs = jax.lax.scan(step, h0, (ar, br))
        h = hs.swapaxes(0, 1).reshape(a.shape)

    y = _readout(p, h, C_ssm, x_conv)
    out = (y * jax.nn.silu(z)) @ p["out_proj"].astype(dtype)

    if collect_state:
        K = p["conv_w"].shape[0]
        S = x.shape[1]
        if S >= K - 1:
            conv_state = x_in[:, S - (K - 1) :, :]
        else:
            conv_state = jnp.pad(x_in, ((0, 0), (K - 1 - S, 0), (0, 0)))
        return out, {"conv": conv_state, "ssm": h[:, -1]}
    return out


def mamba_apply_seqpar(
    p: Params,
    x: jax.Array,
    *,
    mesh,
    batch_axes,
    axis: str = "model",
):
    """Sequence-parallel Mamba: distribute the selective scan over ``axis``.

    Beyond-paper optimization (EXPERIMENTS.md §Perf pair 3): instead of
    tensor-parallel weights (whose per-layer all-reduces on (B, S, d_inner)
    dominate the collective roofline), shard the SEQUENCE over the model
    axis. Each device scans its chunk locally; only the O(B x d_inner x s)
    chunk summaries (a-product, boundary state) and a (K-1)-token conv halo
    cross the ICI — megabytes instead of gigabytes per layer. Weights are
    replicated inside the region (ZeRO-3 storage + one gather per layer).

    x: (B, S, d) global. Returns (B, S, d) global.
    """
    import jax.sharding as jsh

    P = jsh.PartitionSpec
    b = tuple(batch_axes) if batch_axes else None
    xspec = P(b, axis, None)
    pspec = jax.tree.map(lambda _: P(), p)

    def inner(p_, x_):
        dtype = x_.dtype
        n = jax.lax.axis_size(axis)
        idx = jax.lax.axis_index(axis)
        d_inner = p_["in_proj"].shape[1] // 2
        xz = x_ @ p_["in_proj"].astype(dtype)
        x_in, z = jnp.split(xz, 2, axis=-1)

        # conv halo: previous chunk's last K-1 inputs from the left neighbor
        K = p_["conv_w"].shape[0]
        tail = x_in[:, -(K - 1) :, :]
        halo = jax.lax.ppermute(
            tail, axis, [(i, (i + 1) % n) for i in range(n)]
        )
        halo = jnp.where(idx == 0, jnp.zeros_like(halo), halo)
        x_ext = jnp.concatenate([halo, x_in], axis=1)
        x_conv = jax.nn.silu(
            _causal_conv(x_ext, p_["conv_w"].astype(dtype), p_["conv_b"])[
                :, K - 1 :, :
            ]
        )

        a, bb, C_ssm = _ssm_coeffs(p_, x_conv)

        def combine(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, a2 * b1 + b2

        A_cum, h_loc = jax.lax.associative_scan(combine, (a, bb), axis=1)

        # chunk summary -> exclusive prefix across devices (tiny collective)
        summ = (A_cum[:, -1], h_loc[:, -1])              # (B, di, s) x2
        all_A = jax.lax.all_gather(summ[0], axis)        # (n, B, di, s)
        all_h = jax.lax.all_gather(summ[1], axis)
        _, h_pref = jax.lax.associative_scan(combine, (all_A, all_h), axis=0)
        h0 = jnp.take(h_pref, jnp.maximum(idx - 1, 0), axis=0)
        h0 = jnp.where(idx == 0, jnp.zeros_like(h0), h0)

        h = h_loc + A_cum * h0[:, None]
        y = _readout(p_, h, C_ssm, x_conv)
        return (y * jax.nn.silu(z)) @ p_["out_proj"].astype(dtype)

    # default check_vma=True: replicated param in_specs then transpose to a
    # proper psum of the cotangents in the backward pass
    fn = shard_map(inner, mesh=mesh, in_specs=(pspec, xspec), out_specs=xspec)
    return fn(p, x)


def init_mamba_state(p: Params, B: int, dtype) -> Dict[str, jax.Array]:
    d_inner = p["in_proj"].shape[1] // 2
    K = p["conv_w"].shape[0]
    r = p["dt_proj"].shape[0]
    state = (p["x_proj"].shape[1] - r) // 2
    return {
        "conv": jnp.zeros((B, K - 1, d_inner), dtype),
        "ssm": jnp.zeros((B, d_inner, state), jnp.float32),
    }


def mamba_decode(
    p: Params, x: jax.Array, cache: Dict[str, jax.Array]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token recurrent step. x: (B, 1, d)."""
    dtype = x.dtype
    xz = x[:, 0] @ p["in_proj"].astype(dtype)
    x_in, z = jnp.split(xz, 2, axis=-1)                       # (B, di)

    w = p["conv_w"].astype(dtype)                             # (K, di)
    hist = jnp.concatenate([cache["conv"], x_in[:, None]], axis=1)  # (B, K, di)
    x_conv = jax.nn.silu(jnp.einsum("bkd,kd->bd", hist, w) + p["conv_b"].astype(dtype))

    a, b, C_ssm = _ssm_coeffs(p, x_conv[:, None])             # (B,1,di,s)
    h = a[:, 0] * cache["ssm"] + b[:, 0]
    y = _readout(p, h, C_ssm[:, 0], x_conv)
    out = (y * jax.nn.silu(z)) @ p["out_proj"].astype(dtype)
    return out[:, None], {"conv": hist[:, 1:], "ssm": h}

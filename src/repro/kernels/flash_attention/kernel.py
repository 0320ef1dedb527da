"""Pallas TPU flash attention (fwd), GQA + causal + sliding window.

TPU-native tiling (canonical 3D sequential grid, as in the upstream pallas
TPU flash kernel): grid = (batch*heads, n_q_blocks, n_k_blocks) with the
innermost k-block axis executed sequentially per core, carrying the online-
softmax state (row max m, row sum l, accumulator acc) in VMEM scratch.

  q tile  (block_q, hd)  VMEM      k/v tiles (block_k, hd)  VMEM
  scores = q @ k^T on the MXU in f32; masking via explicit mask multiply
  (never exp(-inf + inf) NaNs on fully-masked tiles — sliding windows make
  those reachable).

GQA: the grid's head axis enumerates query heads; the k/v index_map divides
by the group size so each kv head's tiles are shared by its G query heads.

Sequence lengths need not be block multiples: inputs are zero-padded up to
the tile grid and real extents are masked via the static ``q_len``/``kv_len``
kernel parameters.

Backward: fully kernel-fused (``kernel_bwd.py``) — the forward additionally
emits the per-row logsumexp ``lse = m + log(l)`` so the backward can
recompute tile probabilities ``p = exp(s - lse)`` on the MXU from saved
stats instead of replaying the softmax reduction. ``repro.kernels
.flash_attention.ops`` wires both directions into one ``custom_vjp``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def pick_blocks(S: int, Sk: int, block_q: int, block_k: int) -> Tuple[int, int]:
    """Clamp block sizes to the (8-aligned) padded sequence extents."""
    return min(block_q, _round_up(S, 8)), min(block_k, _round_up(Sk, 8))


def pad_seq(x: jax.Array, block: int) -> jax.Array:
    """Zero-pad the sequence axis (axis 1) of (BH, S, hd) to a block multiple."""
    pad = _round_up(x.shape[1], block) - x.shape[1]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def make_mask(
    qpos: jax.Array, kpos: jax.Array, *, causal: bool, window: int, kv_len: int
) -> jax.Array:
    """Shared validity mask: kv padding + causal + sliding window."""
    mask = kpos < kv_len
    if causal:
        mask = jnp.logical_and(mask, qpos >= kpos)
    if window > 0:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    return mask


def _fa_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, causal: bool, window: int, block_q: int, block_k: int, n_k: int,
    kv_len: int,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # (bq, hd)
    k = k_ref[0].astype(jnp.float32)              # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (bq, bk)

    qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = make_mask(qpos, kpos, causal=causal, window=window, kv_len=kv_len)

    scores = jnp.where(mask, scores, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1))
    # explicit mask multiply: exp() of fully-masked tiles contributes 0
    p = jnp.where(mask, jnp.exp(scores - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_new = acc_scr[...] * alpha[:, None] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(j == n_k - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(denom)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret", "group"),
)
def flash_attention_fwd_flat(
    q: jax.Array,   # (BH, S, hd) query heads, pre-scaled
    k: jax.Array,   # (BKv, Sk, hd)
    v: jax.Array,
    *,
    group: int,     # BH // BKv
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret=None,
) -> Tuple[jax.Array, jax.Array]:
    """Forward with saved stats. Returns (o (BH, S, hd), lse (BH, 1, S) f32).

    ``lse`` keeps a unit axis so its (1, block_q) block is a whole-array
    second-minor dim, as the TPU block rule asks.
    """
    interpret = resolve_interpret(interpret)
    BH, S, hd = q.shape
    Sk = k.shape[1]
    block_q, block_k = pick_blocks(S, Sk, block_q, block_k)
    q = pad_seq(q, block_q)
    k = pad_seq(k, block_k)
    v = pad_seq(v, block_k)
    Sp, Skp = q.shape[1], k.shape[1]
    n_q, n_k = Sp // block_q, Skp // block_k

    kernel = functools.partial(
        _fa_fwd_kernel, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k, kv_len=Sk,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda h, i, j: (h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sp, hd), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sp), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_q,), jnp.float32),
            _vmem((block_q,), jnp.float32),
            _vmem((block_q, hd), jnp.float32),
        ],
        name="flash_attention_fwd_flat",
        interpret=interpret,
    )(q, k, v)
    return o[:, :S], lse[:, :, :S]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret", "group"),
)
def flash_attention_flat(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    group: int,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret=None,
) -> jax.Array:
    """Output-only forward (compat wrapper over ``flash_attention_fwd_flat``)."""
    o, _ = flash_attention_fwd_flat(
        q, k, v, group=group, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return o


def _vmem(shape: Tuple[int, ...], dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)

"""Pallas TPU flash attention backward: two-pass dq / dk+dv from saved stats.

Standard scheme (FlashAttention §3.2, adapted to the TPU sequential grid):

  pass 0 (preprocess)  delta_i = sum_h dO_ih * O_ih            (BH, S)
  pass 1 (dq)          grid (BH, n_q, n_k), k sequential:
                         p  = exp(q k^T - lse)   (recomputed on the MXU)
                         ds = p * (dO v^T - delta)
                         dq += ds @ k            (VMEM accumulator)
  pass 2 (dk/dv)       grid (BKv, n_k, G * n_q), inner axis sequential over
                       (query head in group, q block):
                         dv += p^T @ dO
                         dk += ds^T @ q

The dk/dv grid walks kv heads, so the GQA group accumulation (G query heads
sharing one kv head) happens in the VMEM scratch accumulator — kv grads are
written once per k block, never materialized per query head.

Scores are recomputed from q/k and the saved forward stats ``lse = m +
log(l)``; nothing quadratic in sequence length is ever read from or written
to HBM. Masking (causal / sliding window / kv padding) matches the forward:
probabilities use an explicit mask-where so fully-masked rows (reachable via
sliding windows and block padding) contribute exact zeros, never NaNs. As in
the forward, dead tiles run no body and fetch no new block (the index_maps
clamp into the live range: k blocks of a q block in pass 1, q blocks of a k
block in pass 2), and full tiles skip the mask.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention.kernel import (
    TileMask,
    _vmem,
    pad_seq,
    pick_blocks,
    run_by_class,
)
from repro.kernels.runtime import resolve_interpret


def _fa_bwd_delta_kernel(o_ref, do_ref, delta_ref):
    o = o_ref[0].astype(jnp.float32)              # (bq, hd)
    do = do_ref[0].astype(jnp.float32)
    delta_ref[0, 0] = jnp.sum(o * do, axis=-1)


def _fa_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, tiles: TileMask,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def body(mask):
        q = q_ref[0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)        # (bq, hd)
        lse = lse_ref[0, 0]                       # (bq,)
        delta = delta_ref[0, 0]                   # (bq,)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                          # (bq, bk)
        p = jnp.exp(s - lse[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                          # (bq, bk)
        ds = p * (dp - delta[:, None])
        dq_scr[...] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    run_by_class(tiles, i, j, body)

    @pl.when(j == tiles.n_k - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, tiles: TileMask, n_inner: int,
):
    jk = pl.program_id(1)
    t = pl.program_id(2)              # enumerates (group member g, q block qi)
    qi = t % tiles.n_q

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(mask):
        q = q_ref[0].astype(jnp.float32)          # (bq, hd)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]                       # (bq,)
        delta = delta_ref[0, 0]
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0].astype(jnp.float32)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                          # (bq, bk)
        p = jnp.exp(s - lse[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # dv += p^T dO
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None])
        # dk += ds^T q
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    run_by_class(tiles, qi, jk, body)

    @pl.when(t == n_inner - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret", "group"),
)
def flash_attention_bwd_flat(
    q: jax.Array,    # (BH, S, hd) pre-scaled, as in the forward
    k: jax.Array,    # (BKv, Sk, hd)
    v: jax.Array,
    o: jax.Array,    # (BH, S, hd) forward output
    lse: jax.Array,  # (BH, 1, S) f32 forward stats
    do: jax.Array,   # (BH, S, hd) upstream cotangent
    *,
    group: int,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (dq (BH, S, hd), dk (BKv, Sk, hd), dv (BKv, Sk, hd))."""
    interpret = resolve_interpret(interpret)
    BH, S, hd = q.shape
    BKv, Sk = k.shape[0], k.shape[1]
    assert BH == BKv * group, (BH, BKv, group)
    block_q, block_k = pick_blocks(S, Sk, block_q, block_k)

    q = pad_seq(q, block_q)
    o = pad_seq(o, block_q)
    do = pad_seq(do, block_q)
    lse_p = jnp.pad(lse, ((0, 0), (0, 0), (0, q.shape[1] - S)))
    k = pad_seq(k, block_k)
    v = pad_seq(v, block_k)
    Sp, Skp = q.shape[1], k.shape[1]
    tiles = TileMask(causal, window, S, Sk, block_q, block_k)
    n_q, n_k = tiles.n_q, tiles.n_k

    # pass 0: per-row delta = sum(dO * O)
    delta = pl.pallas_call(
        _fa_bwd_delta_kernel,
        grid=(BH, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, block_q, hd), lambda h, i: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q), lambda h, i: (h, 0, i)),
        out_shape=jax.ShapeDtypeStruct((BH, 1, Sp), jnp.float32),
        name="flash_attention_bwd_flat_delta",
        interpret=interpret,
    )(o, do)

    # pass 1: dq over the forward's (BH, n_q, n_k) grid; dead steps repeat
    # the nearest live k block, so nothing is fetched for them
    def kv_map(h, i, j):
        return h // group, jnp.clip(j, *tiles.k_blocks(i)), 0

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, tiles=tiles),
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda h, i, j: (h, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda h, i, j: (h, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, hd), q.dtype),
        scratch_shapes=[_vmem((block_q, hd), jnp.float32)],
        name="flash_attention_bwd_flat_dq",
        interpret=interpret,
    )(q, k, v, do, lse_p, delta)

    # pass 2: dk/dv per kv head; the inner axis walks the G query heads of
    # the group times the q blocks, accumulating into one VMEM tile. Within
    # one query head, dead steps repeat the nearest live q block.
    n_inner = group * n_q

    def q_map(h, jk, t):
        qi = jnp.clip(t % n_q, *tiles.q_blocks(jk))
        return h * group + t // n_q, qi, 0

    def row_map(h, jk, t):
        qh, qi, _ = q_map(h, jk, t)
        return qh, 0, qi

    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, tiles=tiles, n_inner=n_inner),
        grid=(BKv, n_k, n_inner),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, 1, block_q), row_map),
            pl.BlockSpec((1, 1, block_q), row_map),
            pl.BlockSpec((1, block_k, hd), lambda h, jk, t: (h, jk, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, jk, t: (h, jk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda h, jk, t: (h, jk, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, jk, t: (h, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BKv, Skp, hd), k.dtype),
            jax.ShapeDtypeStruct((BKv, Skp, hd), v.dtype),
        ],
        scratch_shapes=[
            _vmem((block_k, hd), jnp.float32),
            _vmem((block_k, hd), jnp.float32),
        ],
        name="flash_attention_bwd_flat_dkv",
        interpret=interpret,
    )(q, do, lse_p, delta, k, v)

    return dq[:, :S], dk[:, :Sk], dv[:, :Sk]

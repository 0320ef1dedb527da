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
the tile grid and real extents are masked via ``TileMask``'s static
``q_len``/``kv_len``.

Tiles the mask reaches differently do different work (``TileMask``): a dead
tile (no valid pair: above the causal diagonal, left of a window band) runs
no body, and the k/v index_map clamps its block index into the row's live
range, so consecutive steps repeat a block index and the pipeline fetches
nothing for the dead ones; a full tile (every pair valid) skips the
iota/compare/where; only partial tiles (the diagonal, window edges, kv
padding) build the mask. A skipped tile would have added exact zeros, so
the result is the same.

Backward: fully kernel-fused (``kernel_bwd.py``) — the forward additionally
emits the per-row logsumexp ``lse = m + log(l)`` so the backward can
recompute tile probabilities ``p = exp(s - lse)`` on the MXU from saved
stats instead of replaying the softmax reduction. ``repro.kernels
.flash_attention.ops`` wires both directions into one ``custom_vjp``.
"""
from __future__ import annotations

import dataclasses
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


@dataclasses.dataclass(frozen=True)
class TileMask:
    """Which pairs of the (q block, k block) tile grid ``make_mask`` reaches.

    Works on Python ints and on traced grid indices alike. A tile is dead
    (``live`` false) when ``make_mask`` is false over all of it, full when
    it is true over all of it, and partial otherwise; padded q rows count
    as ``make_mask`` counts them, with no bound on ``qpos``.
    """

    causal: bool
    window: int
    q_len: int
    kv_len: int
    block_q: int
    block_k: int

    @property
    def n_q(self) -> int:
        return -(-self.q_len // self.block_q)

    @property
    def n_k(self) -> int:
        return -(-self.kv_len // self.block_k)

    def classify(self, i, j):
        """(live, full) of tile (q block i, k block j)."""
        q0, k0 = i * self.block_q, j * self.block_k
        q1, k1 = q0 + self.block_q - 1, k0 + self.block_k - 1
        live = k0 < self.kv_len
        full = k1 < self.kv_len
        if self.causal:
            live = jnp.logical_and(live, k0 <= q1)
            full = jnp.logical_and(full, k1 <= q0)
        if self.window > 0:
            k_last = jnp.minimum(k1, self.kv_len - 1)
            live = jnp.logical_and(live, q0 - k_last < self.window)
            full = jnp.logical_and(full, q1 - k0 < self.window)
        return live, full

    def k_blocks(self, i):
        """(lo, hi): every live tile of q block i has lo <= j <= hi."""
        q0 = i * self.block_q
        lo, hi = 0, self.n_k - 1
        if self.causal:
            hi = jnp.minimum(hi, (q0 + self.block_q - 1) // self.block_k)
        if self.window > 0:
            lo = jnp.maximum(q0 - self.window + 1, 0) // self.block_k
        return lo, hi

    def q_blocks(self, j):
        """(lo, hi): every live tile of k block j has lo <= i <= hi."""
        k0 = j * self.block_k
        lo, hi = 0, self.n_q - 1
        if self.causal:
            lo = k0 // self.block_q
        if self.window > 0:
            k_last = jnp.minimum(k0 + self.block_k - 1, self.kv_len - 1)
            hi = jnp.minimum(hi, (self.window + k_last - 1) // self.block_q)
        return lo, hi

    def mask(self, i, j) -> jax.Array:
        """The (block_q, block_k) ``make_mask`` of tile (i, j)."""
        shape = (self.block_q, self.block_k)
        qpos = i * self.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        kpos = j * self.block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return make_mask(qpos, kpos, causal=self.causal, window=self.window,
                         kv_len=self.kv_len)


def run_by_class(tiles: TileMask, i, j, body) -> None:
    """Run ``body(mask)`` on a live tile: mask None where the tile is full,
    the tile's mask where it is partial; nothing where it is dead."""
    live, full = tiles.classify(i, j)

    @pl.when(full)
    def _full():
        body(None)

    @pl.when(jnp.logical_and(live, jnp.logical_not(full)))
    def _partial():
        body(tiles.mask(i, j))


def _fa_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, tiles: TileMask,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(mask):
        q = q_ref[0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0].astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                          # (bq, bk)
        if mask is not None:
            scores = jnp.where(mask, scores, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1))
        p = jnp.exp(scores - m_new[:, None])
        if mask is not None:
            # explicit mask multiply: exp() of fully-masked rows contributes 0
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_new = acc_scr[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc_new

    run_by_class(tiles, i, j, body)

    @pl.when(j == tiles.n_k - 1)
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
    Sp = q.shape[1]
    tiles = TileMask(causal, window, S, Sk, block_q, block_k)

    def kv_map(h, i, j):
        # dead steps repeat the nearest live block: no fetch for them
        return h // group, jnp.clip(j, *tiles.k_blocks(i)), 0

    o, lse = pl.pallas_call(
        functools.partial(_fa_fwd_kernel, tiles=tiles),
        grid=(BH, tiles.n_q, tiles.n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
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

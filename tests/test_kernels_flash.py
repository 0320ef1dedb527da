"""Flash-attention kernel: shape/dtype/window sweeps vs the jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.kernel

from repro.kernels.flash_attention.kernel import TileMask, make_mask
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref


def make_qkv(B, S, Kv, G, hd, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, S, Kv, G, hd), dtype) * (hd**-0.5)
    k = jnp.asarray(rng.randn(B, S, Kv, hd), dtype)
    v = jnp.asarray(rng.randn(B, S, Kv, hd), dtype)
    return q, k, v


SHAPES = [
    (1, 128, 1, 1, 64),    # MQA single head
    (2, 256, 2, 4, 32),    # GQA
    (1, 512, 4, 1, 128),   # MHA-ish, MXU-aligned head_dim
    (1, 1024, 1, 4, 128),  # GQA G=4, 28 dead tiles per head, head_dim 128
]


@pytest.mark.parametrize("shape", SHAPES, ids=[str(s) for s in SHAPES])
@pytest.mark.parametrize("causal", [True, False])
def test_matches_ref(shape, causal):
    q, k, v = make_qkv(*shape)
    out = flash_attention(q, k, v, causal, 0)
    ref = flash_attention_ref(q, k, v, causal=causal, window=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# (S, window); from S = 512 the band has dead tiles on both of its sides
WINDOWS = [(256, 32), (256, 128), (256, 250), (640, 128), (1024, 200),
           (520, 64)]


@pytest.mark.parametrize(
    "S, window", WINDOWS,
    ids=[str(w) if S == 256 else f"S{S}-w{w}" for S, w in WINDOWS],
)
def test_sliding_window(S, window):
    q, k, v = make_qkv(1, S, 2, 2, 32, seed=3)
    out = flash_attention(q, k, v, True, window)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_bf16_tolerance():
    q, k, v = make_qkv(1, 128, 2, 2, 64, dtype=jnp.bfloat16, seed=5)
    out = flash_attention(q, k, v, True, 0)
    ref = flash_attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


def test_gradients_match_ref():
    q, k, v = make_qkv(1, 128, 1, 2, 32, seed=7)

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 0) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(flash_attention_ref(q, k, v, causal=True) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


def test_model_attention_kernel_path_matches_ref_path():
    """attention_apply(use_kernel=True) == attention_apply(use_kernel=False)."""
    from repro.models.attention import attention_apply, init_attention

    d, H, Kv, hd, S = 64, 4, 2, 16, 128
    p = init_attention(jax.random.PRNGKey(0), d, H, Kv, hd)
    x = jnp.asarray(np.random.RandomState(1).randn(2, S, d), jnp.float32)
    out_ref, _ = attention_apply(
        p, x, n_heads=H, n_kv=Kv, head_dim=hd, theta=1e4, chunk_q=32
    )
    out_ker, _ = attention_apply(
        p, x, n_heads=H, n_kv=Kv, head_dim=hd, theta=1e4, use_kernel=True
    )
    np.testing.assert_allclose(
        np.asarray(out_ker), np.asarray(out_ref), atol=3e-5, rtol=3e-5
    )


# (S, causal, window, block_q, block_k): windows under, at and off a block
# multiple, S off a block multiple, block_q != block_k both ways
TILE_CASES = [
    (256, True, 0, 128, 128),
    (256, False, 0, 128, 128),
    (300, True, 0, 128, 128),
    (300, False, 0, 128, 64),
    (640, True, 0, 64, 128),
    (1024, True, 100, 128, 128),
    (1024, True, 128, 128, 128),
    (1024, True, 200, 128, 128),
    (1000, True, 300, 128, 64),
    (520, True, 64, 64, 128),
    (512, False, 96, 128, 128),
    (2048, True, 0, 128, 128),
]


@pytest.mark.parametrize("case", TILE_CASES, ids=[str(c) for c in TILE_CASES])
def test_tile_classes_match_make_mask(case):
    """Dead exactly where make_mask is false over the tile, full exactly
    where it is true; the k range of a q block and the q range of a k block
    are exactly its live tiles (what the clamped index maps fetch)."""
    S, causal, window, bq, bk = case
    tiles = TileMask(causal, window, S, S, bq, bk)
    pos_q, pos_k = np.meshgrid(np.arange(tiles.n_q * bq),
                               np.arange(tiles.n_k * bk), indexing="ij")
    mask = np.asarray(make_mask(jnp.asarray(pos_q), jnp.asarray(pos_k),
                                causal=causal, window=window, kv_len=S))
    live_set = np.zeros((tiles.n_q, tiles.n_k), bool)
    n_full = 0
    for i in range(tiles.n_q):
        for j in range(tiles.n_k):
            tile = mask[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            live, full = (bool(x) for x in tiles.classify(i, j))
            assert live == tile.any(), (i, j)
            assert full == tile.all(), (i, j)
            live_set[i, j] = live
            n_full += full
    for i in range(tiles.n_q):
        lo, hi = (int(x) for x in tiles.k_blocks(i))
        assert list(np.flatnonzero(live_set[i])) == list(range(lo, hi + 1)), i
    for j in range(tiles.n_k):
        lo, hi = (int(x) for x in tiles.q_blocks(j))
        assert list(np.flatnonzero(live_set[:, j])) == list(range(lo, hi + 1)), j
    if case == (2048, True, 0, 128, 128):
        # the train2.seq2k benchmark cell: 136 live tiles, 120 of them full
        assert (live_set.sum(), n_full) == (136, 120)

"""Paged decode-attention kernel vs the jnp oracle, and the oracle vs a
dense gather-free computation. Sweeps GQA group sizes, sliding windows,
non-page-multiple request lengths, and explicit interpret mode. The
prefill-kernel sweeps at the bottom cover the chunked-prefill sibling:
chunk-length queries, ragged valid rows, nonzero start offsets (cached
prefixes), and the T=1 decode degeneration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.kernel

from repro.kernels.paged_attention import (
    paged_attention,
    paged_attention_ref,
    paged_prefill_attention,
    paged_prefill_attention_ref,
)
from repro.kernels.paged_attention.kernel import paged_prefill_attention_kernel


def make_case(B, Kv, G, hd, page, N, P, lengths, seed=0):
    """Random pool + per-request block tables covering ``lengths`` tokens."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, Kv, G, hd), jnp.float32) * (hd**-0.5)
    kp = jnp.asarray(rng.randn(N, Kv, page, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(N, Kv, page, hd), jnp.float32)
    # carve disjoint page runs out of 1..N-1 (page 0 = null)
    tables = np.zeros((B, P), np.int32)
    nxt = 1
    for b, L in enumerate(lengths):
        n = -(-L // page)
        assert nxt + n <= N
        tables[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


CASES = [
    # (B, Kv, G, hd, page, N, P, lengths)  — lengths off page multiples
    (1, 1, 1, 32, 8, 8, 4, [13]),          # MQA
    (3, 2, 4, 32, 8, 32, 4, [13, 27, 5]),  # GQA
    (2, 4, 2, 64, 16, 16, 4, [64, 33]),    # exact + off multiple
    (2, 2, 8, 32, 4, 32, 8, [1, 31]),      # single-token request
    # granite widths (hd 128, page 16): decode blocks of 32 pages, so a
    # 40-page horizon is one whole block and a partial one
    (3, 2, 4, 128, 16, 16, 40, [1, 17, 150]),   # live context << horizon
    (2, 1, 4, 128, 16, 70, 40, [512, 513]),     # ends on / past a block edge
    (1, 2, 4, 128, 16, 48, 40, [640]),          # the whole horizon
    (1, 1, 48, 128, 16, 24, 40, [300]),         # MQA at G = 48
]
LONG = CASES[4]


@pytest.mark.parametrize("case", CASES, ids=[str(c[:4]) for c in CASES])
@pytest.mark.parametrize("window", [0, 6])
def test_kernel_matches_ref(case, window):
    B, Kv, G, hd, page, N, P, lengths = case
    q, kp, vp, tables, lens = make_case(B, Kv, G, hd, page, N, P, lengths)
    out = paged_attention(q, kp, vp, tables, lens, window=window,
                          use_kernel=True)
    ref = paged_attention_ref(q, kp, vp, tables, lens, window=window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_kernel_interpret_mode_explicit():
    q, kp, vp, tables, lens = make_case(2, 2, 2, 32, 8, 16, 4, [9, 20], seed=3)
    out = paged_attention(q, kp, vp, tables, lens, use_kernel=True,
                          interpret=True)
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ref_matches_dense_attention():
    """The oracle's block-table gather == attending over the contiguous
    sequence the pages encode."""
    B, Kv, G, hd, page, N, P = 2, 2, 2, 16, 8, 16, 4
    lengths = [11, 26]
    q, kp, vp, tables, lens = make_case(B, Kv, G, hd, page, N, P, lengths,
                                        seed=7)
    out = paged_attention_ref(q, kp, vp, tables, lens)
    for b, L in enumerate(lengths):
        pages = np.asarray(tables)[b]
        k = np.asarray(kp)[pages].swapaxes(1, 2).reshape(-1, Kv, hd)[:L]
        v = np.asarray(vp)[pages].swapaxes(1, 2).reshape(-1, Kv, hd)[:L]
        scores = np.einsum("kgh,skh->kgs", np.asarray(q)[b], k)
        w = jax.nn.softmax(jnp.asarray(scores), axis=-1)
        expect = np.einsum("kgs,skh->kgh", np.asarray(w), v)
        np.testing.assert_allclose(
            np.asarray(out)[b], expect, atol=2e-5, rtol=2e-5
        )


def test_null_page_padding_is_masked():
    """Garbage in null-page / padding table entries must not leak into any
    request within its valid length."""
    q, kp, vp, tables, lens = make_case(2, 2, 2, 16, 8, 16, 4, [9, 12], seed=1)
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    kp2 = kp.at[0].set(1e3)  # poison the null page
    vp2 = vp.at[0].set(-1e3)
    out = paged_attention(q, kp2, vp2, tables, lens, use_kernel=True)
    ref2 = paged_attention_ref(q, kp2, vp2, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref2),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(ref2), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_window_equals_full_when_covering():
    q, kp, vp, tables, lens = make_case(1, 2, 2, 16, 8, 8, 4, [14], seed=2)
    full = paged_attention_ref(q, kp, vp, tables, lens, window=0)
    wide = paged_attention_ref(q, kp, vp, tables, lens, window=64)
    np.testing.assert_allclose(np.asarray(full), np.asarray(wide),
                               atol=1e-6, rtol=1e-6)


# ---------------------------------------------------- quantized pool gather
def _quantize_case(q, kp, vp, kv_dtype):
    from repro.kernels.paged_attention import quant

    store = quant.kv_storage_dtype(kv_dtype, q.dtype)
    kc, ks = quant.kv_quantize(kp, store)
    vc, vs = quant.kv_quantize(vp, store)
    return kc, vc, ks, vs


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
@pytest.mark.parametrize(
    "case", CASES[:2] + [LONG], ids=[str(c[:4]) for c in CASES[:2] + [LONG]]
)
def test_quantized_kernel_matches_quantized_ref(case, kv_dtype):
    """Fused in-gather dequant inside the Pallas kernel == dequantizing in
    the jnp oracle: both read the same codes + scales, so they must agree
    to kernel tolerance (the quantization error itself cancels out)."""
    B, Kv, G, hd, page, N, P, lengths = case
    q, kp, vp, tables, lens = make_case(B, Kv, G, hd, page, N, P, lengths)
    kc, vc, ks, vs = _quantize_case(q, kp, vp, kv_dtype)
    out = paged_attention(q, kc, vc, tables, lens, k_scale=ks, v_scale=vs,
                          use_kernel=True)
    ref = paged_attention_ref(q, kc, vc, tables, lens, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
    # and the quantized result is close to (but not identical with) exact
    exact = paged_attention_ref(q, kp, vp, tables, lens)
    drift = float(jnp.max(jnp.abs(ref - exact)))
    assert 0.0 < drift < 0.5, drift


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_prefill_kernel_matches_quantized_ref(kv_dtype):
    B, T, Kv, G, hd, page, N, P = 2, 4, 2, 2, 32, 8, 16, 4
    starts, qlens = [0, 5], [4, 3]
    q, kp, vp, tbl, st, ln = make_prefill_case(
        B, T, Kv, G, hd, page, N, P, starts, qlens
    )
    kc, vc, ks, vs = _quantize_case(q, kp, vp, kv_dtype)
    out = paged_prefill_attention(
        q, kc, vc, tbl, st, ln, k_scale=ks, v_scale=vs, use_kernel=True
    )
    ref = paged_prefill_attention_ref(
        q, kc, vc, tbl, st, ln, k_scale=ks, v_scale=vs
    )
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(out)[b, : qlens[b]], np.asarray(ref)[b, : qlens[b]],
            atol=2e-5, rtol=2e-5,
        )


def test_quantized_null_page_dequantizes_to_zero():
    """Zero-initialized scales make the null page read as exact zeros no
    matter what garbage codes it holds — padding masking stays intact."""
    from repro.kernels.paged_attention import quant

    q, kp, vp, tables, lens = make_case(2, 2, 2, 16, 8, 16, 4, [9, 12], seed=1)
    kc, vc, ks, vs = _quantize_case(q, kp, vp, "int8")
    kc = kc.at[0].set(127)                   # poison null-page codes
    vc = vc.at[0].set(-127)
    ks = ks.at[0].set(0.0)                   # null page: scale stays zero
    vs = vs.at[0].set(0.0)
    ref = paged_attention_ref(q, kc, vc, tables, lens, k_scale=ks, v_scale=vs)
    out = paged_attention(q, kc, vc, tables, lens, k_scale=ks, v_scale=vs,
                          use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert bool(jnp.all(jnp.isfinite(ref)))
    # dequant of zero-scale pages is exactly zero (not NaN/Inf)
    assert bool(jnp.all(quant.kv_dequantize(kc[0], ks[0]) == 0.0))


# -------------------------------------------------- chunked prefill kernel
def make_prefill_case(B, T, Kv, G, hd, page, N, P, starts, qlens, seed=0):
    """Pool + block tables covering each request's start + T positions."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, T, Kv, G, hd), jnp.float32) * (hd ** -0.5)
    kp = jnp.asarray(rng.randn(N, Kv, page, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(N, Kv, page, hd), jnp.float32)
    tables = np.zeros((B, P), np.int32)
    nxt = 1
    for b in range(B):
        n = -(-(starts[b] + T) // page)
        assert nxt + n <= N and n <= P
        tables[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return (
        q, kp, vp, jnp.asarray(tables),
        jnp.asarray(starts, jnp.int32), jnp.asarray(qlens, jnp.int32),
    )


PREFILL_CASES = [
    # (B, T, Kv, G, hd, page, N, P, starts, qlens)
    (1, 8, 1, 4, 32, 4, 24, 12, [0], [8]),       # MQA, cold chunk
    (2, 4, 2, 2, 32, 8, 16, 4, [0, 5], [4, 3]),  # GQA, offset + ragged
    (1, 8, 1, 4, 32, 4, 24, 12, [13], [6]),      # mid-prompt chunk
    (3, 4, 2, 4, 16, 8, 32, 4, [0, 9, 17], [4, 2, 1]),  # mixed depths
    # T*G > 128 starting mid-block (blocks of 32 pages), beside an inactive
    # slot (q_len 0)
    (2, 64, 1, 4, 128, 16, 100, 48, [600, 0], [64, 0]),
    (1, 256, 1, 4, 128, 16, 40, 32, [200], [200]),   # a real chunk: 1024 rows
    (1, 8, 1, 48, 128, 16, 24, 40, [290], [8]),      # MQA at G = 48
    (1, 128, 4, 4, 128, 16, 40, 32, [300], [128]),   # 2 of 4 kv heads a program
    (1, 32, 1, 48, 128, 16, 24, 24, [300], [29]),    # 1536 rows in 2 tiles
]


@pytest.mark.parametrize(
    "case", PREFILL_CASES, ids=[str(c[:4]) for c in PREFILL_CASES]
)
@pytest.mark.parametrize("window", [0, 6])
def test_prefill_kernel_matches_ref(case, window):
    B, T, Kv, G, hd, page, N, P, starts, qlens = case
    q, kp, vp, tbl, st, ln = make_prefill_case(
        B, T, Kv, G, hd, page, N, P, starts, qlens
    )
    out = paged_prefill_attention(
        q, kp, vp, tbl, st, ln, window=window, use_kernel=True
    )
    ref = paged_prefill_attention_ref(q, kp, vp, tbl, st, ln, window=window)
    for b in range(B):   # padded rows (t >= q_len) are garbage by contract
        np.testing.assert_allclose(
            np.asarray(out)[b, : qlens[b]], np.asarray(ref)[b, : qlens[b]],
            atol=2e-5, rtol=2e-5,
        )
        if qlens[b] == 0:    # an inactive slot walks nothing: zeros
            assert not np.any(np.asarray(out)[b])


def test_prefill_kernel_interpret_mode_explicit():
    q, kp, vp, tbl, st, ln = make_prefill_case(
        2, 4, 2, 2, 32, 8, 16, 4, [3, 11], [4, 2], seed=3
    )
    out = paged_prefill_attention_kernel(q, kp, vp, tbl, st, ln,
                                         interpret=True)
    ref = paged_prefill_attention_ref(q, kp, vp, tbl, st, ln)
    for b, L in enumerate([4, 2]):
        np.testing.assert_allclose(
            np.asarray(out)[b, :L], np.asarray(ref)[b, :L],
            atol=2e-5, rtol=2e-5,
        )


def test_prefill_t1_degenerates_to_decode():
    """A one-row chunk at position p is exactly a decode step at length
    p + 1 — the two oracles (and thus both kernels) must agree."""
    q, kp, vp, tbl, st, ln = make_prefill_case(
        2, 1, 2, 2, 16, 8, 16, 4, [6, 11], [1, 1], seed=7
    )
    pre = paged_prefill_attention_ref(q, kp, vp, tbl, st, ln)
    dec = paged_attention_ref(q[:, 0], kp, vp, tbl, st + 1)
    np.testing.assert_allclose(
        np.asarray(pre[:, 0]), np.asarray(dec), atol=1e-6, rtol=1e-6
    )


def test_prefill_causality_ignores_future_garbage():
    """Keys beyond each row's own position — including stale garbage in
    allocated-but-unwritten page slots — must not leak into any valid row."""
    q, kp, vp, tbl, st, ln = make_prefill_case(
        1, 4, 2, 2, 16, 8, 16, 4, [2], [4], seed=5
    )
    ref = paged_prefill_attention_ref(q, kp, vp, tbl, st, ln)
    # poison every pool position at kpos > last query position
    last = 2 + 4 - 1
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for j in range(np.asarray(tbl).shape[1]):
        pid = int(np.asarray(tbl)[0, j])
        for s in range(kp2.shape[2]):
            if j * kp2.shape[2] + s > last and pid != 0:
                kp2[pid, :, s] = 1e3
                vp2[pid, :, s] = -1e3
    out = paged_prefill_attention(
        q, jnp.asarray(kp2), jnp.asarray(vp2), tbl, st, ln, use_kernel=True
    )
    np.testing.assert_allclose(
        np.asarray(out)[0], np.asarray(ref)[0], atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
@pytest.mark.parametrize("window", [0, 6])
@pytest.mark.parametrize("T", [1, 4])
def test_dead_pages_are_never_read(T, window, kv_dtype):
    """Each slot's table holds real (non-null) pages over the whole
    horizon, and every key outside its live range — the pages past it, the
    tail of its last live page, and with a window the keys before it —
    holds NaN (int8: garbage codes under NaN scales). The valid rows match
    the oracle on the clean pool: dead pages are neither copied nor let
    into a result."""
    B, Kv, G, hd, page, P = 3, 2, 4, 128, 16, 40
    starts = np.array([0, 13, 146])
    qlens = np.array([T, T, max(T - 2, 1)])
    N = 1 + B * P
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(B, T, Kv, G, hd), jnp.float32) * hd ** -0.5
    kp = rng.randn(N, Kv, page, hd).astype(np.float32)
    vp = rng.randn(N, Kv, page, hd).astype(np.float32)
    tables = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
    pos = np.arange(P * page)
    first = starts - window + 1 if window else np.zeros(B, np.int64)
    dead = (pos[None] >= (starts + qlens)[:, None]) | (pos[None] < first[:, None])
    quant = {}
    if kv_dtype == "int8":
        kc, vc, ks, vs = _quantize_case(q, jnp.asarray(kp), jnp.asarray(vp),
                                        "int8")
        clean = (kc, vc)
        quant = dict(k_scale=ks, v_scale=vs)
        kp, vp = np.asarray(kc).copy(), np.asarray(vc).copy()
        poisoned = dict(k_scale=np.asarray(ks).copy(),
                        v_scale=np.asarray(vs).copy())
    else:
        clean = (jnp.asarray(kp.copy()), jnp.asarray(vp.copy()))
        poisoned = {}
    for b in range(B):
        for key in np.flatnonzero(dead[b]):
            pid, slot = tables[b, key // page], key % page
            if kv_dtype == "int8":
                kp[pid, :, slot], vp[pid, :, slot] = 127, -127
                for sc in poisoned.values():
                    sc[pid, :, slot] = np.nan
            else:
                kp[pid, :, slot] = vp[pid, :, slot] = np.nan
    tbl = jnp.asarray(tables)
    st, ln = jnp.asarray(starts, jnp.int32), jnp.asarray(qlens, jnp.int32)
    out = paged_prefill_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), tbl, st, ln, window=window,
        use_kernel=True, **{k: jnp.asarray(v) for k, v in poisoned.items()},
    )
    ref = paged_prefill_attention_ref(
        q, *clean, tbl, st, ln, window=window, **quant
    )
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(out)[b, : qlens[b]], np.asarray(ref)[b, : qlens[b]],
            atol=2e-5, rtol=2e-5,
        )

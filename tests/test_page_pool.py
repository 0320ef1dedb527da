"""Property-based tests for the serve page-pool allocator.

Invariants under arbitrary admit/append/fork/evict sequences: no page is
leaked or double-assigned, the null page is never handed out, the high-water
mark respects the budget (the pool raises instead of overcommitting), and
freed pages are reusable.

Quantized pools add a device-side invariant: the per-(page-slot, head) f32
scale buffers share the page id with their codes, so COW copies must move
codes + scales together and freeing a page frees its scales (the next writer
overwrites both)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import hypothesis, st

from repro.serve.pool import PagePool, PoolExhausted


def test_alloc_append_free_roundtrip():
    pool = PagePool(num_pages=9, page_size=4)
    a = pool.alloc(6)          # 2 pages
    b = pool.alloc(4)          # 1 page
    assert pool.pages_in_use == 3
    assert sorted(pool.seq_pages(a) + pool.seq_pages(b)) == [1, 2, 3]
    pool.append(a, 3)          # 6 -> 9 tokens: 3 pages
    assert len(pool.seq_pages(a)) == 3
    pool.free(a)
    assert pool.pages_in_use == 1
    c = pool.alloc(16)         # reuses a's freed pages
    assert len(pool.seq_pages(c)) == 4
    pool.check()


def test_exhaustion_raises_without_leaking():
    pool = PagePool(num_pages=4, page_size=2)   # budget 3
    a = pool.alloc(4)
    with pytest.raises(PoolExhausted):
        pool.alloc(4)
    pool.check()
    assert pool.pages_in_use == 2
    with pytest.raises(PoolExhausted):
        pool.append(a, 5)      # needs 3 more pages, 1 free
    pool.check()
    pool.free(a)
    assert pool.pages_in_use == 0
    assert pool.high_water == 2


def test_fork_shares_then_copies_on_write():
    pool = PagePool(num_pages=9, page_size=4)
    a = pool.alloc(6)          # pages [1, 2], tail half-filled
    b = pool.fork(a)
    assert pool.seq_pages(b) == pool.seq_pages(a)
    assert pool.pages_in_use == 2          # fully shared
    pool.append(b, 1)          # writes into shared partial tail -> COW
    copies = pool.drain_copies()
    assert len(copies) == 1 and copies[0][0] == pool.seq_pages(a)[-1]
    assert pool.seq_pages(b)[-1] != pool.seq_pages(a)[-1]
    assert pool.seq_pages(b)[0] == pool.seq_pages(a)[0]  # full page shared
    pool.check()
    # full tail page: fork then append allocates without copying
    c = pool.alloc(4)
    d = pool.fork(c)
    pool.append(d, 1)
    assert pool.drain_copies() == []
    assert pool.seq_pages(d)[0] == pool.seq_pages(c)[0]
    pool.check()


def test_fork_free_order_independent():
    pool = PagePool(num_pages=5, page_size=2)
    a = pool.alloc(4)
    b = pool.fork(a)
    pool.free(a)               # b still holds the pages
    assert pool.pages_in_use == 2
    pool.free(b)
    assert pool.pages_in_use == 0
    pool.check()


def test_truncate_releases_tail_pages():
    """The speculative-decoding rollback verb: shrink a reservation to the
    committed token count, releasing exactly the pages past it."""
    pool = PagePool(num_pages=9, page_size=4)
    a = pool.alloc(15)                 # 4 pages
    keep = pool.seq_pages(a)[:2]
    pool.truncate(a, 7)                # -> 2 pages
    assert pool.seq_pages(a) == keep
    assert pool.pages_in_use == 2
    pool.truncate(a, 7)                # idempotent
    pool.truncate(a, 12)               # growing is not truncate's job: no-op
    assert pool.seq_pages(a) == keep
    pool.ensure(a, 12)                 # the grow verb re-extends
    assert len(pool.seq_pages(a)) == 3
    pool.truncate(a, 0)                # floor 1 token, like alloc
    assert len(pool.seq_pages(a)) == 1
    pool.check()
    # COW safety: truncating a fork releases only the fork's refs; shared
    # pages survive for the other sequence
    b = pool.alloc(8)
    c = pool.fork(b)
    pool.truncate(c, 1)
    assert len(pool.seq_pages(b)) == 2
    pool.free(b)
    pool.free(c)
    pool.free(a)
    pool.check()
    assert pool.pages_in_use == 0


@hypothesis.given(
    st.lists(
        st.tuples(st.integers(0, 4), st.integers(1, 9)),
        min_size=1, max_size=60,
    )
)
@hypothesis.settings(max_examples=60, deadline=None)
def test_pool_invariants_under_random_ops(ops):
    """ops: (verb, amount) with verb 0=alloc 1=append 2=free 3=fork
    4=truncate (the spec-decode rollback verb); the amount doubles as the
    token count / live-sequence selector."""
    pool = PagePool(num_pages=8, page_size=3)   # budget 7
    live = []
    for verb, n in ops:
        try:
            if verb == 0:
                live.append(pool.alloc(n))
            elif verb == 1 and live:
                pool.append(live[n % len(live)], n)
            elif verb == 2 and live:
                pool.free(live.pop(n % len(live)))
            elif verb == 3 and live:
                live.append(pool.fork(live[n % len(live)]))
            elif verb == 4 and live:
                pool.truncate(live[n % len(live)], n - 1)
        except PoolExhausted:
            pass                                # refusal must not corrupt
        pool.check()
        assert pool.high_water <= pool.budget
        assert 0 <= pool.pages_in_use <= pool.budget
    for sid in live:
        pool.free(sid)
    pool.check()
    assert pool.pages_in_use == 0               # nothing leaked
    # freed pages are reusable: the whole budget is allocatable again
    full = pool.alloc(pool.budget * pool.page_size)
    assert len(pool.seq_pages(full)) == pool.budget
    pool.check()


@hypothesis.given(st.integers(1, 40), st.integers(1, 6))
@hypothesis.settings(max_examples=40, deadline=None)
def test_pages_for_matches_alloc(n_tokens, page_size):
    pool = PagePool(num_pages=64, page_size=page_size)
    sid = pool.alloc(n_tokens)
    assert len(pool.seq_pages(sid)) == pool.pages_for(n_tokens)
    assert pool.pages_for(n_tokens) * page_size >= n_tokens


# ------------------------------------------------- quantized pool state
@hypothesis.given(
    st.lists(
        st.tuples(st.integers(0, 3), st.integers(1, 9)),
        min_size=1, max_size=30,
    )
)
@hypothesis.settings(max_examples=20, deadline=None)
def test_quantized_pool_scales_track_pages(ops):
    """Device-side shadow of the allocator workload under an int8 pool: the
    scale buffers are indexed by the same page ids as the codes, so (a)
    every live sequence's pages carry exactly the scales its writer stamped,
    (b) a COW copy moves codes AND scales, and (c) freed pages' scales are
    simply overwritten by the next writer — freeing a page frees its scales.
    """
    from repro.kernels.paged_attention import quant
    from repro.models.attention import init_paged_kv_cache

    page, n_kv, hd = 3, 2, 4
    pool = PagePool(num_pages=8, page_size=page)
    cache = init_paged_kv_cache(8, page, n_kv, hd, jnp.float32,
                                kv_dtype="int8")
    assert set(cache) == {"kp", "vp", "ksc", "vsc"}
    assert cache["kp"].dtype == jnp.int8
    assert cache["ksc"].shape == (8, n_kv, page)
    expected = {}            # page id -> stamped scale value

    def stamp(sid, pages):
        """Write the constant row ``sid + 1`` into each page: absmax is
        exact so the int8 round trip is lossless and the scale is known."""
        val = float(sid % 5 + 1)
        x = jnp.full((len(pages), n_kv, page, hd), val, jnp.float32)
        codes, scales = quant.kv_quantize(x, cache["kp"].dtype)
        idx = jnp.asarray(pages, jnp.int32)
        cache["kp"] = cache["kp"].at[idx].set(codes)
        cache["ksc"] = cache["ksc"].at[idx].set(scales)
        for p in pages:
            expected[p] = val / 127.0

    def apply_copies():
        for src, dst in pool.drain_copies():   # COW: move both
            for key in ("kp", "ksc"):
                cache[key] = cache[key].at[dst].set(cache[key][src])
            expected[dst] = expected[src]

    live = {}
    for verb, n in ops:
        try:
            if verb == 0:
                sid = pool.alloc(n)
                live[sid] = None
                stamp(sid, pool.seq_pages(sid))
            elif verb == 1 and live:
                sid = list(live)[n % len(live)]
                before = set(pool.seq_pages(sid))
                pool.append(sid, n)
                apply_copies()
                stamp(sid, [p for p in pool.seq_pages(sid)
                            if p not in before])
            elif verb == 2 and live:
                sid = list(live)[n % len(live)]
                del live[sid]
                pool.free(sid)
            elif verb == 3 and live:
                sid = list(live)[n % len(live)]
                live[pool.fork(sid)] = None
        except PoolExhausted:
            # append copies a shared tail on write before it checks the
            # budget, so a failed append may leave that copy queued, as
            # the engine finds it when it drains copies after eviction
            apply_copies()
        pool.check()
        ksc = np.asarray(cache["ksc"])
        for sid in live:
            for p in pool.seq_pages(sid):
                np.testing.assert_allclose(
                    ksc[p], expected[p], rtol=1e-6,
                    err_msg=f"page {p} of sid {sid}: scales drifted",
                )
    # the null page's scales stay zero: it dequantizes to exact zeros
    assert np.all(np.asarray(cache["ksc"])[0] == 0.0)
    for sid in list(live):
        pool.free(sid)
    pool.check()
    assert pool.pages_in_use == 0


def test_kv_quant_roundtrip_error_bounds():
    """quantize -> dequantize obeys the per-row analytic bounds, and the
    per-(token, head) symmetric scheme is RMS-comparable to the blockwise
    dynamic-map reference tier (``kernels.blockwise_quant.ref``) on the
    same heavy-tailed data — both are 8-bit absmax-scaled codes."""
    from repro.kernels.blockwise_quant.ref import dequantize_ref, quantize_ref
    from repro.kernels.paged_attention import quant

    rng = np.random.RandomState(0)
    x = jnp.asarray(
        rng.randn(64, 8, 16) * np.exp(rng.randn(64, 8, 16)), jnp.float32
    )
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)

    # int8: round-to-nearest at scale = absmax/127 -> |err| <= scale/2
    c8, s8 = quant.kv_quantize(x, jnp.int8)
    err8 = jnp.abs(x - quant.kv_dequantize(c8, s8))
    assert bool(jnp.all(err8 <= s8[..., None] * 0.5 + 1e-7))

    # fp8 e4m3: 3 mantissa bits -> relative half-ulp 2^-4 of the row max
    cf, sf = quant.kv_quantize(x, jnp.float8_e4m3fn)
    errf = jnp.abs(x - quant.kv_dequantize(cf, sf))
    assert bool(jnp.all(errf <= absmax / 14.0 + 1e-7))

    # zero rows are exact (null-page semantics): scale 0, codes 0
    z = jnp.zeros((2, 3, 16), jnp.float32)
    for dt in (jnp.int8, jnp.float8_e4m3fn):
        cz, sz = quant.kv_quantize(z, dt)
        assert bool(jnp.all(sz == 0))
        assert bool(jnp.all(quant.kv_dequantize(cz, sz) == 0))

    # RMS comparability with the blockwise dynamic-map tier
    flat = x.reshape(-1)
    n = flat.shape[0] - flat.shape[0] % 256
    idx, sc = quantize_ref(flat[:n])
    err_blk = jnp.abs(flat[:n] - dequantize_ref(idx, sc))
    rms = lambda e: float(jnp.sqrt(jnp.mean(e**2)))  # noqa: E731
    ratio = rms(err8) / max(rms(err_blk), 1e-12)
    assert 0.25 < ratio < 4.0, ratio


# ------------------------------------------------- radix prefix workloads
@hypothesis.given(
    st.lists(
        st.tuples(st.integers(0, 4), st.integers(0, 2 ** 16)),
        min_size=1, max_size=80,
    )
)
@hypothesis.settings(max_examples=60, deadline=None)
def test_pool_invariants_under_radix_workload(ops):
    """Fork-heavy adopt/insert/evict/free interleavings through the prefix
    cache: no page is ever leaked or double-freed, the cache's retains and
    the sequences' refs always reconcile (``pool.check``), adopted pages
    can never be evicted out from under a live sequence, and the high-water
    mark respects the budget.

    verbs: 0 = match+adopt+extend a prompt, 1 = retire (insert prompt pages
    into the radix tree, free the sequence), 2 = free without inserting,
    3 = evict_until(n), 4 = append to a live sequence. Prompts draw from a
    3-symbol alphabet so shared prefixes (and node splits) are common.
    """
    from repro.serve.prefix import PrefixCache

    page = 2
    pool = PagePool(num_pages=12, page_size=page)   # budget 11: real pressure
    cache = PrefixCache(pool)
    live = []   # (sid, prompt)

    def mkprompt(seed):
        rng = [(seed >> (2 * i)) % 3 for i in range(8)]
        n = 3 + seed % 6
        return [1 + r for r in rng[:n]]

    for verb, arg in ops:
        try:
            if verb == 0:
                prompt = mkprompt(arg)
                C, pages = cache.match(prompt, max_tokens=len(prompt) - 1)
                sid = pool.adopt(pages, C) if C else pool.alloc(len(prompt))
                if C:
                    try:
                        pool.ensure(sid, len(prompt))
                    except PoolExhausted:
                        pool.free(sid)      # all-or-nothing admission
                        raise
                live.append((sid, prompt))
            elif verb == 1 and live:
                sid, prompt = live.pop(arg % len(live))
                n_full = len(prompt) // page
                cache.insert(prompt, pool.seq_pages(sid)[:n_full])
                pool.free(sid)
            elif verb == 2 and live:
                sid, _ = live.pop(arg % len(live))
                pool.free(sid)
            elif verb == 3:
                cache.evict_until(1 + arg % 4)
            elif verb == 4 and live:
                sid, _ = live[arg % len(live)]
                pool.append(sid, 1 + arg % 3)
        except PoolExhausted:
            pass                                # refusal must not corrupt
        pool.check()
        cache.check()
        assert pool.high_water <= pool.budget
        # a page referenced by any live sequence is never on the free list
        # (checked inside pool.check) and never evictable:
        for sid, _ in live:
            for p in pool.seq_pages(sid):
                assert pool.refcount(p) >= 1
    for sid, _ in live:
        pool.free(sid)
    pool.check()
    cache.check()
    cache.evict_until(pool.budget)
    assert pool.pages_in_use == 0               # nothing leaked
    full = pool.alloc(pool.budget * pool.page_size)
    assert len(pool.seq_pages(full)) == pool.budget
    pool.check()

"""Serve engine + serving-path fixes.

* Oracle equivalence: greedy decode reproduces the teacher-forced full-
  forward argmax token-for-token across architecture families (ring-buffer
  attention, SSM, RG-LRU, enc-dec cross-attention, vision prefix) and across
  the paged vs dense cache paths.
* Continuous batching: each request's engine output is identical to running
  that request alone (including under eviction pressure and through the
  Pallas kernel path).
* Fixes: compile-cache no-retrace regression; finfo-min vocab masking in
  ``sample_token`` over float dtypes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import hypothesis, st

from repro.configs import get_reduced
from repro.models import Runtime, forward, init_params
from repro.serve import (
    EngineConfig,
    ReplicaRouter,
    ServeEngine,
    paged_supported,
)
from repro.serve.sampling import sample_token
from repro.train.serve import generate

RT = Runtime(dtype=jnp.float32, chunk_q=32)


@pytest.fixture(scope="module")
def arch_state():
    cache = {}

    def get(name):
        if name not in cache:
            cfg = get_reduced(name)
            cache[name] = (cfg, init_params(cfg, jax.random.PRNGKey(0)))
        return cache[name]

    return get


def make_batch(cfg, B, S, key=0):
    rng = np.random.RandomState(key)
    batch = {
        "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    }
    if cfg.frontend is not None:
        batch["frontend_embeds"] = jnp.asarray(
            rng.randn(B, cfg.frontend_tokens, cfg.d_model), jnp.float32
        )
    return batch


# --------------------------------------------------- oracle equivalence
FAMILIES = [
    "granite-8b",           # dense full attention
    "gemma3-1b",            # sliding-window ring buffers
    "falcon-mamba-7b",      # recurrent SSM (dense fallback family)
    "recurrentgemma-2b",    # RG-LRU hybrid (dense fallback family)
    "seamless-m4t-medium",  # enc-dec cross-attention (dense fallback family)
    "phi-3-vision-4.2b",    # vision-prefix decode
]


@pytest.mark.parametrize("name", FAMILIES)
def test_greedy_decode_matches_teacher_forced_argmax(arch_state, name):
    """Greedy generation == argmax chain of the full (teacher-forced)
    forward at every step — validates every family's cache path."""
    cfg, params = arch_state(name)
    B, S, M = 2, 9, 5
    batch = make_batch(cfg, B, S, key=11)
    tokens, _ = generate(cfg, params, batch, RT, max_new_tokens=M)
    assert tokens.shape == (B, M)

    full = dict(batch, tokens=jnp.concatenate(
        [batch["tokens"], tokens], axis=1))
    logits, _ = forward(cfg, params, full, RT)
    off = cfg.frontend_tokens if cfg.frontend == "vision" else 0
    for i in range(M):
        expect = jnp.argmax(
            logits[:, off + S - 1 + i, : cfg.vocab_size], axis=-1
        )
        np.testing.assert_array_equal(
            np.asarray(tokens[:, i]), np.asarray(expect), err_msg=f"step {i}"
        )


@pytest.mark.parametrize("name", ["granite-8b", "gemma3-1b"])
def test_paged_path_matches_dense_path(arch_state, name):
    cfg, params = arch_state(name)
    batch = make_batch(cfg, B=2, S=10, key=3)
    dense, _ = generate(cfg, params, batch, RT, max_new_tokens=6)
    paged, stats = generate(cfg, params, batch, RT, max_new_tokens=6,
                            paged=True)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))
    assert set(stats["ttft_s"]) == {0, 1} and set(stats["kv_bytes"]) == {0, 1}


def test_paged_supported_matrix():
    assert paged_supported(get_reduced("granite-8b"))
    assert paged_supported(get_reduced("gemma3-1b"))
    assert paged_supported(get_reduced("phi-3-vision-4.2b"))
    assert not paged_supported(get_reduced("falcon-mamba-7b"))
    assert not paged_supported(get_reduced("recurrentgemma-2b"))
    assert not paged_supported(get_reduced("seamless-m4t-medium"))
    with pytest.raises(ValueError):
        ServeEngine(
            get_reduced("falcon-mamba-7b"), params=None, rt=RT, paged=True
        )


# --------------------------------------------------- continuous batching
def _run_alone(cfg, params, prompt, max_new):
    out, _ = generate(
        cfg, params, {"tokens": jnp.asarray(prompt[None])}, RT, max_new
    )
    return np.asarray(out[0])


def test_continuous_batching_matches_alone(arch_state):
    """Variable-length staggered requests through 2 slots: every request's
    output must equal its isolated run, and the pool must drain."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(0, cfg.vocab_size, (s,)).astype(np.int32)
        for s in (5, 11, 17, 8)
    ]
    max_news = [9, 4, 12, 7]
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=2, page_size=8, num_pages=33, max_len=64,
                     inner_steps=4),
    )
    rids = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
    out = eng.run()
    for rid, p, m in zip(rids, prompts, max_news):
        assert out[rid].shape == (m,)
        np.testing.assert_array_equal(
            out[rid], _run_alone(cfg, params, p, m), err_msg=f"rid={rid}"
        )
    eng.pool.check()
    assert eng.pool.pages_in_use == 0
    assert set(eng.stats["ttft_s"]) == set(rids)
    assert all(b > 0 for b in eng.stats["kv_bytes"].values())


def test_engine_sliding_window_family(arch_state):
    cfg, params = arch_state("gemma3-1b")
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (s,)).astype(np.int32)
               for s in (7, 13)]
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=2, page_size=8, num_pages=33, max_len=64,
                     inner_steps=3),
    )
    rids = [eng.submit(p, 6) for p in prompts]
    out = eng.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(out[rid], _run_alone(cfg, params, p, 6))


def test_engine_eviction_under_pressure_stays_exact(arch_state):
    """Optimistic admission: both requests start at one page and grow past
    the combined budget, so the engine must preempt the YOUNGEST
    (evict+requeue, FIFO fairness) and still produce exact outputs."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
               for _ in range(2)]
    max_news = [24, 16]
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=2, page_size=4, num_pages=10, max_len=48,
                     inner_steps=4, policy="optimistic"),
    )
    rids = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
    out = eng.run()
    assert eng.stats.get("evictions", 0) > 0
    for rid, p, m in zip(rids, prompts, max_news):
        np.testing.assert_array_equal(out[rid], _run_alone(cfg, params, p, m))
    eng.pool.check()
    assert eng.pool.pages_in_use == 0


def test_engine_bucketed_prefill_exact_and_bounded_compiles(arch_state):
    """prefill_bucket pads prompts to a shared shape (bounding XLA prefill
    compiles to max_len/bucket programs) without changing any output token."""
    from repro.serve import dense

    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cfg.vocab_size, (s,)).astype(np.int32)
               for s in (3, 6, 5, 7)]          # all bucket up to length 8
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=2, page_size=8, num_pages=33, max_len=64,
                     inner_steps=4, prefill_bucket=8),
    )
    before = dense.CACHE_BUILDS
    rids = [eng.submit(p, 5) for p in prompts]
    out = eng.run()
    # 4 distinct prompt lengths share ONE bucketed prefill program
    assert dense.CACHE_BUILDS - before == 1
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(out[rid], _run_alone(cfg, params, p, 5))


@pytest.mark.parametrize("n_kv", [1, 2, 4])
def test_engine_bucketed_prefill_exact_across_head_layouts(n_kv):
    """Bucketed-prefill exactness is head-layout-agnostic: the causal mask
    hides pad positions identically for MQA (kv=1), GQA (kv=2, groups of
    2), and MHA (kv=4). Each layout's bucketed engine output must equal its
    exact-shape alone run."""
    cfg = get_reduced("granite-8b", n_kv_heads=n_kv)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cfg.vocab_size, (s,)).astype(np.int32)
               for s in (3, 6, 5)]
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=2, page_size=8, num_pages=33, max_len=64,
                     inner_steps=4, prefill_bucket=8),
    )
    rids = [eng.submit(p, 5) for p in prompts]
    out = eng.run()
    for rid, p in zip(rids, prompts):
        tokens, _ = generate(
            cfg, params, {"tokens": jnp.asarray(p[None])}, RT, 5
        )
        np.testing.assert_array_equal(
            out[rid], np.asarray(tokens[0]), err_msg=f"n_kv={n_kv}"
        )


def test_engine_warns_on_moe_bucketed_or_chunked_prefill(arch_state):
    """The documented fallback: MoE expert capacity counts pad/chunk
    tokens, so bucketed / chunked prefill is not guaranteed token-exact
    for MoE families — the engine says so instead of silently differing."""
    cfg, params = arch_state("qwen3-moe-30b-a3b")
    with pytest.warns(UserWarning, match="expert capacity"):
        ServeEngine(
            cfg, params, RT,
            EngineConfig(max_slots=1, page_size=8, num_pages=17, max_len=32,
                         prefill_bucket=8),
        )
    with pytest.warns(UserWarning, match="expert capacity"):
        ServeEngine(
            cfg, params, RT,
            EngineConfig(max_slots=1, page_size=8, num_pages=17, max_len=32,
                         prefill_chunk=4),
        )
    # no warning for exact-shape non-chunked serving
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        ServeEngine(
            cfg, params, RT,
            EngineConfig(max_slots=1, page_size=8, num_pages=17, max_len=32),
        )


def test_engine_bucketed_prefill_exact_past_sliding_window(arch_state):
    """Regression: right-padding a prompt past a local layer's window must
    not ring-evict real in-window tokens from the prefill cache — the
    engine prefills with full (un-windowed) caches for the page pool."""
    cfg, params = arch_state("gemma3-1b")
    assert cfg.sliding_window == 64
    rng = np.random.RandomState(13)
    prompt = rng.randint(0, cfg.vocab_size, (66,)).astype(np.int32)
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=1, page_size=16, num_pages=13, max_len=96,
                     inner_steps=3, prefill_bucket=16),  # pads 66 -> 80 > 64
    )
    rid = eng.submit(prompt, 4)
    out = eng.run()
    np.testing.assert_array_equal(out[rid], _run_alone(cfg, params, prompt, 4))


def test_engine_pallas_kernel_path(arch_state):
    """End-to-end decode through the Pallas paged kernel (interpret mode on
    CPU) must match the jnp-oracle engine path."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    outs = {}
    for use_kernel in (False, True):
        eng = ServeEngine(
            cfg, params, RT,
            EngineConfig(max_slots=1, page_size=8, num_pages=9, max_len=16,
                         inner_steps=2, use_kernel=use_kernel),
        )
        rid = eng.submit(prompt, 3)
        outs[use_kernel] = eng.run()[rid]
    np.testing.assert_array_equal(outs[True], outs[False])


def test_engine_counts_paged_kv_walk(arch_state):
    """The engine's count of the KV blocks the paged kernel walks, against
    a hand count. granite-8b reduced: 2 layers, G 4, hd 64; page 8 over a
    64-page horizon. Prefill chunks (256 rows x G 4 = 1024 score rows)
    walk blocks of 128 keys, 4 to the horizon; decode (4 rows) one block
    of 512 keys, the whole horizon.

    tick 1: A's chunk [0, 256): 2 blocks; horizon 4
    tick 2: A's chunk [256, 300): 3 blocks; horizon 4
    tick 3: B's chunk [0, 10): 1 block; horizon 4; decode steps at 300,
            301 for A (B inactive): 1 + 1 blocks; horizon 2 x 2 slots
    tick 4: decode at 10, 11 for B: 1 + 1; horizon 2 x 2
    tick 5: decode at 12 for B (its last token): 1; horizon 2 x 2
    Per layer 11 blocks walked of 24, times 2 layers."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(8)
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=2, page_size=8, num_pages=129, max_len=512,
                     inner_steps=2, prefill_chunk=256, use_kernel=True),
    )
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (300, 10)]
    a, b = eng.submit(prompts[0], 3), eng.submit(prompts[1], 4)
    ticks = 0
    while eng.busy:
        eng.step()
        ticks += 1
    out = eng.run_finalize()
    assert ticks == 5
    np.testing.assert_array_equal(out[a], _run_alone(cfg, params, prompts[0], 3))
    np.testing.assert_array_equal(out[b], _run_alone(cfg, params, prompts[1], 4))
    assert eng.stats["paged_kv_blocks_walked"] == 22
    assert eng.stats["paged_kv_blocks_horizon"] == 48


def test_engine_counts_paged_kv_walk_under_spec(arch_state):
    """Speculative ticks: one (k+1)-row verify call a tick over both
    slots, of which one is seated. A 64-token horizon is one block, so a
    tick walks 1 block a layer of the 2 a full walk covers (2 layers)."""
    cfg, params = arch_state("granite-8b")
    prompt = np.random.RandomState(9).randint(0, cfg.vocab_size, (10,))
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=2, page_size=8, num_pages=33, max_len=64,
                     inner_steps=2, spec_tokens=3, use_kernel=True),
    )
    rid = eng.submit(prompt.astype(np.int32), 6)
    ticks = 0
    while eng.busy:
        eng.step()
        ticks += 1
    out = eng.run_finalize()
    np.testing.assert_array_equal(out[rid], _run_alone(cfg, params, prompt, 6))
    assert eng.stats["paged_kv_blocks_walked"] == 2 * ticks
    assert eng.stats["paged_kv_blocks_horizon"] == 4 * ticks


def test_engine_dense_fallback_family(arch_state):
    cfg, params = arch_state("falcon-mamba-7b")
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
               for _ in range(2)]
    eng = ServeEngine(cfg, params, RT, EngineConfig(max_slots=2))
    assert not eng.paged
    rids = [eng.submit(p, 5) for p in prompts]
    out = eng.run()
    batch = {"tokens": jnp.asarray(np.stack(prompts))}
    expect, _ = generate(cfg, params, batch, RT, 5)
    for b, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], np.asarray(expect[b]))
    assert set(eng.stats["ttft_s"]) == set(rids)


def test_engine_reusable_across_runs(arch_state):
    """submit()/run() a second time on the same engine: only the new
    request's output is returned and per-run stats stay sane."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
               for _ in range(2)]
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=2, page_size=8, num_pages=17, max_len=32,
                     inner_steps=4),
    )
    r0 = eng.submit(prompts[0], 5)
    out0 = eng.run()
    assert set(out0) == {r0}
    r1 = eng.submit(prompts[1], 5)
    out1 = eng.run()
    assert set(out1) == {r1}
    assert eng.stats["decode_tokens"] == 4 and eng.stats["tokens_per_s"] > 0
    np.testing.assert_array_equal(
        out1[r1], _run_alone(cfg, params, prompts[1], 5)
    )
    eng.pool.check()


def test_engine_rejects_oversized_request(arch_state):
    cfg, params = arch_state("granite-8b")
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=1, page_size=4, num_pages=5, max_len=64),
    )
    with pytest.raises(ValueError):
        eng.submit(np.zeros(40, np.int32), 20)   # > pool budget


# ----------------------------------------------------- quantized KV pool
PAGED_FAMILIES = ["granite-8b", "gemma3-1b", "phi-3-vision-4.2b"]
DENSE_FAMILIES = ["falcon-mamba-7b", "recurrentgemma-2b", "seamless-m4t-medium"]


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
@pytest.mark.parametrize("name", PAGED_FAMILIES)
def test_quantized_pool_batched_equals_alone(arch_state, name, kv_dtype):
    """Quantize-once-per-write keeps pool bytes independent of batch
    composition, so the engine's batched==alone token identity must hold at
    every kv_dtype (the quantized trajectory may differ from bf16's — the
    guarantee is internal consistency at a FIXED pool dtype)."""
    cfg, params = arch_state(name)
    rng = np.random.RandomState(31)
    prompts = [rng.randint(0, cfg.vocab_size, (s,)).astype(np.int32)
               for s in (5, 11, 8)]
    fes = [
        rng.randn(cfg.frontend_tokens, cfg.d_model).astype(np.float32)
        if cfg.frontend is not None else None
        for _ in prompts
    ]

    def run(reqs, fe_list, slots):
        eng = ServeEngine(
            cfg, params, RT,
            EngineConfig(max_slots=slots, page_size=8, num_pages=33,
                         max_len=64, inner_steps=4, kv_dtype=kv_dtype),
        )
        rids = [eng.submit(p, 6, frontend_embeds=fe)
                for p, fe in zip(reqs, fe_list)]
        out = eng.run()
        eng.pool.check()
        assert eng.pool.pages_in_use == 0
        return [out[r] for r in rids]

    batched = run(prompts, fes, slots=2)
    for i, (p, fe) in enumerate(zip(prompts, fes)):
        alone = run([p], [fe], slots=1)[0]
        np.testing.assert_array_equal(
            batched[i], alone, err_msg=f"{name} {kv_dtype} req {i}"
        )


def _paged_step_logits(cfg, params, prompt, kv_dtype, steps, teacher=None):
    """Admission-path harness: prefill -> write_prefill_to_pool -> paged
    decode steps, returning per-step logits (teacher-forced when given)."""
    from repro.models import init_paged_state, paged_forward, prefill
    from repro.models.stack import write_prefill_to_pool

    rt = RT.replace(kv_dtype=kv_dtype)
    page = 8
    prompt_total = len(prompt) + (
        cfg.frontend_tokens if cfg.frontend == "vision" else 0
    )
    max_len = -(-(prompt_total + steps) // page) * page
    P = max_len // page
    state = init_paged_state(
        cfg, 1, rt, num_pages=P + 1, page_size=page, max_len=max_len
    )
    table_row = jnp.arange(1, P + 1, dtype=jnp.int32)
    batch = {"tokens": jnp.asarray(prompt[None])}
    if cfg.frontend is not None:
        rngf = np.random.RandomState(1)
        batch["frontend_embeds"] = jnp.asarray(
            rngf.randn(1, cfg.frontend_tokens, cfg.d_model), jnp.float32
        )
    logits, pstate = prefill(
        cfg, params, batch, rt, max_len=prompt_total + steps, full_cache=True
    )
    state["caches"] = write_prefill_to_pool(
        state["caches"], pstate["caches"], table_row, page
    )
    state["tables"] = table_row[None]
    state["lengths"] = jnp.asarray([prompt_total], jnp.int32)
    logs, toks = [logits[0]], []
    for i in range(steps):
        tok = (int(jnp.argmax(logs[-1][: cfg.vocab_size]))
               if teacher is None else teacher[i])
        toks.append(tok)
        lg, state["caches"] = paged_forward(
            cfg, params, state["caches"], state["tables"],
            jnp.asarray([[tok]], jnp.int32), state["lengths"],
            jnp.ones((1,), jnp.int32), rt, max_len,
        )
        state["lengths"] = state["lengths"] + 1
        logs.append(lg[0])
    return logs, toks


@pytest.mark.parametrize("kv_dtype,rel_tol", [("int8", 0.04), ("fp8", 0.15)])
@pytest.mark.parametrize("name", PAGED_FAMILIES)
def test_quantized_pool_logit_error_within_tolerance(
    arch_state, name, kv_dtype, rel_tol
):
    """Teacher-forced decode over a quantized pool stays within a measured
    max-logit-error tolerance of the native pool (measured ~0.012 relative
    for int8, ~0.045 for fp8 across these families; asserted at ~3x margin).
    The prefill logits themselves are quantization-free (native ring cache),
    so step 0 must be exact — only pool-reading decode steps may drift."""
    cfg, params = arch_state(name)
    rng = np.random.RandomState(13)
    prompt = rng.randint(0, cfg.vocab_size, (11,)).astype(np.int32)
    ref, toks = _paged_step_logits(cfg, params, prompt, "", steps=5)
    got, _ = _paged_step_logits(
        cfg, params, prompt, kv_dtype, steps=5, teacher=toks
    )
    np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(got[0]))
    scale = max(float(jnp.max(jnp.abs(lg))) for lg in ref)
    err = max(
        float(jnp.max(jnp.abs(a - b))) for a, b in zip(ref[1:], got[1:])
    )
    assert 0.0 < err <= rel_tol * scale, (err, scale)


@pytest.mark.parametrize("name", DENSE_FAMILIES)
def test_quantized_kv_dtype_noop_on_dense_fallback(arch_state, name):
    """Dense-fallback families never touch the page pool: a kv_dtype on the
    engine config must change nothing (dense compiles are shared via
    ``serve.dense._dense_rt`` stripping the field before cache keying)."""
    cfg, params = arch_state(name)
    rng = np.random.RandomState(37)
    prompts = [rng.randint(0, cfg.vocab_size, (7,)).astype(np.int32)
               for _ in range(2)]
    fe_list = [
        rng.randn(cfg.frontend_tokens, cfg.d_model).astype(np.float32)
        if (cfg.frontend is not None or cfg.is_encdec) else None
        for _ in prompts
    ]
    outs = {}
    for kv_dtype in ("", "int8"):
        eng = ServeEngine(
            cfg, params, RT, EngineConfig(max_slots=2, kv_dtype=kv_dtype)
        )
        assert not eng.paged
        rids = [eng.submit(p, 5, frontend_embeds=fe)
                for p, fe in zip(prompts, fe_list)]
        out = eng.run()
        outs[kv_dtype] = [out[r] for r in rids]
    for a, b in zip(outs[""], outs["int8"]):
        np.testing.assert_array_equal(a, b)


# ------------------------------------- latency accounting (TTFT origin)
def _submit_then_wait(eng, prompts, max_new, wait_s=0.05):
    """Submit everything, sit in the queue for wait_s, then drain. With
    TTFT measured from SUBMIT (the fix), every request's TTFT must include
    that wait; the old admit-origin accounting would report only prefill."""
    import time as _time

    rids = [eng.submit(p, max_new) for p in prompts]
    _time.sleep(wait_s)
    out = eng.run()
    return rids, out


@pytest.mark.parametrize("variant", ["legacy", "chunked", "dense"])
def test_ttft_origin_is_submit_on_every_path(arch_state, variant):
    """Regression for the TTFT accounting bug: the legacy whole-prompt
    prefill, the chunked-prefill path, and the dense fallback all timed
    TTFT from admission, hiding queue wait. All three must now span
    submit -> first token (>= the induced queue wait) and keep prefill
    compute in the separate prefill_s."""
    name = "falcon-mamba-7b" if variant == "dense" else "granite-8b"
    cfg, params = arch_state(name)
    rng = np.random.RandomState(23)
    prompts = [rng.randint(0, cfg.vocab_size, (s,)).astype(np.int32)
               for s in ((6, 6) if variant == "dense" else (6, 11))]
    if variant == "legacy":
        ecfg = EngineConfig(max_slots=2, page_size=8, num_pages=17,
                            max_len=32, inner_steps=4)
    elif variant == "chunked":
        ecfg = EngineConfig(max_slots=2, page_size=8, num_pages=17,
                            max_len=32, inner_steps=4, prefix_cache=True,
                            prefill_chunk=8)
    else:
        ecfg = EngineConfig(max_slots=2)
    eng = ServeEngine(cfg, params, RT, ecfg)
    rids, _ = _submit_then_wait(eng, prompts, 4, wait_s=0.05)
    s = eng.stats
    for rid in rids:
        assert s["ttft_s"][rid] >= 0.05, (variant, rid, s["ttft_s"])
        assert 0 < s["prefill_s"][rid] < s["ttft_s"][rid]


def test_ttft_includes_queue_wait_ordering(arch_state):
    """One slot, co-submitted requests: each later admission's TTFT must
    grow by the time spent waiting behind its predecessors."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(29)
    prompts = [rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
               for _ in range(3)]
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=1, page_size=8, num_pages=9, max_len=16,
                     inner_steps=4),
    )
    rids, _ = _submit_then_wait(eng, prompts, 4, wait_s=0.0)
    ttfts = [eng.stats["ttft_s"][r] for r in rids]
    assert ttfts[0] < ttfts[1] < ttfts[2], ttfts


def test_preempt_readmit_ttft_spans_original_submit(arch_state):
    """Preemption pressure driven through the external step() loop: the
    evicted-and-readmitted request's recomputed TTFT still originates at
    its original submit (>= the pre-run queue wait), and outputs stay
    exact."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
               for _ in range(2)]
    max_news = [24, 16]
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=2, page_size=4, num_pages=10, max_len=48,
                     inner_steps=4, policy="optimistic"),
    )
    import time as _time

    rids = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
    _time.sleep(0.05)
    eng.run_begin()
    steps = 0
    while eng.busy:
        assert eng.step()["busy"]
        steps += 1
        assert steps < 200
    out = eng.run_finalize()
    assert eng.stats.get("evictions", 0) > 0
    for rid, p, m in zip(rids, prompts, max_news):
        np.testing.assert_array_equal(out[rid], _run_alone(cfg, params, p, m))
        assert eng.stats["ttft_s"][rid] >= 0.05
    eng.pool.check()


def test_engine_per_run_stats_are_per_run(arch_state):
    """A second submit/run cycle reports ITS OWN completion count and mean
    TTFT — regression for readers that averaged the accumulated per-rid
    ttft_s dict across runs."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
               for _ in range(3)]
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=2, page_size=8, num_pages=17, max_len=32,
                     inner_steps=4),
    )
    r0, r1 = eng.submit(prompts[0], 5), eng.submit(prompts[1], 5)
    eng.run()
    assert eng.stats["run_completed"] == 2
    r2 = eng.submit(prompts[2], 5)
    eng.run()
    s = eng.stats
    assert s["run_completed"] == 1
    assert s["decode_tokens"] == 4 and s["tokens_per_s"] > 0
    # the run mean covers ONLY this run's rid, not the accumulated dict
    assert s["run_mean_ttft_s"] == pytest.approx(s["ttft_s"][r2])
    assert len(s["ttft_s"]) == 3      # the dict does accumulate (by design)


def test_capacity_budget_never_overspends(arch_state):
    """Regression: the null page was not charged, so num_pages * page_bytes
    could exceed pool_bytes by one page. The sized pool (null page
    included) must now fit the budget whenever the budget can hold at
    least one usable page — and the Capacity result's own byte accounting
    must agree with the pool pricing rule."""
    from repro.serve.pool import kv_page_bytes

    cfg, _ = arch_state("granite-8b")
    page = 8
    page_b = kv_page_bytes(page, cfg.n_kv_heads, cfg.head_dim,
                           cfg.n_layers, "bf16")
    pages_per_req = 40 // page                 # horizon 24+12 -> max_len 40
    # smallest budget that holds one request + the null page, then larger
    # ones; below that floor budget sizing still returns 1 slot by
    # design (documented), so the no-overspend contract starts here
    floor = (1 + pages_per_req) * page_b
    for budget in (floor, 150_000, 200_000, 400_000, 1_000_000):
        cap = EngineConfig.capacity(
            24, 12, pool_bytes=budget, cfg=cfg, page_size=page,
            kv_dtype="bf16",
        )
        assert cap.page_bytes == page_b
        assert cap.pool_bytes == cap.num_pages * page_b <= budget, (
            budget, cap.num_pages,
        )
        assert cap.num_pages >= 1 + pages_per_req
        assert cap.pages_per_request == pages_per_req
        ecfg = cap.engine(inner_steps=4)
        assert (ecfg.max_slots, ecfg.num_pages, ecfg.kv_dtype) == (
            cap.slots, cap.num_pages, "bf16",
        )


def test_capacity_api_validation(arch_state):
    cfg, _ = arch_state("granite-8b")
    with pytest.raises(ValueError, match="exactly one"):
        EngineConfig.capacity(24, 12)
    with pytest.raises(ValueError, match="exactly one"):
        EngineConfig.capacity(24, 12, slots=2, pool_bytes=10**6, cfg=cfg)
    with pytest.raises(ValueError, match="needs cfg"):
        EngineConfig.capacity(24, 12, pool_bytes=10**6)
    # slots mode without cfg: geometry exact, byte fields report 0
    cap = EngineConfig.capacity(24, 12, slots=3, page_size=8, headroom=2.0)
    assert cap.max_len == 40 and cap.pages_per_request == 5
    assert cap.num_pages == 1 + 3 * 5 * 2
    assert cap.bytes_per_token == cap.page_bytes == cap.pool_bytes == 0


def test_replicated_submit_is_transactional(arch_state):
    """Regression: ReplicaRouter.route() was committed before the inner
    submit could raise, leaking a phantom request onto the replica's load.
    An oversized submit must leave router counts AND rid numbering
    untouched, and the engine must keep serving afterwards."""
    from repro.serve import ReplicatedServeEngine

    cfg, params = arch_state("granite-8b")
    ecfg = EngineConfig(max_slots=1, page_size=4, num_pages=5, max_len=64,
                        inner_steps=4)
    eng = ReplicatedServeEngine(cfg, params, RT, ecfg, mesh=None)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(40, np.int32), 20)    # > pool budget
    assert eng.router.routed == [0]
    assert eng._next_rid == 0
    rng = np.random.RandomState(13)
    prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    rid = eng.submit(prompt, 4)
    out = eng.run()
    np.testing.assert_array_equal(out[rid], _run_alone(cfg, params, prompt, 4))


# ------------------------------------------------------- sharded serving
def test_replica_router_least_loaded_deterministic():
    """Least-loaded routing over caller-supplied loads, lowest index on
    ties; routed counts accumulate per replica."""
    r = ReplicaRouter(3)
    assert r.route([0, 0, 0]) == 0        # all tied -> lowest index
    assert r.route([100, 0, 0]) == 1
    assert r.route([100, 10, 0]) == 2
    assert r.route([100, 10, 10]) == 1    # 1 and 2 tied -> lowest index
    assert r.route([100, 15, 10]) == 2
    assert r.route([0, 15, 11]) == 0      # drained replica is emptiest
    assert r.routed == [2, 2, 2]


def test_engine_outstanding_tokens_tracks_queue_and_pool(arch_state):
    """The load measure the router balances on: queued tokens before run,
    zero after the pool drains."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(17)
    prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    eng = ServeEngine(
        cfg, params, RT,
        EngineConfig(max_slots=1, page_size=8, num_pages=9, max_len=16,
                     inner_steps=2),
    )
    assert eng.outstanding_tokens == 0
    eng.submit(prompt, 4)
    assert eng.outstanding_tokens == len(prompt) + 4
    eng.run()
    assert eng.outstanding_tokens == 0


def test_engine_trivial_mesh_matches_unsharded(arch_state):
    """A 1x1 mesh exercises the whole sharded code path (placement, specs,
    shard_map guards) on one device and must change nothing."""
    import jax as _jax

    cfg, params = arch_state("granite-8b")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.RandomState(21)
    prompt = rng.randint(0, cfg.vocab_size, (7,)).astype(np.int32)
    ecfg = EngineConfig(max_slots=2, page_size=8, num_pages=17, max_len=32,
                        inner_steps=4)
    outs = {}
    for key, rt in (("plain", RT), ("mesh", RT.replace(mesh=mesh))):
        eng = ServeEngine(cfg, params, rt, ecfg)
        rid = eng.submit(prompt, 6)
        outs[key] = eng.run()[rid]
        assert eng.kv_pool_bytes_per_device() > 0
    np.testing.assert_array_equal(outs["plain"], outs["mesh"])


# ----------------------------------------------------- retrace regression
def test_generate_does_not_retrace_on_same_shapes(arch_state):
    from repro.serve import dense

    cfg, params = arch_state("granite-8b")
    batch = make_batch(cfg, B=2, S=19, key=8)   # unique shape for this test
    before = dense.CACHE_BUILDS
    generate(cfg, params, batch, RT, max_new_tokens=4)
    cold = dense.CACHE_BUILDS - before
    assert cold == 2                             # prefill + decode loop
    generate(cfg, params, batch, RT, max_new_tokens=4)
    assert dense.CACHE_BUILDS - before == cold   # cache hit: no rebuild

    total = 19 + 4
    bkey = dense.batch_shape_key(batch)
    prefill_fn = dense.compiled_prefill(cfg, RT, bkey, total)
    loop_fn = dense.compiled_decode_loop(cfg, RT, bkey, total, 4, 0.0)
    for fn in (prefill_fn, loop_fn):             # jax.jit miss counters
        if hasattr(fn, "_cache_size"):
            assert fn._cache_size() == 1, "second call retraced"


# ------------------------------------------------------- sample_token fix
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_sample_token_finfo_masking_over_dtypes(dtype):
    """Padded-vocab masking must use the dtype's finfo min — a hard-coded
    -1e30 overflows fp16 (and exceeds bf16 resolution tricks)."""
    vocab, padded = 5, 8
    logits = jnp.full((2, padded), 10.0, dtype)
    logits = logits.at[:, vocab:].set(20.0)      # padding ids look best
    tok = sample_token(logits, jax.random.PRNGKey(0), 0.0, vocab)
    assert np.asarray(tok).max() < vocab
    for seed in range(5):
        tok = sample_token(logits, jax.random.PRNGKey(seed), 1.0, vocab)
        assert np.asarray(tok).max() < vocab, "sampled a padded id"
    masked = jnp.where(
        jnp.arange(padded) < vocab, logits, jnp.finfo(dtype).min
    )
    assert bool(jnp.all(jnp.isfinite(masked) | (masked == jnp.finfo(dtype).min)))


@hypothesis.given(st.integers(0, 2**31 - 1))
@hypothesis.settings(max_examples=25, deadline=None)
def test_zero_temperature_equals_argmax(seed):
    rng = np.random.RandomState(seed % (2**31 - 1))
    vocab = 11
    logits = jnp.asarray(rng.randn(3, 16), jnp.float32)
    tok = sample_token(logits, jax.random.PRNGKey(seed), 0.0, vocab)
    expect = jnp.argmax(logits[:, :vocab], axis=-1)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(expect))

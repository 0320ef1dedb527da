"""Speculative decoding over the paged engine.

* Decode step: the T=1 call of the one paged forward (``paged_forward``)
  agrees with an independent path, the dense ring-buffer ``decode_step``
  after the same prefill — argmax exact, logits within float noise on
  native pools and within the quantized-pool tolerances on int8/fp8.
* Verify: at T=k the batched rows reproduce k sequential decode steps up to
  argmax (token-exact); raw logits drift ~1e-6 from XLA's row-count-
  dependent GEMM accumulation order, so the float check is a tight
  allclose, not bitwise. Token identity of the committed stream is what
  the engine guarantee rests on, and that is exact.
* Engine: spec-on greedy output is token-identical to spec-off and to
  each request alone, for both drafter kinds (ngram and paired-model,
  including the self-draft full-accept extreme), under optimistic-policy
  eviction with ``PagePool.truncate`` rollback, and mixed with the
  prefix cache + chunked prefill (ngram only).
* Drafting never changes tokens, only speed — so every identity test
  doubles as a rejection-rollback test wherever acceptance < 1.
* Config gating: the ValueErrors that keep unsupported mode combinations
  out of ``ServeEngine.__init__`` (dense-fallback families among them —
  which is how the non-paged half of the family matrix is covered here).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import reduced
from repro.models import (
    Runtime,
    decode_step,
    init_paged_state,
    init_params,
    paged_forward,
    prefill,
)
from repro.models.stack import write_prefill_to_pool
from repro.serve import EngineConfig, ServeEngine
from repro.serve.spec import ngram_draft, paired_drafter_cfg
from repro.train.serve import generate

RT = Runtime(dtype=jnp.float32, chunk_q=32)

PAGED_FAMILIES = ["granite-8b", "gemma3-1b", "phi-3-vision-4.2b"]


@pytest.fixture(scope="module")
def arch_state():
    cache = {}

    def get(name):
        if name not in cache:
            cfg = get_reduced(name)
            cache[name] = (cfg, init_params(cfg, jax.random.PRNGKey(0)))
        return cache[name]

    return get


# ------------------------------------------------ verify-pass identity
def _prefilled_state(cfg, params, prompt, kv_dtype, horizon):
    """Paged state with the prompt written to the pool (the admission
    path: prefill -> write_prefill_to_pool), plus the pending token and
    the dense ring-buffer state the same prefill left."""
    rt = RT.replace(kv_dtype=kv_dtype)
    page = 8
    prompt_total = len(prompt) + (
        cfg.frontend_tokens if cfg.frontend == "vision" else 0
    )
    max_len = -(-(prompt_total + horizon) // page) * page
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
        cfg, params, batch, rt, max_len=prompt_total + horizon,
        full_cache=True,
    )
    state["caches"] = write_prefill_to_pool(
        state["caches"], pstate["caches"], table_row, page
    )
    state["tables"] = table_row[None]
    state["lengths"] = jnp.asarray([prompt_total], jnp.int32)
    tok0 = int(jnp.argmax(logits[0, : cfg.vocab_size]))
    return state, tok0, rt, max_len, pstate


def _decode_step(cfg, params, state, tok, rt, max_len):
    """One decode step of every slot: the T=1 paged forward, then the
    caller's ``lengths`` advance."""
    lg, caches = paged_forward(
        cfg, params, state["caches"], state["tables"],
        jnp.asarray(tok, jnp.int32)[:, None], state["lengths"],
        jnp.ones_like(state["lengths"]), rt, max_len,
    )
    return lg, dict(state, caches=caches, lengths=state["lengths"] + 1)


# max logit error over the largest dense logit, as held by
# test_serve_engine.py::test_quantized_pool_logit_error_within_tolerance
QUANT_REL_TOL = {"int8": 0.04, "fp8": 0.15}


@pytest.mark.parametrize("kv_dtype", ["", "int8", "fp8"])
@pytest.mark.parametrize("name", PAGED_FAMILIES)
def test_verify_at_t1_is_decode_step_bitwise(arch_state, name, kv_dtype):
    """The T=1 paged forward is one decode step, checked against an
    independent path: the dense ring-buffer ``decode_step`` after the same
    prefill. Argmax exact everywhere; on native pools the logits, and the
    K/V rows the step wrote to the pool, match the dense step within
    float noise (not bitwise: the two paths accumulate differently); on
    quantized pools the logit error stays within the quantized-pool
    tolerance. This is the base case of the spec-tick determinism
    argument — a draft-free tick is an ordinary decode step."""
    cfg, params = arch_state(name)
    rng = np.random.RandomState(13)
    prompt = rng.randint(0, cfg.vocab_size, (11,)).astype(np.int32)
    state, tok0, rt, max_len, dense = _prefilled_state(
        cfg, params, prompt, kv_dtype, horizon=8
    )

    lg_p, st_p = _decode_step(cfg, params, state, [tok0], rt, max_len)
    lg_d, st_d = decode_step(
        cfg, params, dense, jnp.asarray([tok0], jnp.int32), RT, max_len
    )
    lg_p, lg_d = np.asarray(lg_p[0]), np.asarray(lg_d[0])
    V = cfg.vocab_size
    assert int(np.argmax(lg_p[:V])) == int(np.argmax(lg_d[:V]))
    if not kv_dtype:
        np.testing.assert_allclose(lg_p, lg_d, rtol=1e-4, atol=1e-4)
        # the new token's K/V row sits in its block-table page
        pos = int(state["lengths"][0])
        page = st_p["caches"][0]["p0"]["kp"].shape[-2]
        pid = int(state["tables"][0, pos // page])
        for seg_p, seg_d in zip(st_p["caches"], st_d["caches"]):
            for key, pool in seg_p.items():
                for pk, dk in (("kp", "k"), ("vp", "v")):
                    np.testing.assert_allclose(
                        np.asarray(pool[pk][:, pid, :, pos % page]),
                        np.asarray(seg_d[key][dk][:, 0, pos]),
                        rtol=1e-4, atol=1e-4, err_msg=f"{key} {pk}",
                    )
    else:
        err = float(np.max(np.abs(lg_p - lg_d)))
        scale = float(np.max(np.abs(lg_d)))
        assert err <= QUANT_REL_TOL[kv_dtype] * scale, (err, scale)


@pytest.mark.parametrize(
    "name,kv_dtype",
    [(n, "") for n in PAGED_FAMILIES]
    + [("granite-8b", "int8"), ("granite-8b", "fp8")],
)
def test_verify_at_tk_matches_sequential_decode(arch_state, name, kv_dtype):
    """One T=k verify pass over the target's own greedy chain reproduces
    k sequential decode steps: argmax token-exact at every row (the
    committed stream), logits within batched-GEMM accumulation noise."""
    cfg, params = arch_state(name)
    k = 4
    rng = np.random.RandomState(17)
    prompt = rng.randint(0, cfg.vocab_size, (9,)).astype(np.int32)
    state, tok0, rt, max_len, _ = _prefilled_state(
        cfg, params, prompt, kv_dtype, horizon=k + 2
    )

    seq_logits, toks, st = [], [tok0], state
    for _ in range(k):
        lg, st = _decode_step(cfg, params, st, [toks[-1]], rt, max_len)
        seq_logits.append(np.asarray(lg[0]))
        toks.append(int(jnp.argmax(lg[0, : cfg.vocab_size])))

    lg_v, _ = paged_forward(
        cfg, params, state["caches"], state["tables"],
        jnp.asarray([toks[:k]], jnp.int32), state["lengths"],
        jnp.asarray([k], jnp.int32), rt, max_len, all_rows=True,
    )
    for j in range(k):
        assert int(jnp.argmax(lg_v[0, j, : cfg.vocab_size])) == toks[j + 1], j
        np.testing.assert_allclose(
            np.asarray(lg_v[0, j]), seq_logits[j], rtol=1e-4, atol=1e-4,
            err_msg=f"row {j}",
        )


# ---------------------------------------------------- engine identity
def _run_alone(cfg, params, prompt, max_new):
    out, _ = generate(
        cfg, params, {"tokens": jnp.asarray(prompt[None])}, RT, max_new
    )
    return np.asarray(out[0])


def _drive(cfg, params, ecfg, prompts, max_news, **kw):
    eng = ServeEngine(cfg, params, RT, ecfg, **kw)
    rids = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
    out = eng.run()
    return eng, [np.asarray(out[r]) for r in rids]


def _spec_prompts(cfg):
    """Staggered lengths plus one cyclic prompt (the identity claims hold
    at ANY acceptance rate, so a near-zero-acceptance full-vocab workload
    is the harshest rejection exercise)."""
    rng = np.random.RandomState(2)
    base = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
    prompts = [
        np.tile(base, 4),                                       # cyclic
        rng.randint(0, cfg.vocab_size, (11,)).astype(np.int32),
        rng.randint(0, cfg.vocab_size, (17,)).astype(np.int32),
        rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32),
    ]
    return prompts, [10, 6, 9, 8]


ECFG = dict(max_slots=2, page_size=8, num_pages=33, max_len=64,
            inner_steps=4)


def test_spec_ngram_token_identical_and_counters(arch_state):
    """Anchored binary-vocab scenario (the bench's trick): a vocab-2
    random-init model's greedy stream falls into short cycles, so the
    prompt-lookup drafter provably lands hits — the accept counters are
    non-zero, not just well-formed. Full-vocab ngram identity (where every
    draft is junk and must be rejected) is covered by the rollback and
    prefix-cache tests below."""
    base_cfg, _ = arch_state("granite-8b")
    cfg = reduced(base_cfg, name="granite-8b-bin", vocab_size=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 2, (n,)).astype(np.int32) for n in (12, 7, 15)]
    max_news = [24, 16, 20]
    _, off = _drive(cfg, params, EngineConfig(**ECFG), prompts, max_news)
    eng, on = _drive(
        cfg, params, EngineConfig(spec_tokens=3, **ECFG), prompts, max_news
    )
    for i, (a, b) in enumerate(zip(off, on)):
        np.testing.assert_array_equal(a, b, err_msg=f"req {i}")
        np.testing.assert_array_equal(
            a, _run_alone(cfg, params, prompts[i], max_news[i]),
            err_msg=f"req {i} alone",
        )
    s = eng.stats
    assert s["spec_verify_calls"] > 0
    assert s["spec_drafted_tokens"] > 0
    # the cyclic greedy stream guarantees some prompt-lookup hits land
    assert s["spec_accepted_tokens"] > 0
    assert 0.0 < s["spec_accept_rate"] <= 1.0
    # every verify commits at least the target's own next token
    assert s["spec_accepted_per_verify"] >= 1.0
    # fewer ticks than tokens: the whole point of the multi-token commit
    total = sum(len(o) for o in on)
    assert s["spec_verify_calls"] < total, (s["spec_verify_calls"], total)
    eng.pool.check()
    assert eng.pool.pages_in_use == 0


def test_spec_model_drafter_token_identical(arch_state):
    """Paired 1-layer drafter with its own random init: mostly-rejected
    drafts (the rejection path), yet the committed stream is exactly the
    target's greedy stream."""
    cfg, params = arch_state("granite-8b")
    dcfg = paired_drafter_cfg(cfg)
    dparams = init_params(dcfg, jax.random.PRNGKey(1))
    prompts, max_news = _spec_prompts(cfg)
    _, off = _drive(cfg, params, EngineConfig(**ECFG), prompts, max_news)
    eng, on = _drive(
        cfg, params,
        EngineConfig(spec_tokens=3, spec_drafter="model", **ECFG),
        prompts, max_news, draft_params=dparams, draft_cfg=dcfg,
    )
    for i, (a, b) in enumerate(zip(off, on)):
        np.testing.assert_array_equal(a, b, err_msg=f"req {i}")
    assert eng.stats["spec_verify_calls"] > 0
    eng.pool.check()
    assert eng.pool.pages_in_use == 0


def test_spec_self_draft_accepts_nearly_everything(arch_state):
    """Drafter == target: every draft token IS the target argmax, so only
    the per-request remaining-token cap can reject — acceptance must be
    near 1 and each verify must commit multiple tokens. Exercises the
    full-accept catch-up path (drafter one token behind after k+1
    commits) that partial acceptance never reaches. Longer max_news than
    ``_spec_prompts`` so the tail-cap rejections amortize below 20%."""
    cfg, params = arch_state("granite-8b")
    prompts, _ = _spec_prompts(cfg)
    max_news = [22, 18, 21, 20]
    eng, on = _drive(
        cfg, params,
        EngineConfig(spec_tokens=3, spec_drafter="model", **ECFG),
        prompts, max_news, draft_params=params, draft_cfg=cfg,
    )
    _, off = _drive(cfg, params, EngineConfig(**ECFG), prompts, max_news)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)
    s = eng.stats
    assert s["spec_accept_rate"] > 0.8, s["spec_accept_rate"]
    assert s["spec_accepted_per_verify"] > 2.0, s["spec_accepted_per_verify"]


def test_spec_rollback_under_optimistic_eviction(arch_state):
    """Optimistic policy + tiny pool: eviction mid-decode AND per-tick
    ``PagePool.truncate`` rewinds of over-reserved draft capacity. The
    rollback must be invisible in the tokens and leave the pool clean."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
               for _ in range(2)]
    max_news = [24, 16]
    tight = dict(max_slots=2, page_size=4, num_pages=10, max_len=48,
                 inner_steps=4, policy="optimistic")
    _, off = _drive(cfg, params, EngineConfig(**tight), prompts, max_news)
    eng, on = _drive(
        cfg, params, EngineConfig(spec_tokens=3, **tight), prompts, max_news
    )
    assert eng.stats.get("evictions", 0) > 0
    for i, (a, b) in enumerate(zip(off, on)):
        np.testing.assert_array_equal(a, b, err_msg=f"req {i}")
        np.testing.assert_array_equal(
            a, _run_alone(cfg, params, prompts[i], max_news[i])
        )
    eng.pool.check()
    assert eng.pool.pages_in_use == 0


def test_spec_with_prefix_cache_and_chunked_prefill(arch_state):
    """ngram drafting composes with the radix prefix cache and chunked
    prefill: spec ticks interleave with mid-prefill ticks (which fall
    back to the ordinary chunk path) without changing a token."""
    cfg, params = arch_state("granite-8b")
    rng = np.random.RandomState(6)
    sys_prompt = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    prompts = [
        np.concatenate([sys_prompt,
                        rng.randint(0, cfg.vocab_size, (s,)).astype(np.int32)])
        for s in (5, 3, 7)
    ]
    max_news = [8, 10, 6]
    mode = dict(prefix_cache=True, prefill_chunk=8, **ECFG)
    _, off = _drive(cfg, params, EngineConfig(**mode), prompts, max_news)
    eng, on = _drive(
        cfg, params, EngineConfig(spec_tokens=3, **mode), prompts, max_news
    )
    for i, (a, b) in enumerate(zip(off, on)):
        np.testing.assert_array_equal(a, b, err_msg=f"req {i}")
    assert eng.stats["spec_verify_calls"] > 0
    eng.pool.check()
    # retired prompts stay resident in the radix cache by design; spec
    # drafting must not leak pages beyond what the cache accounts for
    assert eng.pool.pages_in_use == eng.prefix.pages_cached()
    eng.prefix.clear()
    assert eng.pool.pages_in_use == 0


# ------------------------------------------------------------- gating
def test_spec_config_gating(arch_state):
    cfg, params = arch_state("granite-8b")
    vis_cfg, vis_params = arch_state("phi-3-vision-4.2b")
    spec = dict(spec_tokens=3, **ECFG)
    # dense-fallback families have no paged verify path
    for name in ("falcon-mamba-7b", "recurrentgemma-2b",
                 "seamless-m4t-medium"):
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(get_reduced(name), None, RT, EngineConfig(**spec))
    with pytest.raises(ValueError, match="temperature"):
        ServeEngine(cfg, params, RT,
                    EngineConfig(temperature=0.7, **spec))
    with pytest.raises(ValueError, match="spec_drafter"):
        ServeEngine(cfg, params, RT,
                    EngineConfig(spec_drafter="medusa", **spec))
    with pytest.raises(ValueError, match="prefix_cache"):
        ServeEngine(cfg, params, RT,
                    EngineConfig(spec_drafter="model", prefix_cache=True,
                                 **spec))
    with pytest.raises(ValueError, match="draft_params"):
        ServeEngine(cfg, params, RT,
                    EngineConfig(spec_drafter="model", **spec))
    with pytest.raises(ValueError, match="ngram drafter"):
        ServeEngine(vis_cfg, vis_params, RT,
                    EngineConfig(spec_drafter="model", **spec),
                    draft_params=vis_params, draft_cfg=vis_cfg)


# ----------------------------------------------------------- drafters
def test_ngram_draft_prompt_lookup():
    # continuation after the earlier occurrence of the final 3-gram
    ctx = np.array([7, 1, 2, 3, 9, 8, 1, 2, 3], np.int32)
    np.testing.assert_array_equal(ngram_draft(ctx, k=2), [9, 8])
    # k truncation; a continuation that runs off the end cycles the tail
    np.testing.assert_array_equal(ngram_draft(ctx, k=1), [9])
    np.testing.assert_array_equal(
        ngram_draft(np.array([1, 2, 3, 1, 2, 3], np.int32), k=5),
        [1, 2, 3, 1, 2],
    )
    # longest n wins: a 2-gram match beats a more recent 1-gram match
    ctx = np.array([1, 2, 5, 4, 2, 9, 1, 2], np.int32)
    np.testing.assert_array_equal(ngram_draft(ctx, k=1), [5])
    # most recent occurrence wins at equal n
    ctx = np.array([1, 2, 5, 0, 1, 2, 8, 0, 1, 2], np.int32)
    np.testing.assert_array_equal(ngram_draft(ctx, k=1), [8])
    # periodic tail extension: on the period-2 stream the nearest match
    # sits 2 tokens from the end — blind truncation would propose only
    # [0, 1] and cap every accepted run at one period
    ctx = np.tile(np.array([0, 1], np.int32), 5)
    np.testing.assert_array_equal(ngram_draft(ctx, k=3), [0, 1, 0])
    # no repeat -> empty proposal (draft-free verify tick)
    assert ngram_draft(np.array([1, 2, 3, 4], np.int32), k=3).size == 0
    assert ngram_draft(np.array([5], np.int32), k=3).size == 0
    assert ngram_draft(np.array([1, 2, 1, 2], np.int32), k=0).size == 0


def test_paired_drafter_cfg_contract():
    from repro.serve import paged_supported

    cfg = get_reduced("granite-8b")
    dcfg = paired_drafter_cfg(cfg)
    assert dcfg.n_layers == 1
    assert dcfg.vocab_size == cfg.vocab_size     # draft tokens ARE target ids
    assert dcfg.family == cfg.family
    assert dcfg.name == cfg.name + "-draft"
    assert paged_supported(dcfg)
    assert paired_drafter_cfg(cfg, n_layers=2).n_layers == 2

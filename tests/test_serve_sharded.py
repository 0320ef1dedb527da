"""Sharded serving: tensor-parallel paged decode over the (data, model) mesh.

Subprocess tests on forced host devices (the in-process jax backend is
already locked to one CPU device):

* TP=2 / 2x2 engines must produce TOKEN-IDENTICAL output to the TP=1
  engine for the same requests — the sharded decode is a layout change,
  not a numerics change the sampler can see.
* The KV pool's per-device bytes must shrink by the model-axis factor
  (kv-head axis sharding, ``sharding.specs.pool_kv_spec``).
* The Pallas paged kernel runs inside shard_map on per-shard head slices.
* ``ReplicatedServeEngine`` routes work to every data replica and matches
  the single engine.
* MQA families (kv heads don't divide TP) fall back to a replicated pool
  and still serve correctly.
* Quantized (int8) pools shard codes AND scale buffers over the model
  axis: token-identical to the single-device int8 engine, per-device
  bytes halved exactly.
"""
import subprocess
import sys
import textwrap

import pytest

from _subproc import REPO_ROOT, subprocess_env

pytestmark = pytest.mark.multidevice

HEADER = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_reduced
    from repro.launch.mesh import make_serve_mesh, replica_submeshes
    from repro.models import Runtime, init_params
    from repro.serve import EngineConfig, ReplicatedServeEngine, ServeEngine

    cfg = get_reduced("{arch}")
    rt = Runtime(dtype=jnp.float32, chunk_q=32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (s,)).astype(np.int32)
               for s in (5, 11, 17, 8)]
    max_news = [9, 4, 12, 7]
    ecfg = EngineConfig(max_slots=2, page_size=8, num_pages=33, max_len=64,
                        inner_steps=4)

    def run_engine(mesh, ec=ecfg):
        eng = ServeEngine(cfg, params, rt.replace(mesh=mesh), ec)
        rids = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
        out = eng.run()
        return eng, [out[r] for r in rids]
    """
)

TP_SCRIPT = HEADER.format(arch="moonshot-v1-16b-a3b") + textwrap.dedent(
    """
    eng1, out1 = run_engine(None)
    eng2, out2 = run_engine(make_serve_mesh(1, 2))
    eng4, out4 = run_engine(make_serve_mesh(2, 2))
    for a, b, c in zip(out1, out2, out4):
        np.testing.assert_array_equal(a, b)   # TP=2 == TP=1, every token
        np.testing.assert_array_equal(a, c)   # 2x2 mesh (data-replicated)
    b1 = eng1.kv_pool_bytes_per_device()
    b2 = eng2.kv_pool_bytes_per_device()
    assert b1 == 2 * b2, (b1, b2)             # kv-head shard halves KV/chip
    assert b2 == eng4.kv_pool_bytes_per_device()
    for eng in (eng1, eng2, eng4):
        eng.pool.check()
        assert eng.pool.pages_in_use == 0
    print("TP_OK", b1, b2)
    """
)

KERNEL_SCRIPT = HEADER.format(arch="moonshot-v1-16b-a3b") + textwrap.dedent(
    """
    ek = EngineConfig(max_slots=1, page_size=8, num_pages=9, max_len=32,
                      inner_steps=2, use_kernel=True)
    prompts, max_news = prompts[:1], [4]
    _, out_oracle = run_engine(None, EngineConfig(
        max_slots=1, page_size=8, num_pages=9, max_len=32, inner_steps=2))
    _, out_kernel = run_engine(make_serve_mesh(1, 2), ek)
    np.testing.assert_array_equal(out_oracle[0], out_kernel[0])
    print("KERNEL_SHARDED_OK")
    """
)

REPLICA_SCRIPT = HEADER.format(arch="moonshot-v1-16b-a3b") + textwrap.dedent(
    """
    rep = ReplicatedServeEngine(cfg, params, rt, ecfg,
                                mesh=make_serve_mesh(2, 2))
    assert len(rep.engines) == 2
    rids = [rep.submit(p, m) for p, m in zip(prompts, max_news)]
    out = rep.run()
    assert all(n > 0 for n in rep.stats["replica_requests"]), (
        rep.stats["replica_requests"])        # least-loaded: both replicas used
    _, alone = run_engine(None)
    for rid, want in zip(rids, alone):
        np.testing.assert_array_equal(out[rid], want)
    assert set(rep.stats["ttft_s"]) == set(rids)
    assert rep.stats["kv_pool_bytes_per_device"] > 0
    print("REPLICA_OK", rep.stats["replica_requests"])
    """
)

PREFIX_SCRIPT = HEADER.format(arch="moonshot-v1-16b-a3b") + textwrap.dedent(
    """
    # radix prefix cache + chunked prefill under TP: cache hits and chunk
    # scheduling are host-side and topology-blind, so TP=2 must be token-
    # identical to TP=1 on both the cold and the all-hit warm pass
    shared = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    prompts = [np.concatenate([shared, t]) for t in (
        rng.randint(0, cfg.vocab_size, (5,)).astype(np.int32),
        rng.randint(0, cfg.vocab_size, (9,)).astype(np.int32),
        rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32),
    )]
    max_news = [6, 4, 7]
    ecfg = EngineConfig(max_slots=2, page_size=8, num_pages=33, max_len=64,
                        inner_steps=4, prefix_cache=True, prefill_chunk=4)

    def run_prefix(mesh):
        eng = ServeEngine(cfg, params, rt.replace(mesh=mesh), ecfg)
        rids = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
        cold = eng.run()
        rids2 = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
        warm = eng.run()
        eng.pool.check(); eng.prefix.check()
        return eng, [cold[r] for r in rids], [warm[r] for r in rids2]

    e1, cold1, warm1 = run_prefix(None)
    e2, cold2, warm2 = run_prefix(make_serve_mesh(1, 2))
    for a, b, c, d in zip(cold1, warm1, cold2, warm2):
        np.testing.assert_array_equal(a, b)   # cold == warm (reuse exact)
        np.testing.assert_array_equal(a, c)   # TP=1 == TP=2 cold
        np.testing.assert_array_equal(a, d)   # TP=1 == TP=2 warm
    assert e1.stats["prefix_hits"] >= 4 and e2.stats["prefix_hits"] >= 4
    assert e1.stats["prefix_hits"] == e2.stats["prefix_hits"]
    print("PREFIX_SHARDED_OK", e2.stats["prefix_hits"])
    """
)

QUANT_SCRIPT = HEADER.format(arch="moonshot-v1-16b-a3b") + textwrap.dedent(
    """
    # int8 KV pool under TP=2: the scale buffers shard their kv-head axis
    # alongside the code pools (sharding.specs.pool_scale_spec), so the
    # sharded engine is token-identical to the single-device int8 engine
    # and per-device pool bytes (codes + scales) halve exactly
    eq = EngineConfig(max_slots=2, page_size=8, num_pages=33, max_len=64,
                      inner_steps=4, kv_dtype="int8")
    eng1, out1 = run_engine(None, eq)
    eng2, out2 = run_engine(make_serve_mesh(1, 2), eq)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)   # TP=2 == TP=1 at int8
    b1 = eng1.kv_pool_bytes_per_device()
    b2 = eng2.kv_pool_bytes_per_device()
    assert b1 == 2 * b2, (b1, b2)             # codes AND scales shard
    leaves = jax.tree.leaves(eng2._dev["caches"])
    assert any(l.dtype == jnp.int8 for l in leaves)      # quantized pool
    assert any(l.ndim == 4 and l.dtype == jnp.float32    # (R, N, Kv, page)
               for l in leaves)                          # scale buffers
    for eng in (eng1, eng2):
        eng.pool.check()
        assert eng.pool.pages_in_use == 0
    print("QUANT_SHARDED_OK", b1, b2)
    """
)

MQA_SCRIPT = HEADER.format(arch="granite-8b") + textwrap.dedent(
    """
    assert cfg.n_kv_heads == 1                # MQA: heads can't divide TP=2
    eng1, out1 = run_engine(None)
    eng2, out2 = run_engine(make_serve_mesh(1, 2))
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)
    # pool falls back to replication: same bytes on every device
    assert eng1.kv_pool_bytes_per_device() == eng2.kv_pool_bytes_per_device()
    print("MQA_FALLBACK_OK")
    """
)


SPEC_SCRIPT = HEADER.format(arch="moonshot-v1-16b-a3b") + textwrap.dedent(
    """
    # speculative decoding under TP=2: drafting (host-side ngram lookup)
    # and the batched verify pass are layout-blind, so the spec engine at
    # TP=2 must be token-identical to spec-off at TP=1 — the strongest
    # form of the "drafting changes speed, never tokens" claim
    base = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
    prompts = [np.tile(base, 4)] + prompts[:3]
    max_news = [10, 9, 4, 12]
    es = EngineConfig(max_slots=2, page_size=8, num_pages=33, max_len=64,
                      inner_steps=4, spec_tokens=3)
    eng0, out0 = run_engine(None)                       # spec-off TP=1
    eng1, out1 = run_engine(None, es)                   # spec-on  TP=1
    eng2, out2 = run_engine(make_serve_mesh(1, 2), es)  # spec-on  TP=2
    for a, b, c in zip(out0, out1, out2):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    assert eng2.stats["spec_verify_calls"] > 0
    assert eng1.stats["spec_accepted_tokens"] == (
        eng2.stats["spec_accepted_tokens"])   # same ticks, same commits
    for eng in (eng1, eng2):
        eng.pool.check()
        assert eng.pool.pages_in_use == 0
    print("SPEC_SHARDED_OK", eng2.stats["spec_accepted_tokens"])
    """
)


def _run(script, marker):
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=1200,
        env=subprocess_env(),
        cwd=REPO_ROOT,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    assert marker in r.stdout, r.stdout[-2000:]


def test_tp_engine_token_identical_and_kv_bytes_halved():
    _run(TP_SCRIPT, "TP_OK")


def test_paged_kernel_inside_shard_map_matches_oracle():
    _run(KERNEL_SCRIPT, "KERNEL_SHARDED_OK")


def test_replicated_engine_routes_and_matches_single():
    _run(REPLICA_SCRIPT, "REPLICA_OK")


def test_mqa_family_falls_back_to_replicated_pool():
    _run(MQA_SCRIPT, "MQA_FALLBACK_OK")


def test_quantized_pool_token_identical_and_bytes_halved_under_tp():
    _run(QUANT_SCRIPT, "QUANT_SHARDED_OK")


def test_prefix_cache_and_chunked_prefill_token_identical_under_tp():
    _run(PREFIX_SCRIPT, "PREFIX_SHARDED_OK")


def test_speculative_decoding_token_identical_under_tp():
    _run(SPEC_SCRIPT, "SPEC_SHARDED_OK")

#!/usr/bin/env python3
"""Smoke run of the paged server and the trainer on TPU chips, at the
published widths of granite-8b (d_model 4096, 32 q / 8 kv heads, head_dim
128, d_ff 14336, vocab 49152), through the library calls the launchers use.

    python chip_smoke.py               # one chip: serve + train
    python chip_smoke.py --four-chips  # four chips: TP serving + 3D trainer

One chip, two phases:

* serve — ``ServeEngine`` over granite-8b cut to 16 of 36 layers, weights
  in bf16 (~3.9 B parameters), a ~4 GiB paged KV pool sized by
  ``EngineConfig.capacity`` (64 KiB per token), the compiled Pallas paged
  prefill and decode kernels. Variable-length requests run to completion.
  The same requests then run on the jnp-oracle engine (``use_kernel=False``)
  and every request's first-token logits must agree within LOGIT_RTOL; each
  greedy token the kernel engine emitted must be, within LOGIT_RTOL, the
  argmax of the dense training forward (``models.forward``) over the same
  tokens.
* train — ``launch.train.build_train`` on a 1x1 mesh over granite-8b cut to
  one layer (one period of its homogeneous stack), bf16, full remat, the
  fused backward (flash fwd/bwd, fused RMSNorm, chunked CE); five steps on
  one repeated batch: the loss stays finite and falls.

Four chips (``--four-chips``), and nothing else: whole granite-8b served at
TP=4; an 8-layer cut at TP=4 against TP=1 on chip 0, both in f32 (identical
greedy streams, first-token logits within TP_LOGIT_RTOL); the 3D trainer at
(dp, tp, pp) = (1, 2, 2) with 1F1B against the 2D trainer at TP=4 on a
two-layer cut (per-step loss and gradient norm within TRAIN_RTOL).

Weights and data come from ``--seed``; nothing is read from outside the
checkout and no process is started. The script refuses to run without a
TPU or with Pallas kernels in interpret mode. Times printed are those of
this one smoke run, compilation marked where it is included; they are not
benchmark results. Any failed check raises and exits non-zero; only a
complete run prints the last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "granite-8b"

# Logit agreement between two bf16 programs that differ only in summation
# order (Pallas kernel vs jnp oracle, paged engine vs dense forward), as a
# fraction of the reference's largest |logit|. In a CPU
# rehearsal at two layers such pairs differed by 1.2% of it, while a
# page-indexing fault moved logits by more than 100% of it; 10% sits an
# order of magnitude from both and leaves room for the noise to grow with
# depth. Random weights give near-flat logits, so greedy streams of two
# correct programs may part at a near-tie: each emitted greedy token is
# therefore checked to be within this tolerance of the dense forward's
# argmax rather than required identical (identical streams are counted).
LOGIT_RTOL = 0.1
# TP=4 against TP=1 runs both engines in f32 with f32 matmul passes: the
# two programs then differ only in the order of the TP partial sums, an
# error near f32's 1e-7 that stays far below 1e-3 through eight layers,
# while the bf16 activations of serving would round it up to the 1e-2 of
# LOGIT_RTOL's pairs and part greedy streams at near-ties. So here the
# streams must be identical, and a sharding fault (wrong head slice, a
# missing reduce) moves logits by far more than 1e-3.
TP_LOGIT_RTOL = 1e-3
# Per-step loss and gradient norm of the 3D trainer against the 2D trainer:
# same math in bf16, different reduction trees (pipeline microbatches and
# TP=2 partial sums against TP=4), so they agree to bf16's ~3 digits.
TRAIN_RTOL = 2e-2

SERVE_LAYERS = 16
POOL_BYTES = 4 << 30
FOUR_POOL_BYTES = 1 << 30     # across the four chips
PAGE = 16
MAX_PROMPT = 1008            # prompt tokens of the longest request
MAX_NEW = 48                 # horizon MAX_PROMPT + MAX_NEW = 66 pages
PREFILL_CHUNK = 256
INNER_STEPS = 4
N_REQUESTS = 10

TRAIN_LAYERS = 1
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 2048, 5


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def make_requests(rng, vocab, n, max_prompt, max_new):
    """``n`` (prompt, max_new) pairs of spread lengths from ``rng``."""
    lens = rng.randint(16, max_prompt + 1, size=n)
    lens[0] = max_prompt                        # one request at the horizon
    news = rng.randint(max(max_new // 4, 2), max_new + 1, size=n)
    return [
        (rng.randint(0, vocab, size=int(L)).astype(np.int32), int(m))
        for L, m in zip(lens, news)
    ]


def serve(cfg, params, requests, *, use_kernel, pool_bytes, mesh=None,
          dtype=None, max_prompt=MAX_PROMPT, max_new=MAX_NEW,
          chunk=PREFILL_CHUNK, page=PAGE, inner_steps=INNER_STEPS):
    """Run ``requests`` to completion on a fresh paged engine computing in
    ``dtype`` (bf16 by default); returns its tokens, first-token logits and
    timings. The engine (and its pool) is released on return."""
    import jax.numpy as jnp

    from repro.models import Runtime
    from repro.serve import EngineConfig, ServeEngine

    dtype = jnp.dtype(dtype or jnp.bfloat16)
    cap = EngineConfig.capacity(
        max_prompt, max_new, pool_bytes=pool_bytes, cfg=cfg, page_size=page,
        native_itemsize=dtype.itemsize,
    )
    ecfg = cap.engine(
        use_kernel=use_kernel, prefill_chunk=chunk, inner_steps=inner_steps,
        keep_first_logits=True,
    )
    eng = ServeEngine(cfg, params, Runtime(dtype=dtype, mesh=mesh), ecfg)
    t0 = time.perf_counter()
    rids = [eng.submit(p, n) for p, n in requests]
    out = eng.run()
    wall = time.perf_counter() - t0
    for rid, (_, n) in zip(rids, requests):
        check(len(out.get(rid, ())) == n,
              f"request {rid} returned {len(out.get(rid, ()))} of {n} tokens")
    programs = eng.lowered_programs()
    res = {
        "tokens": [out[r] for r in rids],
        "logits": [eng.first_logits[r][: cfg.vocab_size] for r in rids],
        "wall_s": wall,
        "ttft_s": [eng.stats["ttft_s"][r] for r in rids],
        "kernel_in_hlo": {k: "tpu_custom_call" in v
                          for k, v in programs.items()},
        "slots": cap.slots,
        "pool_bytes": cap.pool_bytes,
        "pool_bytes_per_device": eng.kv_pool_bytes_per_device(),
    }
    for lg in res["logits"]:
        check(bool(np.all(np.isfinite(lg))), "non-finite first-token logits")
    return res


def logit_gap(a, b) -> float:
    """Worst max|a - b| over requests, as a fraction of max|b|."""
    return max(float(np.max(np.abs(x - y)) / np.max(np.abs(y)))
               for x, y in zip(a, b))


def greedy_regret(cfg, params, requests, tokens):
    """For each emitted greedy token, how far its logit falls below the
    argmax of the dense training forward over the same sequence, as a
    fraction of that row's max|logit|; the worst over all requests and
    positions."""
    import jax
    import jax.numpy as jnp

    from repro.models import Runtime, forward

    rt = Runtime(dtype=jnp.bfloat16)
    L = max(len(p) + len(t) for (p, _), t in zip(requests, tokens))
    L = -(-L // 128) * 128                       # one compiled length
    fwd = jax.jit(lambda p, t: forward(cfg, p, {"tokens": t}, rt)[0][0])
    worst = 0.0
    for (prompt, _), gen in zip(requests, tokens):
        seq = np.concatenate([prompt, gen[:-1]])
        padded = np.zeros((1, L), np.int32)
        padded[0, : len(seq)] = seq                # causal: tail is inert
        pos = len(prompt) - 1 + np.arange(len(gen))
        lg = np.asarray(fwd(params, jnp.asarray(padded)), np.float32)
        lg = lg[pos, : cfg.vocab_size]
        short = lg.max(axis=1) - lg[np.arange(len(gen)), gen]
        worst = max(worst, float(np.max(short / np.abs(lg).max(axis=1))))
    return worst


def serve_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import launch_config
    from repro.models import init_params_as

    cfg = launch_config(ARCH, full=True, layers=SERVE_LAYERS)
    t0 = time.perf_counter()
    params = init_params_as(cfg, jax.random.PRNGKey(seed), jnp.bfloat16)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"serve: {cfg.name} {n_params / 1e9:.2f}B params bf16, init "
        f"{time.perf_counter() - t0:.1f}s")
    rng = np.random.RandomState(seed)
    requests = make_requests(rng, cfg.vocab_size, N_REQUESTS, MAX_PROMPT,
                             MAX_NEW)
    runs = {}
    for use_kernel in (True, False):
        name = "kernel" if use_kernel else "oracle"
        r = serve(cfg, params, requests, use_kernel=use_kernel,
                  pool_bytes=POOL_BYTES)
        runs[name] = r
        log(f"serve[{name}]: {len(requests)} requests, "
            f"{sum(len(t) for t in r['tokens'])} tokens, {r['slots']} slots, "
            f"pool {r['pool_bytes'] / 2**30:.2f} GiB; smoke wall "
            f"{r['wall_s']:.1f}s incl. compile, first TTFT "
            f"{r['ttft_s'][0]:.1f}s, last TTFT {max(r['ttft_s']):.1f}s; "
            f"tpu_custom_call in {r['kernel_in_hlo']}")
    check(all(runs["kernel"]["kernel_in_hlo"].values()),
          "the kernel engine's programs hold no Pallas kernel")
    check(not any(runs["oracle"]["kernel_in_hlo"].values()),
          "the oracle engine's programs hold a Pallas kernel")
    gap = logit_gap(runs["kernel"]["logits"], runs["oracle"]["logits"])
    same = sum(np.array_equal(a, b) for a, b in
               zip(runs["kernel"]["tokens"], runs["oracle"]["tokens"]))
    log(f"serve: first-token logits kernel vs oracle max|diff|/max|logit| "
        f"{gap:.4g} (rtol {LOGIT_RTOL}); greedy streams identical {same}/"
        f"{len(requests)}")
    check(gap <= LOGIT_RTOL, f"kernel vs oracle logits differ by {gap}")
    regret = greedy_regret(cfg, params, requests, runs["kernel"]["tokens"])
    log(f"serve: kernel engine's greedy tokens vs dense forward argmax: "
        f"worst shortfall/max|logit| {regret:.4g} (rtol {LOGIT_RTOL})")
    check(regret <= LOGIT_RTOL, f"greedy token shortfall {regret}")


def train(cfg, mesh, tc, shape, seed, steps, batch_np=None):
    """Compile and run ``steps`` steps of the 2D trainer (or, with
    ``tc.pipe > 1``, the 3D one) on one repeated batch (``batch_np``, or
    one drawn from the data pipeline); returns (metrics per step, compiled
    step, compile seconds, the batch)."""
    import jax
    import jax.numpy as jnp

    from repro.data import DataPipeline
    from repro.launch.train import (
        build_train,
        build_train_pipeline,
        init_train_state,
    )

    if tc.pipe > 1:
        from repro.core.partitioner import ParallelPlan

        plan = ParallelPlan(
            dp=mesh.shape["data"], tp=mesh.shape["model"], pp=tc.pipe,
            microbatches=tc.microbatches, schedule=tc.schedule,
            remat=tc.remat,
        ).validate(cfg)
        jitted, (s_struct, b_struct) = build_train_pipeline(
            cfg.name, mesh, plan, tc, shape
        )
    else:
        jitted, (s_struct, b_struct) = build_train(cfg.name, mesh, tc, shape)
    t0 = time.perf_counter()
    compiled = jitted.lower(s_struct, b_struct).compile()
    compile_s = time.perf_counter() - t0
    if batch_np is None:
        data = DataPipeline(cfg, shape.global_batch, shape.seq_len, seed=seed)
        batch_np = {k: np.asarray(v) for k, v in dict(next(data)).items()}
        data.close()
    batch = jax.tree.map(
        lambda v, st: jax.device_put(jnp.asarray(v), st.sharding),
        batch_np, b_struct,
    )
    state = init_train_state(cfg, tc, s_struct, seed)
    metrics = []
    for i in range(steps):
        t1 = time.perf_counter()
        state, m = compiled(state, batch)
        m = {k: float(v) for k, v in m.items()}
        m["wall_s"] = time.perf_counter() - t1
        metrics.append(m)
    return metrics, compiled, compile_s, batch_np


def train_phase(seed: int) -> None:
    from repro.configs import ShapeSpec, launch_config
    from repro.launch.mesh import make_mesh
    from repro.train import TrainConfig

    cfg = launch_config(ARCH, full=True, layers=TRAIN_LAYERS)
    tc = TrainConfig(precision="bf16", remat="full", fused_backward=True)
    shape = ShapeSpec("smoke", TRAIN_SEQ, TRAIN_BATCH, "train")
    mesh = make_mesh((1, 1), ("data", "model"))
    metrics, compiled, compile_s, _ = train(cfg, mesh, tc, shape, seed,
                                            TRAIN_STEPS)
    mem = compiled.memory_analysis()
    kernel = "tpu_custom_call" in compiled.as_text()
    log(f"train: {cfg.name} batch {TRAIN_BATCH}x{TRAIN_SEQ}, compile "
        f"{compile_s:.1f}s; memory_analysis args "
        f"{mem.argument_size_in_bytes / 2**30:.2f} GiB temp "
        f"{mem.temp_size_in_bytes / 2**30:.2f} GiB; tpu_custom_call in "
        f"HLO: {kernel}")
    losses = [m["loss"] for m in metrics]
    log("train: losses " + " ".join(f"{x:.4f}" for x in losses)
        + "; smoke step wall " + " ".join(f"{m['wall_s']:.2f}s"
                                          for m in metrics))
    check(kernel, "the fused train step holds no Pallas kernel")
    check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")


def four_chip_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import ShapeSpec, launch_config
    from repro.launch.mesh import make_mesh, make_serve_mesh, make_train_mesh
    from repro.models import init_params, init_params_as
    from repro.sharding.specs import param_specs, with_sharding
    from repro.train import TrainConfig

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, have "
          f"{len(jax.devices())}")
    tp4 = make_serve_mesh(1, 4)
    rng = np.random.RandomState(seed)

    def sharded_params(cfg, mesh):
        shape = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        shardings = with_sharding(mesh, param_specs(cfg, shape, mesh))
        return init_params_as(cfg, jax.random.PRNGKey(seed), jnp.bfloat16,
                              shardings)

    # whole granite-8b at TP=4
    cfg = launch_config(ARCH, full=True)
    params = sharded_params(cfg, tp4)
    reqs = make_requests(rng, cfg.vocab_size, 4, 256, 16)
    r = serve(cfg, params, reqs, use_kernel=True, pool_bytes=FOUR_POOL_BYTES,
              mesh=tp4, max_prompt=256, max_new=16)
    log(f"four: {cfg.name} {cfg.n_layers} layers TP=4: {len(reqs)} requests "
        f"{sum(len(t) for t in r['tokens'])} tokens, smoke wall "
        f"{r['wall_s']:.1f}s incl. compile, KV pool "
        f"{r['pool_bytes_per_device'] / 2**30:.2f} GiB per chip; "
        f"tpu_custom_call in {r['kernel_in_hlo']}")
    check(all(r["kernel_in_hlo"].values()), "TP=4 programs hold no kernel")
    del params

    # 8-layer cut: TP=4 against TP=1 on chip 0, computing in f32 (see
    # TP_LOGIT_RTOL) so that greedy streams must be identical
    cfg = launch_config(ARCH, full=True, layers=8)
    params = sharded_params(cfg, tp4)
    reqs = make_requests(rng, cfg.vocab_size, 4, 256, 16)
    kw = dict(use_kernel=True, pool_bytes=FOUR_POOL_BYTES, dtype=jnp.float32,
              max_prompt=256, max_new=16)
    with jax.default_matmul_precision("highest"):
        r4 = serve(cfg, params, reqs, mesh=tp4, **kw)
        params = jax.device_put(params, jax.devices()[0])
        r1 = serve(cfg, params, reqs, **kw)
    del params
    gap = logit_gap(r4["logits"], r1["logits"])
    same = sum(np.array_equal(a, b) for a, b in zip(r4["tokens"], r1["tokens"]))
    log(f"four: {cfg.name} f32 TP=4 vs TP=1: first-token logits "
        f"max|diff|/max|logit| {gap:.4g} (rtol {TP_LOGIT_RTOL}); greedy "
        f"streams identical {same}/{len(reqs)}; tpu_custom_call in "
        f"{r1['kernel_in_hlo']} at TP=1")
    check(all(r1["kernel_in_hlo"].values()), "TP=1 programs hold no kernel")
    check(gap <= TP_LOGIT_RTOL, f"TP=4 vs TP=1 logits differ by {gap}")
    check(same == len(reqs), f"TP=4 vs TP=1 greedy streams differ: "
          f"{r4['tokens']} vs {r1['tokens']}")

    # 3D trainer (1, 2, 2) 1F1B against the 2D trainer at TP=4
    cfg = launch_config(ARCH, full=True, layers=2)
    shape = ShapeSpec("smoke4", 1024, 4, "train")
    tc3 = TrainConfig(precision="bf16", remat="full", pipe=2,
                      microbatches=2, schedule="1f1b")
    m3, _, c3, batch = train(cfg, make_train_mesh(1, 2, 2), tc3, shape,
                             seed, 3)
    tc2 = TrainConfig(precision="bf16", remat="full")
    m2, _, c2, _ = train(cfg, make_mesh((1, 4), ("data", "model")), tc2,
                         shape, seed, 3, batch)
    for key in ("loss", "grad_norm"):
        a, b = [m[key] for m in m3], [m[key] for m in m2]
        log(f"four: 3D (1,2,2) 1F1B vs 2D TP=4 {key}: "
            + " ".join(f"{x:.4f}/{y:.4f}" for x, y in zip(a, b))
            + f" (compile {c3:.1f}s / {c2:.1f}s)")
        check(np.allclose(a, b, rtol=TRAIN_RTOL),
              f"3D vs 2D {key} differ: {a} vs {b}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases")
    args = ap.parse_args()

    check(not os.environ.get("REPRO_PALLAS_INTERPRET"),
          "REPRO_PALLAS_INTERPRET is set: the smoke runs compiled kernels")
    src = os.path.join(ROOT, "src")
    check(os.path.isdir(os.path.join(src, "repro")),
          f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, src)

    import jax

    from repro.kernels.runtime import default_interpret
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"no TPU: jax found {dev.platform}")
    check(not default_interpret(), "Pallas kernels would run interpreted")
    cache = enable_compile_cache()
    log(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {cache}")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        serve_phase(args.seed)
        train_phase(args.seed)
    log(f"smoke: all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()

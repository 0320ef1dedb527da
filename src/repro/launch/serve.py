"""Serving launcher: continuous-batching engine / batched generation driver.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
        --batch 4 --prompt-len 32 --new-tokens 32

    # continuous batching over the paged KV pool (variable-length requests):
    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --paged \
        --requests 8 --slots 4 --page-size 16

    # radix prefix cache + chunked prefill (shared system prompt workload):
    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --paged \
        --requests 8 --prefix-cache --prefill-chunk 8 --shared-prefix 24

    # sharded serving: 2 data replicas x TP=2 over 4 (forced-host) devices
    PYTHONPATH=src python -m repro.launch.serve --arch moonshot-v1-16b-a3b \
        --paged --mesh 2x2 --requests 8

    # SLO-grade trace replay through the async front-end (p50/p99 + goodput):
    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --paged \
        --trace poisson --arrival-rate 0.8 --qos mixed --max-queue 4

By default a smoke-size ``reduced()`` variant of the architecture family is
instantiated (small enough for a CPU) and driven through the real prefill +
decode path. ``--full`` keeps the published widths and holds the weights in
bf16, initialized in place on the devices (sharded over ``--mesh``'s model
axis); ``--layers N`` cuts the depth. On one TPU v5e chip:

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --full \
        --layers 16 --paged --kernel --prefill-chunk 128 --requests 8

``--paged`` routes through ``repro.serve.ServeEngine``: requests with
varying prompt lengths are admitted into fixed decode slots against the
paged KV-cache pool; unsupported families (SSM / enc-dec) fall back to the
dense path automatically.

``--prefix-cache`` turns on the radix-tree KV prefix cache (retired prompts'
pages stay pooled; token-exact shared prefixes are adopted with zero prefill
FLOPs) and ``--prefill-chunk N`` interleaves N-token prefill chunks with the
decode batch (one jitted step runs both). ``--shared-prefix K`` prepends a
common K-token system prompt to every generated request so the hit rate is
demonstrable; engine prefix stats (hit rate, cached-token fraction, mean
TTFT) print at exit.

``--mesh DxM`` serves over a ``(data, model)`` mesh: the KV pool and params
shard over the ``model`` axis (Megatron head split; KV bytes per device
shrink by M) and the ``data`` axis runs D least-loaded-routed engine
replicas. On CPU the device count is forced via
``--xla_force_host_platform_device_count`` unless ``--no-force-devices``
(set it for real TPU fleets, where the devices already exist).
"""
from __future__ import annotations

import argparse
import os
import time


def _replay_cli(args, cfg, eng) -> None:
    """--trace mode: replay a timed arrival stream through the async
    front-end and print the latency distribution + tick-exact goodput."""
    import asyncio

    import numpy as np

    from repro.serve import bursty_trace, goodput, poisson_trace, replay_trace

    rng = np.random.RandomState(args.seed)
    kw = dict(
        vocab=cfg.vocab_size,
        prompt_range=(max(args.prompt_len // 2, 1), args.prompt_len),
        new_range=(max(args.new_tokens // 2, 1), args.new_tokens),
        qos_batch_frac={"interactive": 0.0, "batch": 1.0, "mixed": 0.25}[
            args.qos
        ],
        shared_prefix=(
            rng.randint(0, cfg.vocab_size, (args.shared_prefix,)).astype(
                np.int32
            )
            if args.shared_prefix else None
        ),
        shared_frac=0.5 if args.shared_prefix else 0.0,
    )
    if args.trace == "poisson":
        trace = poisson_trace(
            rng, args.requests, rate=args.arrival_rate, **kw
        )
    else:
        gap = max(int(round(4 / args.arrival_rate)), 1)
        trace = bursty_trace(rng, args.requests, burst=4, gap=gap, **kw)

    records, fe = asyncio.run(replay_trace(eng, trace))
    ttfts = [r["ttft_s"] for r in records if r["ttft_s"] is not None]
    tpots = [r["tpot_s"] for r in records if r["tpot_s"] is not None]
    met, total = goodput(records, args.slo_ticks)
    s = eng.stats
    print(
        f"{cfg.name} [{cfg.family}] trace={args.trace} "
        f"rate={args.arrival_rate}/tick qos={args.qos}: "
        f"{total} requests over {fe.ticks} ticks, "
        f"{s['tokens_per_s']:.1f} tok/s"
    )
    if ttfts:
        print(
            f"  ttft_ms p50={np.percentile(ttfts, 50) * 1e3:.1f} "
            f"p99={np.percentile(ttfts, 99) * 1e3:.1f}; "
            f"tpot_ms p50={np.percentile(tpots, 50) * 1e3:.2f} "
            f"p99={np.percentile(tpots, 99) * 1e3:.2f}"
            if tpots else
            f"  ttft_ms p50={np.percentile(ttfts, 50) * 1e3:.1f} "
            f"p99={np.percentile(ttfts, 99) * 1e3:.1f}"
        )
    completed = sum(1 for r in records if r["status"] == "complete")
    deferred = sum(r["deferred_ticks"] for r in records)
    print(
        f"  goodput={met}/{total} (first token within {args.slo_ticks} "
        f"ticks of arrival); completed={completed}; "
        f"preemptions={s.get('evictions', 0)}; deferred_ticks={deferred}"
    )
    _print_kv_walk(s)


def _print_kv_walk(s) -> None:
    """The share of the KV horizon the paged kernel walked (kernel on)."""
    if s.get("paged_kv_blocks_horizon"):
        walked, horizon = (
            s["paged_kv_blocks_walked"], s["paged_kv_blocks_horizon"]
        )
        print(
            f"  paged-kernel kv walk: {walked}/{horizon} blocks "
            f"({walked / horizon:.3f} of the horizon)"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="smoke-size reduced() widths (the default)")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="the published widths, weights in bf16")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to N layers (0 = the config's own)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="continuous batching over the paged KV pool")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of variable-length requests (--paged)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kernel", action="store_true",
                    help="route decode through the Pallas paged kernel")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=("bf16", "int8", "fp8"),
                    help="KV pool storage dtype: quantized pages are "
                         "dequantized inside the paged kernels (~2x "
                         "resident requests per device at int8)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree KV prefix reuse across requests")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="interleave N-token prefill chunks with decode "
                         "(0 = whole-prompt prefill at admission)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common K-token system prompt to every "
                         "request (makes --prefix-cache hits observable)")
    ap.add_argument("--trace", default="", choices=("", "poisson", "bursty"),
                    help="replay a timed arrival trace through the async "
                         "front-end instead of one submit-all drain "
                         "(requires --paged); prints p50/p99 TTFT/TPOT "
                         "and tick-exact SLO goodput")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="mean arrivals per engine tick for --trace "
                         "(poisson: exponential gaps; bursty: bursts of 4 "
                         "spaced to the same mean rate)")
    ap.add_argument("--qos", default="mixed",
                    choices=("interactive", "batch", "mixed"),
                    help="QoS population for --trace: all-interactive, "
                         "all-batch, or a 25%% batch mix")
    ap.add_argument("--slo-ticks", type=int, default=10,
                    help="goodput SLO for --trace: first token within this "
                         "many ticks of arrival")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="per-QoS-tier admission queue cap (0 = unbounded); "
                         "overflow raises QueueFull / defers trace arrivals")
    ap.add_argument("--spec", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per tick and "
                         "commit the verified run (greedy only — forces "
                         "temperature 0; requires --paged)")
    ap.add_argument("--drafter", default="ngram", choices=("ngram", "model"),
                    help="--spec drafter: model-free prompt lookup, or a "
                         "paired reduced same-family model "
                         "(spec.paired_drafter_cfg)")
    ap.add_argument("--mesh", default="",
                    help="DxM (data replicas x model shards), e.g. 2x2")
    ap.add_argument("--no-force-devices", dest="force_devices",
                    action="store_false", default=True,
                    help="don't force host platform device count for --mesh")
    args = ap.parse_args()

    data_par = model_par = 1
    if args.mesh:
        if not args.paged:
            ap.error("--mesh requires --paged (only the continuous-batching "
                     "engine serves sharded; the dense driver is unsharded)")
        data_par, model_par = (int(x) for x in args.mesh.lower().split("x"))
        if args.force_devices:
            # must land before jax initializes its backend (first device use)
            flags = os.environ.get("XLA_FLAGS", "")
            if "--xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags
                    + f" --xla_force_host_platform_device_count="
                    f"{data_par * model_par}"
                ).strip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ASSIGNED, launch_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_serve_mesh
    from repro.models import Runtime, init_params, init_params_as
    from repro.serve import (
        EngineConfig,
        ReplicatedServeEngine,
        ServeEngine,
        paged_supported,
    )
    from repro.train import generate

    assert args.arch in ASSIGNED, f"--arch must be one of {ASSIGNED}"
    enable_compile_cache()
    cfg = launch_config(args.arch, full=not args.reduced, layers=args.layers)
    mesh = make_serve_mesh(data_par, model_par) if args.mesh else None
    rt = Runtime(dtype=jnp.float32 if args.reduced else jnp.bfloat16, chunk_q=32)
    if args.reduced:
        params = init_params(cfg, jax.random.PRNGKey(args.seed))
    else:
        shardings = None
        if mesh is not None:
            from repro.sharding.specs import param_specs, with_sharding

            p_shape = jax.eval_shape(
                lambda: init_params(cfg, jax.random.PRNGKey(0))
            )
            shardings = with_sharding(mesh, param_specs(cfg, p_shape, mesh))
        params = init_params_as(
            cfg, jax.random.PRNGKey(args.seed), jnp.bfloat16, shardings
        )
    rng = np.random.RandomState(args.seed)

    if args.paged:
        paged = paged_supported(cfg)
        if not paged:
            print(f"{cfg.name}: family {cfg.family!r} -> dense fallback")
        if args.spec and not paged:
            ap.error(f"--spec needs a paged-capable family, not {args.arch}")
        max_prompt = args.prompt_len + args.shared_prefix
        temperature = 0.0 if args.spec else args.temperature
        ecfg = EngineConfig.capacity(
            max_prompt + cfg.frontend_tokens, args.new_tokens,
            slots=args.slots, page_size=args.page_size, headroom=2.0,
            kv_dtype=args.kv_dtype,
        ).engine(
            temperature=temperature, seed=args.seed,
            use_kernel=args.kernel,
            prefill_bucket=args.page_size,  # random lengths: bound compiles
            prefix_cache=args.prefix_cache,
            prefill_chunk=args.prefill_chunk,
            max_queue=args.max_queue,
            spec_tokens=args.spec, spec_drafter=args.drafter,
        )
        draft_params = draft_cfg = None
        if args.spec and args.drafter == "model":
            from repro.serve import paired_drafter_cfg

            draft_cfg = paired_drafter_cfg(cfg)
            draft_params = init_params(
                draft_cfg, jax.random.PRNGKey(args.seed + 1)
            )
        if mesh is not None:
            eng = ReplicatedServeEngine(
                cfg, params, rt, ecfg, mesh=mesh, paged=paged,
                draft_params=draft_params, draft_cfg=draft_cfg,
            )
        else:
            eng = ServeEngine(
                cfg, params, rt, ecfg, paged=paged,
                draft_params=draft_params, draft_cfg=draft_cfg,
            )
        if args.trace:
            # the dense fallback works too: _step_dense is one tick
            _replay_cli(args, cfg, eng)
            return
        sys_prompt = rng.randint(
            0, cfg.vocab_size, (args.shared_prefix,)
        ).astype(np.int32)
        rids = []
        for _ in range(args.requests):
            plen = rng.randint(max(args.prompt_len // 2, 1), args.prompt_len + 1)
            tokens = np.concatenate([
                sys_prompt,
                rng.randint(0, cfg.vocab_size, (plen,)).astype(np.int32),
            ])
            fe = (
                rng.randn(cfg.frontend_tokens, cfg.d_model).astype(np.float32)
                if cfg.frontend is not None else None
            )
            rids.append(eng.submit(tokens, args.new_tokens, frontend_embeds=fe))
        out = eng.run()
        s = eng.stats
        # per-run mean (submit -> first token); stats["ttft_s"] accumulates
        # per-rid entries across runs on a reused engine
        ttft = s["run_mean_ttft_s"]
        print(
            f"{cfg.name} [{cfg.family}] paged={paged}"
            + (f" mesh={data_par}x{model_par}" if mesh is not None else "")
            + f": {sum(len(v) for v in out.values())} tokens, "
            f"{s['tokens_per_s']:.1f} tok/s, mean TTFT {ttft * 1e3:.0f}ms, "
            f"evictions={s.get('evictions', 0)}"
        )
        from repro.kernels.paged_attention.quant import kv_token_bytes

        kv_maps = (
            [e.stats.get("kv_bytes", {}) for e in eng.engines]
            if mesh is not None else [s.get("kv_bytes", {})]
        )
        per_req = [b for m in kv_maps for b in m.values()]
        cap_factor = (
            kv_token_bytes(cfg.n_kv_heads, cfg.head_dim, "bf16")
            / kv_token_bytes(cfg.n_kv_heads, cfg.head_dim, args.kv_dtype)
        )
        print(
            f"  kv-pool: dtype={args.kv_dtype}, "
            f"bytes/request={np.mean(per_req):.0f} (mean over {len(per_req)}), "
            f"capacity_factor_vs_bf16={cap_factor:.2f}x"
        )
        _print_kv_walk(s)
        if args.spec:
            print(
                f"  spec: k={args.spec} drafter={args.drafter} "
                f"accept_rate={s.get('spec_accept_rate', 0.0):.2f} "
                f"accepted_per_verify="
                f"{s.get('spec_accepted_per_verify', 1.0):.2f}"
            )
        if args.prefix_cache and "prefix_lookups" in s:
            hit_rate = s["prefix_hits"] / max(s["prefix_lookups"], 1)
            cached_frac = (
                s["prefix_cached_tokens"] / max(s.get("prompt_tokens", 1), 1)
            )
            print(
                f"  prefix-cache: hit_rate={hit_rate:.2f} "
                f"({s['prefix_hits']}/{s['prefix_lookups']}), "
                f"cached_token_fraction={cached_frac:.2f}, "
                f"prefill_chunks={s.get('prefill_chunks', 0)}, "
                f"mean_ttft_ms={ttft * 1e3:.1f}"
            )
        if mesh is not None:
            print(
                f"  replicas={s.get('replica_requests')} "
                f"kv_pool_bytes_per_device={s.get('kv_pool_bytes_per_device')}"
            )
        for rid in rids[:2]:
            print(f"  req[{rid}]: {out[rid][:12].tolist()}...")
        return

    batch = {
        "tokens": jnp.asarray(
            rng.randint(0, cfg.vocab_size, (args.batch, args.prompt_len)),
            jnp.int32,
        )
    }
    if cfg.frontend is not None:
        batch["frontend_embeds"] = jnp.asarray(
            rng.randn(args.batch, cfg.frontend_tokens, cfg.d_model), jnp.float32
        )
    t0 = time.perf_counter()
    tokens, _ = generate(
        cfg, params, batch, rt, max_new_tokens=args.new_tokens,
        temperature=args.temperature, seed=args.seed,
    )
    dt = time.perf_counter() - t0
    print(f"{cfg.name} [{cfg.family}]: {tokens.size} tokens in {dt:.1f}s")
    for b in range(min(2, args.batch)):
        print(f"  seq[{b}]: {tokens[b].tolist()}")


if __name__ == "__main__":
    main()

"""Continuous-batching serve engine over the paged KV-cache pool.

Execution model
---------------
A fixed number of decode *slots* (the jitted batch dimension) is fed from a
FIFO scheduler. Each admitted request is prefilled alone (B=1, cached
compiled prefill), its KV scattered into pool pages through its block table,
and its first token sampled (that wall time is the request's TTFT). Decode
then runs in jitted ``lax.scan`` chunks of ``inner_steps`` single-token
steps over ALL slots at once — every slot at its own depth, masked by a
per-slot ``remaining`` counter — with the host only intervening between
chunks to retire finished requests (freeing their pages) and admit new ones
into the vacated slots. Per-slot sample keys + step counters make each
request's token stream independent of what else shares the batch, so engine
output is identical to running the request alone (the dense path can only
promise that for greedy decoding).

Tick API + async intake
-----------------------
The drain loop is reentrant: one ``step()`` is a complete engine tick
(admit -> top-up -> one jitted chunk -> collect -> retire) that an external
driver — ``serve.frontend.AsyncFrontend`` — can call between its own
events. ``run()`` is now just ``run_begin(); while busy: step();
run_finalize()``. Requests carry a QoS tier (interactive beats batch at
admission, strictly and deterministically — see ``serve.scheduler``) and an
optional per-token callback ``on_token(rid, tokens, done)`` invoked as
tokens are collected from each chunk; delivered-token counts survive
preemption, so a preempted-and-recomputed request never re-delivers tokens
it already streamed (decode is deterministic, the regenerated prefix is
identical). ``cancel(rid)`` removes a queued request or stops an in-flight
one mid-decode, freeing its pool pages immediately; it is safe to call
from inside an ``on_token`` callback (early stop). Latency accounting:
``stats["ttft_s"]`` is measured from ``submit()`` wall time on every path
(queue wait included; preempt-then-readmit spans the original submit),
while ``stats["prefill_s"]`` keeps the prefill compute time separately.

Families whose decode state is not a KV cache (SSM / RG-LRU recurrences,
enc-dec cross caches) fall back to the dense path (``paged=False``), grouped
into equal-prompt-length batches.

Speculative decoding
--------------------
With ``EngineConfig.spec_tokens = k`` a decode tick commits a VARIABLE-
length token run per slot instead of exactly one token: a drafter proposes
k tokens per slot (model-free prompt-lookup by default, or a paired small
same-family model — ``serve.spec``), one batched (k+1)-row call of the
paged forward verifies them (``models.lm.paged_forward`` with every row's
logits — the same function a decode step calls at T=1), and the
longest draft prefix matching the target's own argmax chain commits
together with the verify pass's bonus token — 1..k+1 tokens per slot per
tick. Greedy acceptance (``temperature`` must be 0) makes every committed
token the target's own argmax, so spec-on output is token-identical to
spec-off (and to running alone); draft quality only moves throughput.
Rejected rows roll back for free on device (their KV sits past the
committed length, masked and overwritten) and via ``PagePool.truncate``
host-side for the pool reservation under the optimistic policy. The
multi-token commit rides the existing emits contract: ``on_token``
streaming, TPOT/goodput accounting, cancel/preempt bookkeeping all see the
same per-slot token runs they would under one-token ticks.

Prefix cache + chunked prefill
------------------------------
With ``EngineConfig.prefix_cache`` a radix tree (``serve.prefix``) keeps
retired prompts' KV pages alive: a new request adopts the longest token-
exact cached prefix (refcount++ on the shared pages — zero prefill FLOPs
for the shared part) and only its uncached remainder is computed. With
``prefill_chunk`` the remainder is split into fixed-size chunks that run
*inside* the decode step: one jitted program executes a prefill chunk for
the admitting request AND ``inner_steps`` decode steps for every active
slot, so long prompts no longer stall in-flight decodes (continuous
batching stays continuous). Both features keep the batched == alone
guarantee: the paged-prefill path produces bit-identical logits to the
dense prefill (asserted in tests), and the per-slot sample streams are
untouched. Requests with a modality prefix (vision) fall back to the
legacy whole-prompt prefill — the radix key is token IDs and cannot see
image content.

Sharded serving
---------------
With ``Runtime.mesh`` set, one engine spans the mesh's ``model`` axis:
params are laid out by the Megatron rules in ``sharding.specs.param_specs``,
the KV page pools shard their kv-head axis (``paged_state_specs``) so KV
bytes per device shrink by the TP factor, and the paged-attention op runs
inside shard_map on per-shard head slices — only the final (vocab-sharded)
logits are gathered for sampling. Block tables, lengths, and every other
slot-addressing array stay replicated, so the host-side scheduler is
topology-blind. ``ReplicatedServeEngine`` adds the ``data`` axis: one engine
per data slice, with requests routed to the least-loaded replica
(``scheduler.ReplicaRouter``).
"""
from __future__ import annotations

import dataclasses
import math
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels.paged_attention.kernel import walked_blocks
from repro.models import Runtime, init_paged_state, paged_forward
from repro.models.layers import Params
from repro.models.stack import layer_specs, write_prefill_to_pool
from repro.serve import dense as dense_mod
from repro.serve import spec as spec_mod
from repro.serve.pool import PagePool, PoolExhausted
from repro.serve.prefix import PrefixCache
from repro.serve.sampling import SamplingPolicy
from repro.serve.scheduler import Request, Scheduler


def paged_supported(cfg: ArchConfig) -> bool:
    """Paged decode needs every mixer to be a KV-cache attention kind and no
    cross-attention cache (enc-dec)."""
    return (
        not cfg.is_encdec
        and cfg.n_heads > 0
        and all(k in ("attn", "local") for k in cfg.pattern)
    )


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 4        # decode batch width (jit-static)
    page_size: int = 16       # tokens per KV page (= kernel block size)
    num_pages: int = 129      # pool size incl. reserved null page 0
    max_len: int = 256        # per-request horizon; block-table width
    inner_steps: int = 8      # decode steps per jitted scan chunk
    temperature: float = 0.0
    seed: int = 0
    use_kernel: bool = False  # Pallas paged kernel vs jnp oracle gather
    policy: str = "reserve"   # admission policy (see serve.scheduler)
    # Pad prompts up to a multiple of this bucket before prefill, so distinct
    # prompt lengths share max_len/bucket compiled programs instead of one
    # XLA compile each (0 = exact shapes). Exactness: padded positions are
    # causally invisible, the engine prefills with full (un-windowed) caches
    # so no real token is ring-evicted by the padding, and padded KV is
    # either null-paged or overwritten before it can be attended — outputs
    # are unchanged for every attention head layout (MHA, GQA, and MQA
    # alike: the causal mask is head-agnostic; asserted across layouts in
    # tests/test_serve_engine.py). The one exception is MoE routing, which
    # sees pad tokens in its capacity count and can perturb token dropping
    # vs an exact-shape run — the engine warns on that combination (same
    # caveat applies to chunked prefill, whose chunk grid changes the
    # token population each router call sees).
    prefill_bucket: int = 0
    # Radix-tree KV prefix reuse: retired prompts' pages stay cached and
    # new requests adopt their longest token-exact cached prefix (COW/fork
    # machinery of the pool; LRU eviction under pressure).
    prefix_cache: bool = False
    # Split uncached prompt remainders into chunks of this many tokens,
    # each executed INSIDE a decode step (one jitted program = 1 prefill
    # chunk + inner_steps decode steps over all slots), bounding the decode
    # stall a long prompt can cause. 0 with prefix_cache on still routes
    # through the chunked path using page_size-ish chunks (see
    # ``chunk_tokens``); 0 with prefix_cache off = legacy whole-prompt
    # prefill at admission.
    prefill_chunk: int = 0
    # KV pool storage dtype: "bf16" (native — pages stored at the runtime
    # compute dtype), "int8", or "fp8". Quantized pools store ~0.5x the
    # bytes per token (codes + per-(slot, head) f32 scales), dequantized
    # inside the paged kernels' page gather; each token row is quantized
    # exactly once at write time, so batched==alone determinism holds at
    # any fixed kv_dtype (see kernels.paged_attention.quant).
    kv_dtype: str = "bf16"
    # Admission backpressure: bound on EACH QoS tier's wait queue (0 =
    # unbounded). submit() raises scheduler.QueueFull at the bound; the
    # async front-end turns that into an awaitable retry.
    max_queue: int = 0
    # Speculative decoding: draft spec_tokens tokens per decode tick and
    # verify them in ONE batched (k+1)-row pass through the paged-prefill
    # write-then-attend path; a prefix of matching drafts plus the verify
    # pass's own next token commit together (1..k+1 tokens per slot per
    # tick). Greedy acceptance only (temperature must be 0): every
    # committed token equals the target model's own argmax, so the stream
    # is the target's greedy stream and batched==alone survives. 0 = off.
    spec_tokens: int = 0
    # Drafter kind: "ngram" (model-free prompt lookup, default) or "model"
    # (a paired small same-family config; pass draft_params to ServeEngine).
    spec_drafter: str = "ngram"
    # Longest n-gram the prompt-lookup drafter matches on.
    spec_ngram: int = 3
    # Keep each request's first-token logits (V,) f32 on the host in
    # ``ServeEngine.first_logits`` — for comparing kernel and oracle
    # engines; off in serving (a vocab-sized copy per request).
    keep_first_logits: bool = False

    @property
    def chunk_tokens(self) -> int:
        """Effective prefill-chunk width for the paged-prefill path (one
        compiled chunk shape: ragged tails are right-padded to this)."""
        return self.prefill_chunk or self.prefill_bucket or self.page_size

    @classmethod
    def capacity(
        cls,
        max_prompt_total: int,
        max_new: int,
        *,
        slots: Optional[int] = None,
        pool_bytes: Optional[int] = None,
        cfg=None,
        page_size: int = 16,
        headroom: float = 1.0,
        kv_dtype: str = "bf16",
        native_itemsize: int = 2,
    ) -> "Capacity":
        """THE capacity arithmetic, in one direction-agnostic call.

        Give ``slots`` to size a pool for that many worst-case requests
        (prompt incl. any frontend prefix + ``max_new``; the reservation
        policy needs ``horizon - 1`` tokens per request), or ``pool_bytes``
        to size the SLOT count to an HBM budget at ``kv_dtype`` page
        pricing (``pool.kv_page_bytes``, incl. scale buffers — the
        resident-request capacity quantized pools multiply). Exactly one of
        the two. ``headroom`` > 1 over-provisions pages for queue churn.
        Byte pricing needs the model ``cfg`` (required with ``pool_bytes``;
        optional with ``slots``, where the byte fields report 0 without
        it). Returns a :class:`Capacity`; call ``.engine(**kw)`` on it for
        the ``EngineConfig``."""
        if (slots is None) == (pool_bytes is None):
            raise ValueError("pass exactly one of slots= / pool_bytes=")
        if pool_bytes is not None and cfg is None:
            raise ValueError("pool_bytes sizing needs cfg= for byte pricing")
        horizon = max_prompt_total + max_new
        max_len = -(-horizon // page_size) * page_size
        pages_per_request = max_len // page_size
        page_bytes = 0
        if cfg is not None:
            from repro.serve.pool import kv_page_bytes

            page_bytes = kv_page_bytes(
                page_size, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers,
                kv_dtype, native_itemsize,
            )
        if slots is not None:
            num_pages = 1 + math.ceil(slots * pages_per_request * headroom)
        else:
            # The pool allocates 1 + slots * per_slot pages and the reserved
            # null page costs page_bytes like any other, so it is charged
            # against the budget too — otherwise the pool overspends
            # pool_bytes by up to one page. (The max(1, .) floor still
            # returns a working 1-slot config for budgets too small to
            # honor; callers sizing to a real HBM budget pass enough.)
            budget_pages = pool_bytes // page_bytes - 1    # null page charged
            per_slot = math.ceil(pages_per_request * headroom)
            slots = max(1, int(budget_pages) // per_slot)
            num_pages = 1 + slots * per_slot
        return Capacity(
            slots=slots, page_size=page_size, max_len=max_len,
            pages_per_request=pages_per_request, num_pages=num_pages,
            bytes_per_token=(page_bytes // page_size if page_bytes else 0),
            page_bytes=page_bytes, pool_bytes=num_pages * page_bytes,
            kv_dtype=kv_dtype,
        )


@dataclasses.dataclass(frozen=True)
class Capacity:
    """Named result of :meth:`EngineConfig.capacity` — the worst-case-request
    -> pages -> pool arithmetic as one inspectable value instead of fields
    scattered across an ``EngineConfig``."""

    slots: int                # concurrent worst-case requests
    page_size: int
    max_len: int              # per-request horizon, page-aligned
    pages_per_request: int    # pages one worst-case request spans (no headroom)
    num_pages: int            # pool size INCLUDING the reserved null page 0
    bytes_per_token: int      # KV bytes/token across layers (0 without cfg)
    page_bytes: int           # bytes_per_token * page_size (0 without cfg)
    pool_bytes: int           # num_pages * page_bytes (null page included)
    kv_dtype: str

    def engine(self, **kw) -> EngineConfig:
        """The ``EngineConfig`` realizing this capacity plan; ``kw`` passes
        every non-capacity field through (inner_steps, policy, ...)."""
        kw.setdefault("kv_dtype", self.kv_dtype)
        return EngineConfig(
            max_slots=self.slots, page_size=self.page_size,
            num_pages=self.num_pages, max_len=self.max_len, **kw,
        )


@dataclasses.dataclass
class _Slot:
    rid: int
    sid: int                  # pool sequence id
    req: Request
    order: int                # admission order (eviction picks the youngest)
    phase: str = "decode"     # "prefill" while chunks of the prompt remain
    pf_next: int = 0          # next uncomputed prompt position (chunked path)
    t_admit: float = 0.0      # admission wall time (TTFT under chunking)


# Module-wide compile caches: fresh ServeEngine instances with an identical
# (cfg, rt, engine-config) key reuse the jitted chunk fn instead of
# retracing (same policy as repro.serve.dense's prefill/loop cache). The
# page pools are donated in both fns — per-chunk/per-admission updates land
# in place instead of double-buffering the whole KV pool (the donation is a
# no-op on CPU backends, which jax reports with a one-time warning).
_CHUNK_CACHE: Dict[Any, Any] = {}
_SCATTER = jax.jit(
    write_prefill_to_pool, static_argnames=("page_size",), donate_argnums=(0,)
)
_COPY_PAGES = jax.jit(
    lambda caches, src, dst: jax.tree.map(
        lambda leaf: leaf.at[:, dst].set(leaf[:, src]), caches
    ),
    donate_argnums=(0,),
)


def dense_kv_bytes(cfg: ArchConfig, rt: Runtime, total: int) -> int:
    """Dense per-request cache footprint for a ``total``-token horizon: each
    layer holds its full ``cache_len`` extent regardless of request length
    (window-truncated local layers, rough recurrent-state share)."""
    from repro.models.stack import layer_specs

    itemsize = jnp.dtype(rt.dtype).itemsize
    specs = layer_specs(cfg, seq_len=total, long_variant=rt.long_variant)
    tokens = sum(s.cache_len for s in specs if s.kind in ("attn", "local"))
    per_token = cfg.n_kv_heads * cfg.head_dim * 2 * itemsize
    rec = sum(
        1 for s in specs if s.kind not in ("attn", "local")
    ) * cfg.d_model * 4 * itemsize
    return tokens * per_token + rec


class ServeEngine:
    def __init__(
        self,
        cfg: ArchConfig,
        params: Params,
        rt: Optional[Runtime] = None,
        engine: EngineConfig = EngineConfig(),
        paged: Optional[bool] = None,
        draft_params: Optional[Params] = None,
        draft_cfg: Optional[ArchConfig] = None,
    ):
        from repro.kernels.paged_attention import quant

        self.cfg = cfg
        self.params = params
        self.ecfg = engine
        rt = rt if rt is not None else Runtime()
        self.rt = rt.replace(
            use_paged_kernel=engine.use_kernel or rt.use_paged_kernel,
            kv_dtype=quant.normalize_kv_dtype(engine.kv_dtype or rt.kv_dtype),
        )
        if paged is None:
            paged = paged_supported(cfg)
        if paged and not paged_supported(cfg):
            raise ValueError(
                f"{cfg.name}: family {cfg.family!r} has non-KV decode state; "
                "use paged=False (dense fallback)"
            )
        self.paged = paged
        self._policy = SamplingPolicy(
            temperature=engine.temperature, vocab=cfg.vocab_size,
            seed=engine.seed,
        )
        if engine.spec_tokens:
            if not paged:
                raise ValueError(
                    "speculative decoding needs the paged engine (the "
                    "verify pass is the paged-prefill path); spec_tokens=0 "
                    "for dense-fallback families"
                )
            if engine.temperature != 0.0:
                raise ValueError(
                    "speculative decoding is greedy-acceptance only: "
                    "spec_tokens>0 requires temperature=0.0"
                )
            if engine.spec_drafter not in spec_mod.DRAFTER_KINDS:
                raise ValueError(
                    f"spec_drafter={engine.spec_drafter!r} not in "
                    f"{spec_mod.DRAFTER_KINDS}"
                )
            if engine.spec_drafter == "model":
                if engine.prefix_cache or engine.prefill_chunk:
                    raise ValueError(
                        "spec_drafter='model' needs the legacy whole-prompt "
                        "admission prefill (the drafter's KV is built "
                        "there); prefix_cache/prefill_chunk admit without "
                        "recompute, leaving the drafter blind — use the "
                        "ngram drafter with those modes"
                    )
                if cfg.frontend is not None:
                    raise ValueError(
                        "spec_drafter='model': modality-prefix embeddings "
                        "are sized for the target d_model and cannot feed "
                        "the reduced drafter — use the ngram drafter"
                    )
                if draft_params is None:
                    raise ValueError(
                        "spec_drafter='model' needs draft_params (init the "
                        "paired config from spec.paired_drafter_cfg(cfg))"
                    )
        if self.rt.mesh is not None and params is not None:
            # Megatron layout over the mesh's `model` axis; leaves whose
            # dims don't divide stay replicated (specs.py guards), so any
            # reduced config lowers on any mesh.
            from repro.sharding.specs import param_specs, with_sharding

            shardings = with_sharding(
                self.rt.mesh,
                param_specs(cfg, jax.eval_shape(lambda: params), self.rt.mesh),
            )
            self.params = jax.tree.map(jax.device_put, params, shardings)
        self.pool = PagePool(engine.num_pages, engine.page_size)
        self.scheduler = Scheduler(
            policy=engine.policy, max_queue=engine.max_queue
        )
        self._next_rid = 0
        self._admit_count = 0
        self._slots: List[Optional[_Slot]] = [None] * engine.max_slots
        self._outputs: Dict[int, List[int]] = {}
        self._callbacks: Dict[int, Any] = {}   # rid -> on_token(rid, toks, done)
        self._emitted: Dict[int, int] = {}     # tokens DELIVERED per rid
        self._completed_run: set = set()
        self.first_logits: Dict[int, np.ndarray] = {}
        self._run_t0: Optional[float] = None   # open measurement window
        self.stats: Dict[str, Any] = {
            "ttft_s": {}, "prefill_s": {}, "kv_bytes": {},
        }
        if self.paged:
            self._dev = init_paged_state(
                cfg, engine.max_slots, self.rt,
                num_pages=engine.num_pages, page_size=engine.page_size,
                max_len=engine.max_len,
            )
            B = engine.max_slots
            extras = dict(
                remaining=jnp.zeros((B,), jnp.int32),
                tok=jnp.zeros((B,), jnp.int32),
                keys=jnp.stack([jax.random.PRNGKey(0)] * B),
                steps=jnp.zeros((B,), jnp.int32),
            )
            if self.rt.mesh is not None:
                # commit replicated so host-side .at[].set updates stay on
                # the mesh's device set (mixing with sharded pool args in
                # one jit otherwise errors with incompatible devices)
                from jax.sharding import NamedSharding, PartitionSpec

                extras = {
                    k: jax.device_put(
                        v,
                        NamedSharding(
                            self.rt.mesh, PartitionSpec(*([None] * v.ndim))
                        ),
                    )
                    for k, v in extras.items()
                }
            self._dev.update(extras)
            self.stats["kv_pool_bytes_per_device"] = self.kv_pool_bytes_per_device()
            if self.rt.use_paged_kernel:
                # KV blocks the paged kernel walks, and those a walk over
                # every slot's whole horizon would cover, summed over the
                # target model's attention layers (host-side, per tick)
                self.stats["paged_kv_blocks_walked"] = 0
                self.stats["paged_kv_blocks_horizon"] = 0
                windows = [
                    s.window for s in layer_specs(
                        cfg, seq_len=engine.max_len,
                        long_variant=self.rt.long_variant,
                    )
                ]
                # (window, layers that share it)
                self._windows = [(w, windows.count(w)) for w in set(windows)]
            # key only on what the trace depends on (seed/policy are
            # host-side; self.rt already folds in use_kernel)
            ckey = (
                cfg, self.rt, engine.max_slots, engine.page_size,
                engine.num_pages, engine.max_len, engine.inner_steps,
                engine.temperature, engine.spec_tokens,
            )  # seed/policy/prefill_bucket are host-side only
            if ckey not in _CHUNK_CACHE:
                _CHUNK_CACHE[ckey] = self._build_chunk_fn()
            self._chunk_fn = _CHUNK_CACHE[ckey]
            self._scatter_fn = _SCATTER
            if engine.prefix_cache or engine.prefill_chunk:
                # one fused fn handles any chunk width (jit specializes on
                # the p_tokens shape; the engine only ever passes one)
                fkey = ckey + ("fused",)
                if fkey not in _CHUNK_CACHE:
                    _CHUNK_CACHE[fkey] = (
                        self._build_fused_fn(), self._build_prefill_fn()
                    )
                self._fused_fn, self._prefill_fn = _CHUNK_CACHE[fkey]
            if engine.spec_tokens:
                vkey = ckey + ("verify",)
                if vkey not in _CHUNK_CACHE:
                    _CHUNK_CACHE[vkey] = self._build_verify_fn()
                self._verify_fn = _CHUNK_CACHE[vkey]
                if engine.spec_drafter == "model":
                    # drafter caches share the TARGET's block tables and
                    # page geometry (same page ids index its own smaller
                    # per-layer pools), so pool accounting is done once;
                    # only the caches leafset and the host-side lengths /
                    # catch-up trackers are drafter-private. The drafter
                    # never shards: it is reduced() — tiny — and its pools
                    # must not entangle the mesh donation of the target's.
                    self._draft_cfg = (
                        draft_cfg if draft_cfg is not None
                        else spec_mod.paired_drafter_cfg(cfg)
                    )
                    self._draft_params = draft_params
                    self._draft_rt = self.rt.replace(mesh=None)
                    self._draft_dev = {
                        "caches": init_paged_state(
                            self._draft_cfg, B, self._draft_rt,
                            num_pages=engine.num_pages,
                            page_size=engine.page_size,
                            max_len=engine.max_len,
                        )["caches"]
                    }
                    self._draft_len = np.zeros(B, np.int64)
                    self._spec_catchup = np.full(B, -1, np.int64)
                    dkey = ckey + ("draft", self._draft_cfg)
                    if dkey not in _CHUNK_CACHE:
                        _CHUNK_CACHE[dkey] = self._build_draft_fn()
                    self._draft_fn = _CHUNK_CACHE[dkey]
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(self.pool)
            if self.paged and engine.prefix_cache else None
        )
        if (
            self.paged and cfg.ffn_kind == "moe"
            and (engine.prefill_bucket or engine.prefix_cache
                 or engine.prefill_chunk)
        ):
            warnings.warn(
                f"{cfg.name}: MoE routing counts pad/chunk tokens in its "
                "expert capacity, so bucketed or chunked prefill is not "
                "guaranteed token-exact vs an exact-shape run (attention "
                "itself is exact for MHA/GQA/MQA; see EngineConfig). "
                "Identical engine configs remain deterministic.",
                stacklevel=2,
            )

    # ------------------------------------------------------------- public
    def submit(
        self,
        tokens: np.ndarray,
        max_new: int,
        frontend_embeds: Optional[np.ndarray] = None,
        qos: str = "interactive",
        on_token=None,
    ) -> int:
        """Enqueue a request; returns its rid. ``qos`` picks the admission
        tier (``interactive`` | ``batch``); ``on_token(rid, tokens, done)``
        is called with each newly delivered token batch (``done`` is None
        while streaming, then ``"complete"`` / ``"cancelled"`` exactly
        once). Raises before ANY engine state changes — capacity rejects
        (ValueError) and backpressure (scheduler.QueueFull) leave the rid
        counter, queues, and callbacks untouched, which is what makes
        replica routing transactional one level up."""
        assert max_new >= 1
        req = Request(
            rid=self._next_rid,
            tokens=np.asarray(tokens, np.int32).reshape(-1),
            max_new=int(max_new),
            frontend_embeds=frontend_embeds,
            qos=qos,
            t_submit=time.perf_counter(),
        )
        if self.paged:
            total = self._prompt_total(req) + req.max_new - 1
            if total > self.ecfg.max_len:
                raise ValueError(
                    f"request needs {total} tokens > max_len={self.ecfg.max_len}"
                )
            if self.pool.pages_for(total) > self.pool.budget:
                raise ValueError(
                    f"request needs {self.pool.pages_for(total)} pages "
                    f"> pool budget {self.pool.budget}"
                )
        self.scheduler.add(req)            # may raise QueueFull
        self._next_rid += 1
        if on_token is not None:
            self._callbacks[req.rid] = on_token
        return req.rid

    @property
    def busy(self) -> bool:
        """Work pending: queued requests or seated decode slots."""
        if self.paged:
            return bool(
                len(self.scheduler)
                or any(s is not None for s in self._slots)
            )
        return bool(len(self.scheduler))

    def run_begin(self) -> None:
        """Open a measurement window: per-run counters snapshot here so a
        second submit()/run() cycle on the same engine reports its own
        throughput/latency, not a mix with the previous run's."""
        self._completed_run = set()
        self._run_t0 = time.perf_counter()
        self._run_admit0 = self._admit_count
        self._run_evict0 = self.stats.get("evictions", 0)
        self._run_discard0 = self.stats.get("discarded_tokens", 0)
        self._run_decode_tokens = 0
        self._run_spec0 = tuple(
            self.stats.get(key, 0) for key in (
                "spec_verify_calls", "spec_drafted_tokens",
                "spec_accepted_tokens",
            )
        )

    def step(self) -> Dict[str, Any]:
        """ONE engine tick: admit -> top-up -> one jitted chunk -> collect
        -> retire. Reentrant and externally drivable (the async front-end
        calls this between its own events); an idle engine returns
        ``busy=False`` without touching the device. Opens a measurement
        window implicitly if none is open."""
        if not self.busy:
            return {"busy": False, "finished": [], "decoded": 0}
        if self._run_t0 is None:
            self.run_begin()
        if not self.paged:
            return self._step_dense()
        self._admit_free_slots()
        self._topup_or_evict()
        emits, remaining = self._device_step()
        self._run_decode_tokens += self._collect(emits)
        finished = self._retire(remaining)
        return {
            "busy": True,
            "finished": finished,
            "decoded": int((emits >= 0).sum()),
        }

    def run_finalize(self) -> Dict[int, np.ndarray]:
        """Close the measurement window: compute per-run throughput/latency
        stats and return {rid: generated tokens} for the requests completed
        SINCE run_begin(). No-op ({}) when no window is open."""
        if self._run_t0 is None:
            return {}
        wall = time.perf_counter() - self._run_t0
        # throughput counts DELIVERED tokens; work thrown away by
        # preemption is reported separately, not inflated into tokens/s
        discarded = (
            self.stats.get("discarded_tokens", 0) - self._run_discard0
        )
        n_prefill = (self._admit_count - self._run_admit0) - (
            self.stats.get("evictions", 0) - self._run_evict0
        )
        decode_tokens = self._run_decode_tokens
        self.stats["decode_tokens"] = decode_tokens - discarded
        self.stats["wall_s"] = wall
        self.stats["tokens_per_s"] = (
            decode_tokens - discarded + n_prefill
        ) / max(wall, 1e-9)
        self.stats["pool_high_water_pages"] = self.pool.high_water
        if self.ecfg.spec_tokens:
            # run-window acceptance stats: rate = accepted drafts over
            # drafted; accepted-per-verify adds the bonus token (mean
            # committed run length per verify call, 1..k+1)
            v0, d0, a0 = self._run_spec0
            verifies = self.stats.get("spec_verify_calls", 0) - v0
            drafted = self.stats.get("spec_drafted_tokens", 0) - d0
            acc = self.stats.get("spec_accepted_tokens", 0) - a0
            self.stats["spec_accept_rate"] = acc / max(drafted, 1)
            self.stats["spec_accepted_per_verify"] = (
                (acc + verifies) / max(verifies, 1)
            )
        if self.prefix is not None:
            self.stats.update(self.prefix.stats())
        run_rids = sorted(self._completed_run)
        # per-run latency aggregates: benches must read these (or index
        # ttft_s by this run's rids) — never average the accumulated
        # per-rid dict across runs
        ttfts = [
            self.stats["ttft_s"][r] for r in run_rids
            if r in self.stats["ttft_s"]
        ]
        self.stats["run_completed"] = len(run_rids)
        self.stats["run_mean_ttft_s"] = (
            float(np.mean(ttfts)) if ttfts else 0.0
        )
        self._run_t0 = None
        return {
            rid: np.asarray(self._outputs[rid], np.int32)
            for rid in run_rids
        }

    def run(self) -> Dict[int, np.ndarray]:
        """Drain the queue; returns {rid: generated tokens (max_new,)} for
        the requests completed by THIS call (the engine is reusable —
        submit more and run again; ``self.stats`` throughput fields are
        likewise per-run, while the per-rid dicts accumulate).
        """
        self.run_begin()
        while self.busy:
            self.step()
        return self.run_finalize()

    def cancel(self, rid: int) -> bool:
        """Cancel a request: remove it from the wait queue, or stop it
        mid-flight and free its pool pages immediately. Already-delivered
        tokens stand; the request's callback (if any) gets a final
        ``done="cancelled"`` event. Returns False for unknown or already-
        finished rids. Safe to call from inside an ``on_token`` callback
        (early stop) — the current tick's collect/retire skip the vacated
        slot. Dense fallback: only still-queued requests can be cancelled
        (a launched dense batch is one compiled call)."""
        req = self.scheduler.cancel(rid)
        if req is not None:
            self.stats["cancelled"] = self.stats.get("cancelled", 0) + 1
            self._deliver_done(rid, "cancelled")
            return True
        if not self.paged:
            return False
        for slot_id, slot in enumerate(self._slots):
            if slot is None or slot.rid != rid:
                continue
            if (
                self.prefix is not None and self._use_chunked(slot.req)
                and slot.phase == "decode"
            ):
                # the full prompt was computed — its pages are as cacheable
                # as a retired request's (generated-token pages stay out)
                n_full = slot.req.prompt_len // self.ecfg.page_size
                self.prefix.insert(
                    slot.req.tokens,
                    self.pool.seq_pages(slot.sid)[:n_full],
                )
            self.pool.free(slot.sid)
            d = self._dev
            d["tables"] = d["tables"].at[slot_id].set(0)
            d["lengths"] = d["lengths"].at[slot_id].set(0)
            d["remaining"] = d["remaining"].at[slot_id].set(0)
            self._slots[slot_id] = None
            self.stats["cancelled"] = self.stats.get("cancelled", 0) + 1
            self._deliver_done(rid, "cancelled")
            return True
        return False

    # -------------------------------------------------- token delivery
    def _deliver(self, rid: int) -> None:
        """Push not-yet-delivered output tokens to the request's callback.
        ``_emitted`` tracks the delivered count independently of
        ``_outputs`` (which eviction clears), so a preempted request whose
        deterministic recompute regenerates the same prefix never
        re-delivers tokens the consumer already saw."""
        toks = self._outputs.get(rid)
        if toks is None:
            return
        sent = self._emitted.get(rid, 0)
        if len(toks) <= sent:
            return
        fresh = toks[sent:]
        self._emitted[rid] = len(toks)
        cb = self._callbacks.get(rid)
        if cb is not None:
            cb(rid, list(fresh), None)

    def _deliver_done(self, rid: int, reason: str) -> None:
        self._deliver(rid)
        cb = self._callbacks.pop(rid, None)
        self._emitted.pop(rid, None)
        if cb is not None:
            cb(rid, [], reason)

    # ----------------------------------------------------------- internals
    def _prompt_total(self, req: Request) -> int:
        extra = (
            self.cfg.frontend_tokens if self.cfg.frontend == "vision" else 0
        )
        return req.prompt_len + extra

    def _kv_bytes_per_page(self) -> int:
        from repro.serve.pool import kv_page_bytes

        return kv_page_bytes(
            self.ecfg.page_size, self.cfg.n_kv_heads, self.cfg.head_dim,
            self.cfg.n_layers, self.rt.kv_dtype,
            jnp.dtype(self.rt.dtype).itemsize,
        )

    def kv_pool_bytes_per_device(self) -> int:
        """Bytes of KV pool resident on ONE device — the capacity bound the
        tensor-parallel sharding relaxes. Computed from the actual shard
        shapes, so it reflects replication fallbacks exactly."""
        if not self.paged:
            return 0
        total = 0
        for leaf in jax.tree.leaves(self._dev["caches"]):
            shape = (
                leaf.sharding.shard_shape(leaf.shape)
                if hasattr(leaf, "sharding") else leaf.shape
            )
            total += int(np.prod(shape)) * leaf.dtype.itemsize
        return total

    @property
    def outstanding_tokens(self) -> int:
        """Token-weighted load: queued work plus pool-resident sequences."""
        queued = self.scheduler.queued_tokens(self._prompt_total)
        return queued + self.pool.tokens_in_use

    def _decode_scan_fn(self):
        """Traceable body shared by the decode-only and fused chunk fns:
        ``inner_steps`` T=1 paged forwards, each slot at its own depth."""
        cfg, rt, ecfg = self.cfg, self.rt, self.ecfg
        policy = self._policy

        def chunk(params, caches, tables, lengths, remaining, tok, keys, steps):
            def step(carry, _):
                caches, lengths, rem, tok, steps = carry
                active = rem > 0
                act = active.astype(jnp.int32)
                logits, caches = paged_forward(
                    cfg, params, caches, tables, tok[:, None], lengths, act,
                    rt, ecfg.max_len,
                )
                nxt = policy.sample_slots(logits, keys, steps)
                emit = jnp.where(active, nxt, -1)
                tok = jnp.where(active, nxt, tok)
                return (caches, lengths + act, rem - act, tok, steps + act), emit

            (caches, lengths, remaining, tok, steps), emits = jax.lax.scan(
                step, (caches, lengths, remaining, tok, steps), None,
                length=ecfg.inner_steps,
            )
            return caches, lengths, remaining, tok, steps, emits

        return chunk

    def _prefill_chunk_fn(self):
        """Traceable body shared by the fused and prefill-only fns: one
        prefill chunk of the request seated in ``p_slot``. Returns (caches,
        logits (V,) at the chunk's last valid row)."""
        cfg, rt, ecfg = self.cfg, self.rt, self.ecfg

        def prefill_chunk(
            params, caches, tables, p_tokens, p_slot, p_start, p_len
        ):
            row = jax.lax.dynamic_slice_in_dim(tables, p_slot, 1, axis=0)
            logits, caches = paged_forward(
                cfg, params, caches, row, p_tokens[None], p_start[None],
                p_len[None], rt, ecfg.max_len,
            )
            return caches, logits[0]

        return prefill_chunk

    def _build_chunk_fn(self):
        # caches update in place
        return jax.jit(self._decode_scan_fn(), donate_argnums=(1,))

    def _build_fused_fn(self):
        """One jitted program = one prefill chunk for the admitting request
        + ``inner_steps`` decode steps for every active slot (the prefilling
        slot sits inactive in the decode scan: remaining == 0). Disjoint
        page sets keep the two halves independent — the chunk writes only
        its own sequence's pages, decode slots read only theirs, and shared
        (adopted) prefix pages are read-only for both."""
        decode_scan = self._decode_scan_fn()
        prefill_chunk = self._prefill_chunk_fn()

        def fused(
            params, caches, tables, lengths, remaining, tok, keys, steps,
            p_tokens, p_slot, p_start, p_len,
        ):
            caches, pf_logits = prefill_chunk(
                params, caches, tables, p_tokens, p_slot, p_start, p_len
            )
            caches, lengths, remaining, tok, steps, emits = decode_scan(
                params, caches, tables, lengths, remaining, tok, keys, steps
            )
            return caches, lengths, remaining, tok, steps, emits, pf_logits

        return jax.jit(fused, donate_argnums=(1,))

    def _build_prefill_fn(self):
        """Prefill-chunk-only step, taken when NO slot is decode-active (an
        idle engine admitting a request should not pay the decode scan —
        this is what makes warm-cache TTFT a real reduction rather than a
        decode-tax trade)."""
        return jax.jit(self._prefill_chunk_fn(), donate_argnums=(1,))

    def _build_verify_fn(self):
        """Batched (k+1)-row verify + greedy acceptance, one jitted program.

        ``tokens`` (B, k+1) carries each slot's pending token + its k drafts
        at positions ``lengths .. lengths + k``; ``q_len`` 0 disables a
        slot. Returns (caches, g (B, k+1), a (B,)): ``g`` is the target's
        own argmax of every verify row — row j's argmax is what a
        sequential greedy decode would emit AFTER token j of the run — and
        ``a`` is the count of leading drafts that equal that argmax chain
        (``d_j == g_{j-1}``), i.e. the accepted prefix. Committing
        ``c = a + 1`` tokens ``g_0 .. g_{c-1}`` is therefore exactly the
        target's greedy stream regardless of draft quality (a junk or
        zero-padded draft is accepted only when it IS the argmax)."""
        cfg, rt, ecfg = self.cfg, self.rt, self.ecfg
        policy = self._policy

        def verify(params, caches, tables, lengths, tokens, q_len):
            logits, caches = paged_forward(
                cfg, params, caches, tables, tokens, lengths, q_len, rt,
                ecfg.max_len, all_rows=True,
            )
            g = policy.greedy_tokens(logits)                   # (B, k+1)
            ok = (tokens[:, 1:] == g[:, :-1]).astype(jnp.int32)
            a = jnp.cumprod(ok, axis=1).sum(axis=1)            # (B,)
            return caches, g, a

        return jax.jit(verify, donate_argnums=(1,))

    def _build_draft_fn(self):
        """Greedy k-step decode scan of the paired drafter model.

        The drafter trails the target by at most one token (full-accept
        catch-up), so each tick force-feeds 1–2 known tokens — ``forced``
        (B, 2) = [catch-up-or-pending, pending-or-junk], ``n_forced`` in
        {1, 2} — then free-runs on its own argmax emits. Per slot the scan
        takes ``(n_forced - 1) + k`` active steps (masked per-step), so the
        k proposals for slot b are ``emits[n_forced_b - 1 : n_forced_b - 1
        + k, b]``. ``d_len`` is the drafter's own cached length (host-
        tracked); tables are the TARGET's block tables — same page ids,
        drafter-private pools."""
        dcfg, rt, ecfg = self._draft_cfg, self._draft_rt, self.ecfg
        k = ecfg.spec_tokens
        policy = SamplingPolicy(temperature=0.0, vocab=dcfg.vocab_size)

        def draft(params, caches, tables, d_len, forced, n_forced, active):
            n_steps = (n_forced - 1) + k

            def step(carry, i):
                caches, d_len, tok = carry
                inp = jnp.where(
                    i == 0, forced[:, 0],
                    jnp.where(i < n_forced, forced[:, 1], tok),
                )
                act = (active & (i < n_steps)).astype(jnp.int32)
                logits, caches = paged_forward(
                    dcfg, params, caches, tables, inp[:, None], d_len, act,
                    rt, ecfg.max_len,
                )
                emit = policy.greedy_tokens(logits)
                return (caches, d_len + act, emit), emit

            (caches, _, _), emits = jax.lax.scan(
                step, (caches, d_len, forced[:, 0]), jnp.arange(k + 1)
            )
            return caches, emits                               # (k+1, B)

        return jax.jit(draft, donate_argnums=(1,))

    @property
    def _lookahead(self) -> int:
        """Tokens one tick may write per slot: ``inner_steps`` for the
        decode scan, ``spec_tokens + 1`` verify rows for a speculative
        tick (writes land at ``lengths .. lengths + k`` even when fewer
        commit)."""
        ecfg = self.ecfg
        if ecfg.spec_tokens:
            return max(ecfg.inner_steps, ecfg.spec_tokens + 1)
        return ecfg.inner_steps

    def _admission_headroom(self) -> int:
        """Extra free pages required beyond a newcomer's reservation under
        the optimistic policy: one chunk's worth of page-boundary crossings
        for every request that would then be running. Without this, a
        preempted request re-admits into a pool that cannot sustain the next
        chunk and is immediately evicted again (prefill thrash)."""
        if self.ecfg.policy != "optimistic":
            return 0
        n_active = sum(1 for s in self._slots if s is not None)
        if n_active == 0:
            return 0
        per_slot = self._lookahead // self.ecfg.page_size + 1
        return (n_active + 1) * per_slot

    def _use_chunked(self, req: Request) -> bool:
        """Paged-prefill (prefix-adopting, chunk-interleaved) admission path.
        Modality-prefix requests keep the legacy whole-prompt prefill: the
        radix key is token IDs and cannot see image content, and the chunk
        embedder has no frontend concat."""
        return (
            self.paged
            and bool(self.ecfg.prefix_cache or self.ecfg.prefill_chunk)
            and self.cfg.frontend is None
        )

    def _admit_free_slots(self) -> None:
        for slot_id, slot in enumerate(self._slots):
            if slot is not None:
                continue
            req = self.scheduler.peek()
            if req is None:
                break
            cached, sid = 0, None
            if self.prefix is not None and self._use_chunked(req):
                cached, pages = self.prefix.match(
                    req.tokens, max_tokens=req.prompt_len - 1
                )
                if cached:
                    # adopt FIRST: the refcount pins the matched pages so
                    # the pre-eviction below can never free them
                    sid = self.pool.adopt(pages, cached)
            headroom = self._admission_headroom()
            cached_pages = cached // self.ecfg.page_size
            if self.prefix is not None:
                reserve = self.scheduler.reserve_tokens(
                    req, self._prompt_total(req)
                )
                shortfall = (
                    self.pool.pages_for(reserve) - cached_pages + headroom
                    - self.pool.free_pages
                )
                if shortfall > 0:
                    self.prefix.evict_until(shortfall)
            popped = self.scheduler.pop_admissible(
                self.pool, self._prompt_total, headroom_pages=headroom,
                cached_pages_of=(
                    (lambda r: cached_pages) if sid is not None else None
                ),
            )
            if popped is None:
                if sid is not None:
                    self.pool.free(sid)
                break
            assert popped is req
            self._admit(slot_id, popped, cached=cached, sid=sid)
        if not any(self._slots) and len(self.scheduler):
            raise RuntimeError(
                "deadlock: empty engine cannot admit the head request "
                "(pool too small for it — submit() should have rejected it)"
            )

    def _admit(
        self, slot_id: int, req: Request, cached: int = 0,
        sid: Optional[int] = None,
    ) -> None:
        ecfg, cfg = self.ecfg, self.cfg
        prompt_total = self._prompt_total(req)
        reserve = self.scheduler.reserve_tokens(req, prompt_total)
        self.stats["prompt_tokens"] = (
            self.stats.get("prompt_tokens", 0) + prompt_total
        )
        if self._use_chunked(req):
            if self.prefix is not None:
                self.prefix.note_lookup(cached)   # once per admission
            if sid is None:
                sid = self.pool.alloc(reserve)
            else:
                self.pool.ensure(sid, reserve)   # adopted prefix + fresh tail
            table_row = jnp.asarray(
                self.pool.table(sid, self._dev["tables"].shape[1]), jnp.int32
            )
            self._apply_copies()
            d = self._dev
            d["tables"] = d["tables"].at[slot_id].set(table_row)
            d["lengths"] = d["lengths"].at[slot_id].set(cached)
            d["remaining"] = d["remaining"].at[slot_id].set(0)  # not decoding yet
            self._slots[slot_id] = _Slot(
                req.rid, sid, req, self._admit_count, phase="prefill",
                pf_next=cached, t_admit=time.perf_counter(),
            )
            self._admit_count += 1
            return
        assert sid is None and cached == 0
        sid = self.pool.alloc(reserve)
        t0 = time.perf_counter()
        tokens = req.tokens
        bucket = ecfg.prefill_bucket
        if bucket:
            pad = -len(tokens) % bucket
            tokens = np.pad(tokens, (0, pad))
        batch = {"tokens": jnp.asarray(tokens[None])}
        if req.frontend_embeds is not None:
            batch["frontend_embeds"] = jnp.asarray(req.frontend_embeds[None])
        batch = dense_mod.place_batch(batch, self.rt)
        prefill_fn = dense_mod.compiled_prefill(
            cfg, self.rt, dense_mod.batch_shape_key(batch),
            prompt_total + (len(tokens) - req.prompt_len),
            dynamic_gather=bool(bucket), full_cache=True,
        )
        if bucket:
            logits, pstate = prefill_fn(
                self.params, batch, jnp.int32(prompt_total - 1)
            )
        else:
            logits, pstate = prefill_fn(self.params, batch)
        rkey = self._policy.request_key(req.rid)
        tok0 = self._policy.sample(logits, jax.random.fold_in(rkey, 0))
        tok0.block_until_ready()
        now = time.perf_counter()
        self._keep_first_logits(req.rid, logits)   # after the TTFT stamp
        # TTFT from SUBMIT time — queue wait included — on every path; a
        # readmitted-after-preemption request whose first token was already
        # delivered keeps its original (honest) TTFT, see _evict
        self.stats["ttft_s"].setdefault(req.rid, now - req.t_submit)
        self.stats["prefill_s"][req.rid] = now - t0

        table_row = jnp.asarray(
            self.pool.table(sid, self._dev["tables"].shape[1]), jnp.int32
        )
        self._apply_copies()
        self._dev["caches"] = self._scatter_fn(
            self._dev["caches"], pstate["caches"], table_row,
            page_size=ecfg.page_size,
        )
        if ecfg.spec_tokens and ecfg.spec_drafter == "model":
            # bring the drafter level with the target: prefill the same
            # (padded) prompt through the paired config and scatter its KV
            # through the SAME table row into the drafter's pools; from
            # here on the drafter advances inside the spec tick's scan
            d_prefill = dense_mod.compiled_prefill(
                self._draft_cfg, self._draft_rt,
                dense_mod.batch_shape_key(batch),
                prompt_total + (len(tokens) - req.prompt_len),
                dynamic_gather=bool(bucket), full_cache=True,
            )
            if bucket:
                _, dstate = d_prefill(
                    self._draft_params, batch, jnp.int32(prompt_total - 1)
                )
            else:
                _, dstate = d_prefill(self._draft_params, batch)
            self._draft_dev["caches"] = self._scatter_fn(
                self._draft_dev["caches"], dstate["caches"], table_row,
                page_size=ecfg.page_size,
            )
            self._draft_len[slot_id] = prompt_total
            self._spec_catchup[slot_id] = -1
        d = self._dev
        d["tables"] = d["tables"].at[slot_id].set(table_row)
        d["lengths"] = d["lengths"].at[slot_id].set(prompt_total)
        d["remaining"] = d["remaining"].at[slot_id].set(req.max_new - 1)
        d["tok"] = d["tok"].at[slot_id].set(tok0[0])
        d["keys"] = d["keys"].at[slot_id].set(rkey)
        d["steps"] = d["steps"].at[slot_id].set(1)  # fold 0 used at prefill
        self._slots[slot_id] = _Slot(req.rid, sid, req, self._admit_count)
        self._admit_count += 1
        self._outputs[req.rid] = [int(tok0[0])]
        self._deliver(req.rid)   # last: a callback may cancel() this slot

    def _topup_or_evict(self) -> None:
        """Ensure every active slot's pages cover this chunk's writes;
        evict the youngest on exhaustion. Under the reserve policy the whole
        horizon was reserved at admission, so skip the per-chunk host sync
        and table rewrites entirely."""
        if self.ecfg.policy == "reserve":
            return
        lengths = np.asarray(self._dev["lengths"])
        remaining = np.asarray(self._dev["remaining"])
        for slot_id, slot in enumerate(self._slots):
            if slot is None:
                continue
            need = int(lengths[slot_id]) + min(
                int(remaining[slot_id]), self._lookahead
            )
            while self._slots[slot_id] is not None:
                try:
                    self.pool.ensure(slot.sid, need)
                    break
                except PoolExhausted:
                    # idle prefix-cache pages go first: evicting cached-but-
                    # unused KV is free, preempting a request discards work
                    if self.prefix is not None:
                        short = (
                            self.pool.pages_for(need)
                            - len(self.pool.seq_pages(slot.sid))
                            - self.pool.free_pages
                        )
                        if self.prefix.evict_until(max(short, 1)) > 0:
                            continue
                    # preempt the youngest active request — possibly the
                    # very slot that needs pages (FIFO fairness: the oldest
                    # admissions keep their pages and finish first)
                    actives = [
                        (s_id, s) for s_id, s in enumerate(self._slots)
                        if s is not None
                    ]
                    if len(actives) == 1:
                        raise   # a lone request frees nothing by preemption
                    self._evict(*max(actives, key=lambda kv: kv[1].order))
            if self._slots[slot_id] is None:
                continue                       # this slot was the victim
            self._apply_copies()
            row = jnp.asarray(
                self.pool.table(slot.sid, self._dev["tables"].shape[1]),
                jnp.int32,
            )
            self._dev["tables"] = self._dev["tables"].at[slot_id].set(row)

    def _evict(self, slot_id: int, slot: _Slot) -> None:
        """Recompute-style preemption: free pages, requeue from scratch."""
        self.pool.free(slot.sid)
        # all but the prefill-sampled token were counted as decode output
        # (a slot still mid-prefill has no output entry yet)
        if slot.rid in self._outputs:
            self.stats["discarded_tokens"] = (
                self.stats.get("discarded_tokens", 0)
                + len(self._outputs[slot.rid]) - 1
            )
            del self._outputs[slot.rid]
        # If the first token was never DELIVERED (no callback consumed it),
        # the recompute after readmission is what the user waits for: drop
        # the stale TTFT so it is re-measured — still from req.t_submit, so
        # preempt-then-readmit TTFT spans the original submit. If it WAS
        # delivered, the consumer already saw it at the recorded time and
        # that TTFT stays (the recompute is invisible to them:
        # _emitted suppresses re-delivery of the regenerated prefix).
        if self._emitted.get(slot.rid, 0) == 0:
            self.stats["ttft_s"].pop(slot.rid, None)
            self.stats["prefill_s"].pop(slot.rid, None)
        self.scheduler.requeue_front(slot.req)
        d = self._dev
        d["tables"] = d["tables"].at[slot_id].set(0)
        d["lengths"] = d["lengths"].at[slot_id].set(0)
        d["remaining"] = d["remaining"].at[slot_id].set(0)
        self._slots[slot_id] = None
        self.stats["evictions"] = self.stats.get("evictions", 0) + 1

    def _apply_copies(self) -> None:
        copies = self.pool.drain_copies()
        if not copies:
            return
        src = jnp.asarray([c[0] for c in copies], jnp.int32)
        dst = jnp.asarray([c[1] for c in copies], jnp.int32)
        self._dev["caches"] = _COPY_PAGES(self._dev["caches"], src, dst)

    def _run_chunk(self):
        d = self._dev
        caches, lengths, remaining, tok, steps, emits = self._chunk_fn(
            self.params, d["caches"], d["tables"], d["lengths"],
            d["remaining"], d["tok"], d["keys"], d["steps"],
        )
        d.update(
            caches=caches, lengths=lengths, remaining=remaining, tok=tok,
            steps=steps,
        )
        return np.asarray(emits), np.asarray(remaining)

    def _spec_step(self):
        """One speculative tick over all decode slots: draft k tokens per
        slot (host-side ngram lookup, or the paired drafter model's scan),
        verify every slot's [pending, drafts] run in ONE batched (k+1)-row
        pass through the paged-prefill write-then-attend path, and commit
        the accepted prefix plus the verify pass's own bonus token —
        1..k+1 tokens per slot per tick, never fewer than an ordinary
        decode step's 1 (row 0 alone IS that decode step). Every committed
        token is the target's own argmax, so the stream is token-identical
        to non-speculative greedy decode (and to running alone). Rejected
        rows need no device rollback (see ``models.lm.paged_forward``);
        under the optimistic policy the pool reservation is rewound
        host-side via ``PagePool.truncate``."""
        ecfg = self.ecfg
        k = ecfg.spec_tokens
        B = ecfg.max_slots
        d = self._dev
        lengths = np.array(d["lengths"])
        remaining = np.array(d["remaining"])
        tok = np.array(d["tok"])
        steps = np.array(d["steps"])
        active = np.array(
            [s is not None for s in self._slots]
        ) & (remaining > 0)
        n_act = int(active.sum())
        if n_act == 0:
            return np.full((0, B), -1, np.int32), remaining
        drafts = np.zeros((B, k), np.int32)
        if ecfg.spec_drafter == "model":
            drafts = self._run_draft(active, tok)
        else:
            for slot_id, slot in enumerate(self._slots):
                if not active[slot_id]:
                    continue
                ctx = np.concatenate([
                    slot.req.tokens,
                    np.asarray(self._outputs[slot.rid], np.int32),
                ])
                prop = spec_mod.ngram_draft(ctx, k, ecfg.spec_ngram)
                drafts[slot_id, : len(prop)] = prop
        # row 0 = the pending token (sampled last tick, not yet cached);
        # rows 1..k = drafts. Zero-padded/junk drafts are harmless: they
        # commit only if they equal the argmax — the correct token anyway.
        tokens = np.concatenate([tok[:, None], drafts], axis=1)
        q_len = np.where(active, k + 1, 0).astype(np.int32)
        self._count_kv_walk(False, verify=(lengths, q_len))
        caches, g, a = self._verify_fn(
            self.params, d["caches"], d["tables"], d["lengths"],
            self._place(jnp.asarray(tokens, jnp.int32)),
            self._place(jnp.asarray(q_len)),
        )
        d["caches"] = caches
        g, a = np.asarray(g), np.asarray(a)
        stats = self.stats
        stats["spec_verify_calls"] = stats.get("spec_verify_calls", 0) + n_act
        stats["spec_drafted_tokens"] = (
            stats.get("spec_drafted_tokens", 0) + n_act * k
        )
        emits = np.full((k + 1, B), -1, np.int32)
        accepted = 0
        for slot_id, slot in enumerate(self._slots):
            if not active[slot_id]:
                continue
            c = int(min(a[slot_id] + 1, remaining[slot_id]))
            emits[:c, slot_id] = g[slot_id, :c]
            lengths[slot_id] += c
            remaining[slot_id] -= c
            tok[slot_id] = g[slot_id, c - 1]   # new pending token
            steps[slot_id] += c
            accepted += c - 1
            if ecfg.policy == "optimistic":
                # pool-accounting half of rejection rollback: hand back
                # reservation the rejected tail no longer needs (refcount/
                # COW-safe inside the pool; table rows are rewritten from
                # the pool every tick under this policy)
                self.pool.truncate(slot.sid, int(lengths[slot_id]))
            if ecfg.spec_drafter == "model":
                if c == k + 1:
                    # full accept: the drafter never cached g_{k-1} (it
                    # only consumed through its own (k-1)th emit) — force-
                    # feed it next tick, then the new pending token
                    self._spec_catchup[slot_id] = int(g[slot_id, k - 1])
                    self._draft_len[slot_id] = int(lengths[slot_id]) - 1
                else:
                    # partial accept: the drafter's accepted prefix is
                    # already cached correctly; rewind its length past the
                    # rejected tail (stale KV beyond it is masked by
                    # length and overwritten by the next scan)
                    self._spec_catchup[slot_id] = -1
                    self._draft_len[slot_id] = int(lengths[slot_id])
        stats["spec_accepted_tokens"] = (
            stats.get("spec_accepted_tokens", 0) + accepted
        )
        d["lengths"] = self._place(jnp.asarray(lengths))
        d["remaining"] = self._place(jnp.asarray(remaining))
        d["tok"] = self._place(jnp.asarray(tok))
        d["steps"] = self._place(jnp.asarray(steps))
        return emits, remaining

    def _run_draft(self, active: np.ndarray, tok: np.ndarray) -> np.ndarray:
        """Advance the paired drafter model k greedy steps per active slot
        and return its proposals (B, k). The drafter trails the target by
        at most one cached token, so 1–2 known tokens are force-fed first
        (see ``_build_draft_fn``); its block tables ARE the target's."""
        ecfg = self.ecfg
        k = ecfg.spec_tokens
        B = ecfg.max_slots
        catch = self._spec_catchup
        n_forced = np.where(active & (catch >= 0), 2, 1).astype(np.int32)
        forced = np.zeros((B, 2), np.int32)
        forced[:, 0] = np.where(catch >= 0, catch, tok)
        forced[:, 1] = tok
        caches, emits = self._draft_fn(
            self._draft_params, self._draft_dev["caches"],
            self._dev["tables"], jnp.asarray(self._draft_len, jnp.int32),
            jnp.asarray(forced), jnp.asarray(n_forced), jnp.asarray(active),
        )
        self._draft_dev["caches"] = caches
        emits = np.asarray(emits)               # (k+1, B)
        drafts = np.zeros((B, k), np.int32)
        for b in range(B):
            if active[b]:
                o = int(n_forced[b]) - 1
                drafts[b] = emits[o : o + k, b]
        return drafts

    def _place(self, arr: jax.Array) -> jax.Array:
        """Commit a fresh host array replicated onto the mesh (the fused fn
        mixes it with sharded pools; see ``dense.place_batch``)."""
        if self.rt.mesh is None:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            arr,
            NamedSharding(self.rt.mesh, PartitionSpec(*([None] * arr.ndim))),
        )

    def _device_step(self):
        """One device dispatch: a decode-only chunk, or — when a slot is mid-
        prefill — the fused program (its next prompt chunk + the same decode
        chunk). Oldest-admitted prefilling slot goes first (FIFO fairness:
        one chunk per step keeps decode stalls bounded by one chunk)."""
        pf = [
            (i, s) for i, s in enumerate(self._slots)
            if s is not None and s.phase == "prefill"
        ]
        if not pf:
            # speculative ticks need every seated slot in the decode phase
            # (the verify batch spans all slots); while any prompt is still
            # chunking, the ordinary fused tick below keeps decode moving —
            # both paths emit the same greedy stream, so mixing them tick
            # by tick never changes tokens
            if self.ecfg.spec_tokens:
                return self._spec_step()
            self._count_kv_walk(decode=True)
            return self._run_chunk()
        slot_id, slot = min(pf, key=lambda kv: kv[1].order)
        req = slot.req
        T = self.ecfg.chunk_tokens
        start = slot.pf_next
        n = min(T, req.prompt_len - start)
        chunk = np.zeros(T, np.int32)
        chunk[:n] = req.tokens[start : start + n]
        d = self._dev
        operands = (
            self._place(jnp.asarray(chunk)), self._place(jnp.int32(slot_id)),
            self._place(jnp.int32(start)), self._place(jnp.int32(n)),
        )
        # decode-phase slots still seated always have remaining > 0 (retire
        # runs right after every step), so phase alone decides
        decoding = any(
            s is not None and s.phase == "decode" for s in self._slots
        )
        self._count_kv_walk(decoding, chunk=(start, n))
        if decoding:
            (
                caches, lengths, remaining, tok, steps, emits, pf_logits
            ) = self._fused_fn(
                self.params, d["caches"], d["tables"], d["lengths"],
                d["remaining"], d["tok"], d["keys"], d["steps"], *operands,
            )
            d.update(
                caches=caches, lengths=lengths, remaining=remaining, tok=tok,
                steps=steps,
            )
        else:
            caches, pf_logits = self._prefill_fn(
                self.params, d["caches"], d["tables"], *operands
            )
            d["caches"] = caches
            emits = np.full(
                (0, self.ecfg.max_slots), -1, np.int32
            )                                     # nothing decoded this step
        self.stats["prefill_chunks"] = (
            self.stats.get("prefill_chunks", 0) + 1
        )
        slot.pf_next = start + n
        if slot.pf_next >= req.prompt_len:
            self._finish_prefill(slot_id, slot, pf_logits)
        else:
            d["lengths"] = d["lengths"].at[slot_id].set(slot.pf_next)
        return np.asarray(emits), np.asarray(d["remaining"])

    def _count_kv_walk(self, decode: bool, chunk=None, verify=None) -> None:
        """Add one tick's paged-kernel calls to the walk counters, from the
        slot positions the host tracks: the decode scan's ``inner_steps``
        calls over every slot, a prefill chunk ``(start, rows)``, a verify
        call ``(starts, q_lens)``."""
        if "paged_kv_blocks_walked" not in self.stats:
            return
        ecfg, B = self.ecfg, self.ecfg.max_slots
        if decode:
            pos = np.zeros(B, np.int64)   # next position of a decoding slot
            left = np.zeros(B, np.int64)  # tokens it still has to emit
            for i, s in enumerate(self._slots):
                if s is not None and s.phase == "decode":
                    n_out = len(self._outputs[s.rid])
                    pos[i] = self._prompt_total(s.req) + n_out - 1
                    left[i] = s.req.max_new - n_out
            # the scan's inner_steps calls, one row of slots each
            step = np.arange(ecfg.inner_steps)[:, None]
            self._add_kv_walk(pos + step, (left > step).astype(np.int64), 1)
        if chunk is not None:
            self._add_kv_walk(
                np.array([chunk[0]]), np.array([chunk[1]]), ecfg.chunk_tokens
            )
        if verify is not None:
            self._add_kv_walk(*verify, ecfg.spec_tokens + 1)

    def _add_kv_walk(self, start: np.ndarray, q_len: np.ndarray, T: int):
        """Kernel calls of ``T`` query rows over the slots in ``start`` (any
        shape), in every attention layer."""
        cfg = self.cfg
        for window, n_layers in self._windows:
            walked, horizon = walked_blocks(
                start, q_len, rows=T * (cfg.n_heads // cfg.n_kv_heads),
                hd=cfg.head_dim, page=self.ecfg.page_size,
                n_pages=self._dev["tables"].shape[1], window=window,
            )
            self.stats["paged_kv_blocks_walked"] += walked * n_layers
            self.stats["paged_kv_blocks_horizon"] += horizon * n_layers

    def _finish_prefill(self, slot_id: int, slot: _Slot, pf_logits) -> None:
        """Last chunk done: sample the first token (the request's TTFT) and
        flip the slot into the decode phase — same key/step discipline as
        the legacy at-admission prefill, so the sample stream (and with it
        the batched == alone guarantee) is untouched."""
        ecfg, cfg = self.ecfg, self.cfg
        req = slot.req
        rkey = self._policy.request_key(req.rid)
        tok0 = self._policy.sample(
            pf_logits[None], jax.random.fold_in(rkey, 0)
        )
        tok0.block_until_ready()
        now = time.perf_counter()
        self._keep_first_logits(req.rid, pf_logits)   # after the TTFT stamp
        # TTFT from SUBMIT (queue wait + admission + every chunk), matching
        # the legacy path's origin; prefill compute time kept separately
        self.stats["ttft_s"].setdefault(req.rid, now - req.t_submit)
        self.stats["prefill_s"][req.rid] = now - slot.t_admit
        d = self._dev
        d["lengths"] = d["lengths"].at[slot_id].set(req.prompt_len)
        d["remaining"] = d["remaining"].at[slot_id].set(req.max_new - 1)
        d["tok"] = d["tok"].at[slot_id].set(tok0[0])
        d["keys"] = d["keys"].at[slot_id].set(rkey)
        d["steps"] = d["steps"].at[slot_id].set(1)  # fold 0 used just above
        slot.phase = "decode"
        self._outputs[req.rid] = [int(tok0[0])]
        self._deliver(req.rid)   # last: a callback may cancel() this slot

    def lowered_programs(self) -> Dict[str, str]:
        """StableHLO text of the paged engine's decode chunk program (and,
        on the chunked path, its prefill chunk program), lowered at the
        engine's current state shapes without running them — to see which
        kernels a program holds (``tpu_custom_call`` for Pallas)."""
        d = self._dev
        out = {"decode": self._chunk_fn.lower(
            self.params, d["caches"], d["tables"], d["lengths"],
            d["remaining"], d["tok"], d["keys"], d["steps"],
        ).as_text()}
        if self.ecfg.prefix_cache or self.ecfg.prefill_chunk:
            chunk = (jnp.zeros(self.ecfg.chunk_tokens, jnp.int32),
                     jnp.int32(0), jnp.int32(0), jnp.int32(1))
            out["prefill"] = self._prefill_fn.lower(
                self.params, d["caches"], d["tables"],
                *(self._place(a) for a in chunk),
            ).as_text()
        return out

    def _keep_first_logits(self, rid: int, logits) -> None:
        if self.ecfg.keep_first_logits:
            self.first_logits.setdefault(
                rid, np.asarray(logits, np.float32).reshape(-1)
            )

    def _collect(self, emits: np.ndarray) -> int:
        n = 0
        for slot_id, slot in enumerate(self._slots):
            if slot is None or slot.rid not in self._outputs:
                continue        # mid-prefill: no first token sampled yet
            toks = emits[:, slot_id]
            toks = toks[toks >= 0]
            self._outputs[slot.rid].extend(int(t) for t in toks)
            n += len(toks)
            self._deliver(slot.rid)  # may cancel() this slot (early stop)
        return n

    def _retire(self, remaining: np.ndarray) -> List[int]:
        finished: List[int] = []
        for slot_id, slot in enumerate(self._slots):
            if slot is None or slot.phase == "prefill" or remaining[slot_id] > 0:
                continue
            self.stats["kv_bytes"][slot.rid] = (
                len(self.pool.seq_pages(slot.sid)) * self._kv_bytes_per_page()
            )
            self._completed_run.add(slot.rid)
            if self.prefix is not None and self._use_chunked(slot.req):
                # full prompt pages go back into the radix tree (pages
                # holding generated tokens are not keyed by the prompt and
                # stay out); freeing the sequence below leaves only the
                # cache's retains on them
                n_full = slot.req.prompt_len // self.ecfg.page_size
                self.prefix.insert(
                    slot.req.tokens,
                    self.pool.seq_pages(slot.sid)[:n_full],
                )
            self.pool.free(slot.sid)
            d = self._dev
            d["tables"] = d["tables"].at[slot_id].set(0)
            d["lengths"] = d["lengths"].at[slot_id].set(0)
            self._slots[slot_id] = None
            finished.append(slot.rid)
            self._deliver_done(slot.rid, "complete")
        return finished

    # ------------------------------------------------------ dense fallback
    def _step_dense(self) -> Dict[str, Any]:
        """One dense-fallback tick: pop the head request plus every queued
        request sharing its (prompt_len, max_new) shape — they run as one
        cached compiled generate (contiguous (B, total) caches) — then
        deliver whole outputs. Matching requests beyond ``max_slots`` wait
        for the next tick."""
        cfg, ecfg = self.cfg, self.ecfg
        part = self.scheduler.pop_batch(ecfg.max_slots)
        plen, max_new = part[0].prompt_len, part[0].max_new
        batch = {
            "tokens": jnp.asarray(
                np.stack([r.tokens for r in part]), jnp.int32
            )
        }
        if part[0].frontend_embeds is not None:
            batch["frontend_embeds"] = jnp.asarray(
                np.stack([r.frontend_embeds for r in part])
            )
        t_call = time.perf_counter()
        tokens, _, pf_s = dense_mod.generate_dense(
            cfg, self.params, batch, self.rt, max_new,
            temperature=ecfg.temperature, seed=ecfg.seed,
        )
        tokens.block_until_ready()
        total = plen + max_new + (
            cfg.frontend_tokens if cfg.frontend == "vision" else 0
        )
        kv = self._dense_kv_bytes(total)
        finished: List[int] = []
        for b, r in enumerate(part):
            self._outputs[r.rid] = [int(t) for t in np.asarray(tokens[b])]
            # generate_dense's returned latency is its prefill(+first
            # sample) wall time from call start; TTFT spans from submit,
            # so queue wait before this tick is included
            self.stats["ttft_s"][r.rid] = t_call + pf_s - r.t_submit
            self.stats["prefill_s"][r.rid] = pf_s
            self.stats["kv_bytes"][r.rid] = kv
            self._run_decode_tokens += max_new - 1
            self._admit_count += 1
            self._completed_run.add(r.rid)
            finished.append(r.rid)
            self._deliver(r.rid)
            self._deliver_done(r.rid, "complete")
        return {
            "busy": True, "finished": finished,
            "decoded": len(part) * max_new,
        }

    def _dense_kv_bytes(self, total: int) -> int:
        return dense_kv_bytes(self.cfg, self.rt, total)


class ReplicatedServeEngine:
    """Data-parallel serving over a ``(data, model)`` mesh.

    The mesh factorizes into ``data`` replicas of a model-only submesh
    (``launch.mesh.replica_submeshes``); each replica carries a full
    (TP-sharded) parameter copy and its own KV pool + scheduler, and
    ``ReplicaRouter`` assigns every request to the least-loaded replica.
    Because an engine's per-request output is identical to running the
    request alone, routing can never change tokens — only latency — so the
    replicated engine inherits the batched==alone determinism guarantee.

    ``step()`` ticks every replica round-robin from this host (``run()``
    just loops it); on real hardware each replica's chunk executes on its
    own device slice, so a multi-controller launcher can drive them
    concurrently without any change to the engines themselves. Routing is
    transactional: if the chosen engine's ``submit`` raises (capacity
    reject, QueueFull backpressure), the routing decision is rolled back
    and no global rid is consumed.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params: Params,
        rt: Optional[Runtime] = None,
        engine: EngineConfig = EngineConfig(),
        mesh=None,
        paged: Optional[bool] = None,
        draft_params: Optional[Params] = None,
        draft_cfg: Optional[ArchConfig] = None,
    ):
        from repro.launch.mesh import replica_submeshes
        from repro.serve.scheduler import ReplicaRouter

        rt = rt if rt is not None else Runtime()
        meshes = replica_submeshes(mesh) if mesh is not None else [rt.mesh]
        self.engines = [
            ServeEngine(
                cfg, params, rt.replace(mesh=m), engine, paged=paged,
                draft_params=draft_params, draft_cfg=draft_cfg,
            )
            for m in meshes
        ]
        self.router = ReplicaRouter(len(self.engines))
        self._where: Dict[int, Tuple[int, int]] = {}  # rid -> (replica, local)
        self._next_rid = 0
        self.stats: Dict[str, Any] = {}

    def submit(
        self,
        tokens: np.ndarray,
        max_new: int,
        frontend_embeds: Optional[np.ndarray] = None,
        qos: str = "interactive",
        on_token=None,
    ) -> int:
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        idx = self.router.route(
            [e.outstanding_tokens for e in self.engines]
        )
        rid = self._next_rid
        cb = None
        if on_token is not None:
            # translate the replica-local rid back to the global one
            def cb(_local, toks, done, _g=rid, _f=on_token):
                _f(_g, toks, done)
        try:
            local = self.engines[idx].submit(
                tokens, max_new, frontend_embeds=frontend_embeds,
                qos=qos, on_token=cb,
            )
        except Exception:
            # transactional routing: a rejected request (capacity ValueError,
            # QueueFull backpressure) must not inflate the chosen replica's
            # routed count or consume a global rid
            self.router.unroute(idx)
            raise
        self._next_rid += 1
        self._where[rid] = (idx, local)
        return rid

    def cancel(self, rid: int) -> bool:
        if rid not in self._where:
            return False
        idx, local = self._where[rid]
        return self.engines[idx].cancel(local)

    @property
    def busy(self) -> bool:
        return any(e.busy for e in self.engines)

    def run_begin(self) -> None:
        # open windows on every replica, queued or not: an empty window
        # still resets that engine's per-run stats, so the aggregates in
        # run_finalize never mix a previous run's numbers into this one
        for e in self.engines:
            e.run_begin()

    def step(self) -> Dict[str, Any]:
        """One tick across all replicas (round-robin from this host; on
        real hardware each replica's chunk runs on its own device slice)."""
        if self.busy and any(e._run_t0 is None for e in self.engines):
            self.run_begin()
        finished: List[int] = []
        busy = False
        l2g = {
            (idx, local): rid
            for rid, (idx, local) in self._where.items()
        }
        for idx, e in enumerate(self.engines):
            rep = e.step()
            busy = busy or rep["busy"]
            finished.extend(
                l2g[(idx, lr)] for lr in rep["finished"]
                if (idx, lr) in l2g
            )
        return {"busy": busy, "finished": finished}

    def run_finalize(self) -> Dict[int, np.ndarray]:
        outs: List[Dict[int, np.ndarray]] = [
            eng.run_finalize() for eng in self.engines
        ]
        merged = {
            rid: outs[idx][local]
            for rid, (idx, local) in self._where.items()
            if local in outs[idx]
        }
        # replicas are stepped round-robin from this host, so their
        # measurement windows overlap: the elapsed window is the max
        # per-replica wall, and aggregate throughput is total delivered
        # work over it (a concurrent multi-controller drive would approach
        # the per-replica sum)
        wall = max(
            [e.stats.get("wall_s", 0.0) for e in self.engines] + [0.0]
        )
        delivered = sum(
            e.stats.get("decode_tokens", 0) for e in self.engines
        )
        completed = sum(
            e.stats.get("run_completed", 0) for e in self.engines
        )
        self.stats = {
            "replica_requests": list(self.router.routed),
            "tokens_per_s": delivered / max(wall, 1e-9),
            "wall_s": wall,
            "decode_tokens": delivered,
            "run_completed": completed,
            "run_mean_ttft_s": (
                sum(
                    e.stats.get("run_mean_ttft_s", 0.0)
                    * e.stats.get("run_completed", 0)
                    for e in self.engines
                ) / max(completed, 1)
            ),
            "evictions": sum(
                e.stats.get("evictions", 0) for e in self.engines
            ),
            "cancelled": sum(
                e.stats.get("cancelled", 0) for e in self.engines
            ),
            "ttft_s": {
                rid: self.engines[idx].stats["ttft_s"][local]
                for rid, (idx, local) in self._where.items()
                if local in self.engines[idx].stats["ttft_s"]
            },
            "prefill_s": {
                rid: self.engines[idx].stats["prefill_s"][local]
                for rid, (idx, local) in self._where.items()
                if local in self.engines[idx].stats["prefill_s"]
            },
            "kv_pool_bytes_per_device": max(
                e.stats.get("kv_pool_bytes_per_device", 0)
                for e in self.engines
            ),
        }
        # prefix-cache counters sum across replicas (each replica keys its
        # own radix tree over its own pool — a cross-replica hit requires
        # the router to have sent the matching request to the same replica)
        for key in (
            "prompt_tokens", "prefix_lookups", "prefix_hits",
            "prefix_cached_tokens", "prefill_chunks",
            "paged_kv_blocks_walked", "paged_kv_blocks_horizon",
            "spec_verify_calls", "spec_drafted_tokens",
            "spec_accepted_tokens",
        ):
            vals = [e.stats[key] for e in self.engines if key in e.stats]
            if vals:
                self.stats[key] = sum(vals)
        if "spec_verify_calls" in self.stats:
            # fleet-level acceptance from the summed run-window counters
            # (each engine's own rates cover only its replica)
            acc = self.stats.get("spec_accepted_tokens", 0)
            self.stats["spec_accept_rate"] = acc / max(
                self.stats.get("spec_drafted_tokens", 0), 1
            )
            self.stats["spec_accepted_per_verify"] = (
                acc + self.stats["spec_verify_calls"]
            ) / max(self.stats["spec_verify_calls"], 1)
        return merged

    def run(self) -> Dict[int, np.ndarray]:
        self.run_begin()
        while self.busy:
            self.step()
        return self.run_finalize()

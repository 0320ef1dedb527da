"""Layer-stack engine: init + forward/prefill/decode over scanned units.

The stack is executed as a sequence of *segments*; each segment is a
``lax.scan`` over ``R`` repeats of a *unit* of layers (statically-known mixer
kinds and windows inside the unit). This keeps HLO size O(unit) for 88-layer
stacks while supporting patterned architectures:

  granite / phi3 / qwen3 / ...   unit = (attn,)           R = n_layers
  gemma3                         unit = 5x local + attn   R = 4  (+ tail 2)
  recurrentgemma                 unit = (rglru, rglru, local)  R = 8 (+ tail 2)
  sw-variant long-context        unit = 7x local + attn   R = n_layers/8

Parameters are stored grouped by the *param pattern* (mixer kinds modulo
attn==local, which share parameters); at apply time they are re-grouped to
the *runtime pattern* (which also fixes windows/cache sizes) by strided
slicing — a pure-layout transform.

For 3D pipelined training, ``stack_stage_apply`` applies one pipeline
stage's contiguous layer slice of a homogeneous stack (the canonical
stacked layout sharded over ``pipe`` IS the stage split) with manual
Megatron tensor parallelism (``tp_region_start/end``); see the
"pipeline stage apply" section below and repro.core.pipeline.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import Params, init_mlp, init_norm, mlp_apply, norm_apply
from repro.models.runtime import Runtime


class LayerSpec(NamedTuple):
    kind: str       # attn | local | rglru | mamba
    window: int     # 0 = full attention
    cache_len: int  # kv cache entries (attention kinds only)


def param_kind(kind: str) -> str:
    return "attn" if kind == "local" else kind


# --------------------------------------------------------------------- specs
def layer_specs(
    cfg: ArchConfig, *, seq_len: int, long_variant: bool = False
) -> Tuple[LayerSpec, ...]:
    kinds = cfg.mixer_kinds()
    out: List[LayerSpec] = []
    if long_variant and cfg.long_context == "sw_variant":
        for i in range(cfg.n_layers):
            if (i + 1) % cfg.lc_global_every == 0:
                out.append(LayerSpec("attn", 0, seq_len))
            else:
                w = cfg.lc_window
                out.append(LayerSpec("local", w, min(w, seq_len)))
        return tuple(out)
    for k in kinds:
        if k == "attn":
            out.append(LayerSpec("attn", 0, seq_len))
        elif k == "local":
            w = cfg.sliding_window
            out.append(LayerSpec("local", w, min(w, seq_len)))
        else:
            out.append(LayerSpec(k, 0, 0))
    return tuple(out)


def runtime_period(cfg: ArchConfig, long_variant: bool) -> int:
    if long_variant and cfg.long_context == "sw_variant":
        return cfg.lc_global_every
    return len(cfg.pattern)


def param_groups(cfg: ArchConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """[(unit param-kind pattern, repeats)] — variant-independent storage."""
    kinds = tuple(param_kind(k) for k in cfg.pattern)
    if len(set(kinds)) == 1:
        return [((kinds[0],), cfg.n_layers)]
    u = len(kinds)
    n, rem = divmod(cfg.n_layers, u)
    groups = [(kinds, n)]
    if rem:
        groups.append((kinds[:rem], 1))
    return groups


# ---------------------------------------------------------------------- init
def _init_block(cfg: ArchConfig, key: jax.Array, kind: str, cross: bool) -> Params:
    p: Params = {"norm1": init_norm(cfg.norm, cfg.d_model)}
    if kind == "attn":
        p["mixer"] = attn_mod.init_attention(
            jax.random.fold_in(key, 1), cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim,
        )
    elif kind == "mamba":
        p["mixer"] = ssm_mod.init_mamba(
            jax.random.fold_in(key, 1), cfg.d_model, cfg.d_inner, cfg.ssm_state,
            cfg.ssm_conv,
        )
    elif kind == "rglru":
        p["mixer"] = rglru_mod.init_rglru(
            jax.random.fold_in(key, 1), cfg.d_model,
            cfg.rglru_width or cfg.d_model, cfg.ssm_conv,
        )
    else:
        raise ValueError(kind)
    if cross:
        p["norm_x"] = init_norm(cfg.norm, cfg.d_model)
        p["cross"] = attn_mod.init_attention(
            jax.random.fold_in(key, 2), cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cross=True,
        )
    if cfg.ffn_kind != "none":
        p["norm2"] = init_norm(cfg.norm, cfg.d_model)
        if cfg.ffn_kind == "dense":
            p["ffn"] = init_mlp(
                jax.random.fold_in(key, 3), cfg.d_model, cfg.d_ff, cfg.mlp_gated
            )
        else:
            p["ffn"] = moe_mod.init_moe(
                jax.random.fold_in(key, 3), cfg.d_model, cfg.d_ff, cfg.n_experts,
                cfg.mlp_gated,
            )
            extra = cfg.n_shared_experts * cfg.d_ff + (
                cfg.residual_d_ff if cfg.dense_residual else 0
            )
            if extra:
                p["extra_mlp"] = init_mlp(
                    jax.random.fold_in(key, 4), cfg.d_model, extra, cfg.mlp_gated
                )
    return p


def init_stack(cfg: ArchConfig, key: jax.Array, cross: bool = False) -> Params:
    """Stacked (R, ...) params per param-group (see ``param_groups``)."""
    stack: Params = {}
    layer0 = 0
    for gi, (pattern, R) in enumerate(param_groups(cfg)):
        def init_unit(k: jax.Array) -> Params:
            return {
                f"p{j}": _init_block(cfg, jax.random.fold_in(k, j), kind, cross)
                for j, kind in enumerate(pattern)
            }

        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            layer0 + jnp.arange(R)
        )
        stack[f"g{gi}"] = jax.vmap(init_unit)(keys)
        layer0 += R * len(pattern)
    return stack


# ----------------------------------------------------------------- segments
@dataclasses.dataclass(frozen=True)
class Segment:
    unit_specs: Tuple[LayerSpec, ...]   # static per-position specs
    group_key: str                      # which param group this reads from
    take: Tuple[Tuple[int, int, int], ...]  # per-position (start, stop, step) on L axis
    repeats: int
    patterned: bool                     # param storage keyed by unit position?


def _spec_period(specs: Tuple[LayerSpec, ...]) -> int:
    """Smallest U with specs[i] == specs[i % U] for the non-tail prefix."""
    L = len(specs)
    for U in range(1, L + 1):
        if all(specs[i] == specs[i % U] for i in range(L - (L % U))):
            return U
    return L


def build_segments(
    cfg: ArchConfig, specs: Tuple[LayerSpec, ...]
) -> List[Segment]:
    groups = param_groups(cfg)
    if len(groups[0][0]) > 1:
        # patterned param storage (recurrentgemma): runtime unit == param unit
        segs = []
        off = 0
        for gi, (pattern, R) in enumerate(groups):
            u = len(pattern)
            segs.append(
                Segment(
                    unit_specs=specs[off : off + u],
                    group_key=f"g{gi}",
                    take=tuple((j, j + 1, 1) for j in range(u)),
                    repeats=R,
                    patterned=True,
                )
            )
            off += R * u
        return segs

    # homogeneous params: re-group to the runtime period by strided slices
    L = cfg.n_layers
    U = _spec_period(specs)
    n, rem = divmod(L, U)
    segs = [
        Segment(
            unit_specs=specs[:U],
            group_key="g0",
            take=tuple((j, n * U, U) for j in range(U)),
            repeats=n,
            patterned=False,
        )
    ]
    if rem:
        segs.append(
            Segment(
                unit_specs=specs[n * U :],
                group_key="g0",
                take=tuple((n * U + j, n * U + j + 1, 1) for j in range(rem)),
                repeats=1,
                patterned=False,
            )
        )
    return segs


def _widen_segment(seg: Segment, k: int) -> Segment:
    """Group k repeats into one scan unit (plan-based remat granularity).

    Only applies cleanly to homogeneous-storage segments whose repeat count
    divides by k; otherwise the segment is returned unchanged (the plan
    degrades gracefully on pattern tails)."""
    if seg.patterned or seg.repeats % k or seg.repeats < k:
        return seg
    U = len(seg.unit_specs)
    new_take = []
    for rep in range(k):
        for j, (start, stop, step) in enumerate(seg.take):
            # position (rep, j) reads layer (r*k + rep)*U + j = start + rep*U + r*(k*U)
            new_take.append((start + rep * step, stop, step * k))
    return Segment(
        unit_specs=seg.unit_specs * k,
        group_key=seg.group_key,
        take=tuple(new_take),
        repeats=seg.repeats // k,
        patterned=False,
    )


def segment_params(stack: Params, seg: Segment) -> Params:
    """Extract per-unit-position stacked params: {'p{j}': leaves (R, ...)}."""
    group = stack[seg.group_key]
    if seg.patterned:
        return {f"p{j}": group[f"p{j}"] for j in range(len(seg.take))}
    # homogeneous storage: group = {'p0': leaves (L, ...)}; strided re-group
    src = group["p0"]
    out: Params = {}
    for j, (start, stop, step) in enumerate(seg.take):
        out[f"p{j}"] = jax.tree.map(lambda p, s=start, e=stop, st=step: p[s:e:st], src)
    return out


# ------------------------------------------------------------------- apply
def _seq_shard_constraint(h: jax.Array, rt: Runtime) -> jax.Array:
    """Sequence/hidden-parallel residual stream (see Runtime.seq_shard)."""
    if not rt.seq_shard or rt.mesh is None or h.ndim != 3:
        return h
    dim = 1 if rt.seq_shard == "seq" else 2
    if h.shape[dim] % rt.mesh.shape["model"] != 0:
        return h
    P = jax.sharding.PartitionSpec
    b = tuple(rt.batch_axes) if rt.batch_axes else None
    spec = P(b, "model", None) if dim == 1 else P(b, None, "model")
    return jax.lax.with_sharding_constraint(
        h, jax.sharding.NamedSharding(rt.mesh, spec)
    )


@jax.named_scope("mlp")
def _ffn_apply(
    cfg: ArchConfig, p: Params, x: jax.Array, rt: Runtime
) -> Tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    if cfg.ffn_kind == "none":
        return x, aux
    h = norm_apply(p["norm2"], x, cfg.norm, fused=rt.fused_backward)
    if cfg.ffn_kind == "dense":
        out = mlp_apply(p["ffn"], h, cfg.mlp_gated)
    else:
        out, aux = _moe_dispatch(cfg, p["ffn"], h, rt)
        if "extra_mlp" in p:
            out = out + mlp_apply(p["extra_mlp"], h, cfg.mlp_gated)
    return x + out, aux


def _moe_dispatch(
    cfg: ArchConfig, p: Params, h: jax.Array, rt: Runtime
) -> Tuple[jax.Array, jax.Array]:
    kw = dict(
        top_k=cfg.experts_top_k,
        capacity_factor=cfg.capacity_factor,
        gated=cfg.mlp_gated,
    )
    if rt.moe_mode != "ep":
        return moe_mod.moe_apply(p, h, **kw)
    assert rt.mesh is not None, "moe_mode='ep' requires Runtime.mesh"
    P = jax.sharding.PartitionSpec
    bspec = P(rt.batch_axes if rt.batch_axes else None, None, None)
    pspec = {
        "router": P(None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    if cfg.mlp_gated:
        pspec["w_gate"] = P("model", None, None)

    def inner(p_, h_):
        out, aux = moe_mod.moe_apply(p_, h_, axis_name="model", **kw)
        axes = tuple(rt.batch_axes) + ("model",)
        return out, jax.lax.pmean(aux, axes)

    fn = shard_map(
        inner,
        mesh=rt.mesh,
        in_specs=(pspec, bspec),
        out_specs=(bspec, P()),
        check_vma=False,
    )
    return fn(p, h)


def _mixer_scope(kind: str):
    """The named scope of an attention layer's ops (QKV and output
    projections, RoPE, the kernel): ``attn``, the device trace's region.
    Recurrent mixers run under no scope."""
    if kind in ("attn", "local"):
        return jax.named_scope("attn")
    return contextlib.nullcontext()


def _mixer_apply(
    cfg: ArchConfig,
    p: Params,
    x: jax.Array,
    spec: LayerSpec,
    rt: Runtime,
    positions: Optional[jax.Array],
    collect: bool,
    causal: bool = True,
    cache_len: Optional[int] = None,
):
    with _mixer_scope(spec.kind):
        h = norm_apply(p["norm1"], x, cfg.norm, fused=rt.fused_backward)
        if spec.kind in ("attn", "local"):
            out, kv = attn_mod.attention_apply(
                p["mixer"], h,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                theta=cfg.rope_theta, window=spec.window, causal=causal,
                positions=positions, chunk_q=rt.chunk_q, collect_kv=collect,
                use_kernel=rt.use_flash_kernel,
            )
            cache = None
            if collect:
                cache = attn_mod.fill_kv_cache(
                    attn_mod.init_kv_cache(
                        x.shape[0], cache_len or spec.cache_len, cfg.n_kv_heads,
                        cfg.head_dim, rt.dtype,
                    ),
                    kv["k"], kv["v"], positions,
                )
            return x + out, cache
        if spec.kind == "mamba":
            if rt.ssm_seqpar and rt.mesh is not None and not collect:
                res = ssm_mod.mamba_apply_seqpar(
                    p["mixer"], h, mesh=rt.mesh, batch_axes=rt.batch_axes,
                )
            else:
                res = ssm_mod.mamba_apply(
                    p["mixer"], h, scan_mode=rt.scan_mode, chunk=rt.ssm_chunk,
                    collect_state=collect,
                )
        else:
            if rt.ssm_seqpar and rt.mesh is not None and not collect:
                res = rglru_mod.rglru_apply_seqpar(
                    p["mixer"], h, mesh=rt.mesh, batch_axes=rt.batch_axes,
                )
            else:
                res = rglru_mod.rglru_apply(p["mixer"], h, collect_state=collect)
        if collect:
            out, cache = res
            return x + out, cache
        return x + res, None


@jax.named_scope("attn")
def _cross_apply(
    cfg: ArchConfig, p: Params, x: jax.Array, memory: jax.Array, rt: Runtime
) -> jax.Array:
    h = norm_apply(p["norm_x"], x, cfg.norm, fused=rt.fused_backward)
    out, _ = attn_mod.attention_apply(
        p["cross"], h,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        theta=cfg.rope_theta, window=0, causal=False, memory=memory,
        chunk_q=rt.chunk_q,
    )
    return x + out


def stack_forward(
    cfg: ArchConfig,
    stack: Params,
    x: jax.Array,
    rt: Runtime,
    specs: Tuple[LayerSpec, ...],
    *,
    positions: Optional[jax.Array] = None,
    memory: Optional[jax.Array] = None,
    collect_cache: bool = False,
    causal: bool = True,
    cache_specs: Optional[Tuple[LayerSpec, ...]] = None,
):
    """Full-sequence forward. Returns (x, aux_loss, caches | None).

    ``caches``: list aligned with segments; each entry is a pytree whose
    leaves are stacked (R, ...) per unit position — the decode cache layout.
    ``cache_specs`` (same period as ``specs``) sizes the collected caches for
    a longer decode horizon than the prefill length.
    """
    if positions is None:
        B, S = x.shape[0], x.shape[1]
        positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None, :], (B, 1))
    if cache_specs is None:
        cache_specs = specs
    segments = build_segments(cfg, specs)
    if rt.remat_period > 1:
        segments = [_widen_segment(s, rt.remat_period) for s in segments]
    aux_total = jnp.zeros((), jnp.float32)
    caches: List[Any] = []
    seg_off = 0

    for seg in segments:
        params_seg = segment_params(stack, seg)
        unit_cache_specs = tuple(
            cache_specs[(seg_off + j) % len(cache_specs)]
            for j in range(len(seg.unit_specs))
        )
        seg_off += seg.repeats * len(seg.unit_specs)

        def unit_body(carry, unit_p, _seg=seg, _cspecs=unit_cache_specs):
            h, aux = carry
            h = _seq_shard_constraint(h, rt)
            unit_caches = {}
            for j, spec in enumerate(_seg.unit_specs):
                bp = unit_p[f"p{j}"]
                h, cache = _mixer_apply(
                    cfg, bp, h, spec, rt, positions, collect_cache, causal,
                    cache_len=_cspecs[j].cache_len,
                )
                if memory is not None and "cross" in bp:
                    h = _cross_apply(cfg, bp, h, memory, rt)
                    if collect_cache:
                        dtype = rt.dtype
                        ck = (memory @ bp["cross"]["wk"].astype(dtype)).reshape(
                            memory.shape[0], memory.shape[1], cfg.n_kv_heads, cfg.head_dim
                        )
                        cv = (memory @ bp["cross"]["wv"].astype(dtype)).reshape(
                            memory.shape[0], memory.shape[1], cfg.n_kv_heads, cfg.head_dim
                        )
                        cache = {"self": cache, "ck": ck, "cv": cv}
                h, aux_l = _ffn_apply(cfg, bp, h, rt)
                aux = aux + aux_l
                if collect_cache:
                    unit_caches[f"p{j}"] = cache
            return (h, aux), (unit_caches if collect_cache else None)

        body = unit_body
        if rt.remat == "full":
            body = jax.checkpoint(unit_body, prevent_cse=False)
        elif rt.remat == "dots":
            body = jax.checkpoint(
                unit_body, prevent_cse=False,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        elif rt.remat == "offload":
            body = jax.checkpoint(
                unit_body, prevent_cse=False,
                policy=jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                    "device", "pinned_host"
                ),
            )

        (x, aux_total), seg_cache = jax.lax.scan(
            body, (x, aux_total), params_seg
        )
        caches.append(seg_cache)

    return x, aux_total, (caches if collect_cache else None)


def init_stack_cache(
    cfg: ArchConfig,
    stack: Params,
    B: int,
    rt: Runtime,
    specs: Tuple[LayerSpec, ...],
    enc_len: int = 0,
) -> List[Any]:
    """Zero decode cache in the segment layout (used when skipping prefill)."""
    segments = build_segments(cfg, specs)
    caches = []
    for seg in segments:
        unit: Dict[str, Any] = {}
        for j, spec in enumerate(seg.unit_specs):
            if spec.kind in ("attn", "local"):
                c: Any = attn_mod.init_kv_cache(
                    B, spec.cache_len, cfg.n_kv_heads, cfg.head_dim, rt.dtype
                )
            elif spec.kind == "mamba":
                p0 = jax.tree.map(
                    lambda p: p[0], segment_params(stack, seg)[f"p{j}"]
                )
                c = ssm_mod.init_mamba_state(p0["mixer"], B, rt.dtype)
            else:
                p0 = jax.tree.map(
                    lambda p: p[0], segment_params(stack, seg)[f"p{j}"]
                )
                c = rglru_mod.init_rglru_state(p0["mixer"], B, rt.dtype)
            if cfg.is_encdec and enc_len:
                c = {
                    "self": c,
                    "ck": jnp.zeros((B, enc_len, cfg.n_kv_heads, cfg.head_dim), rt.dtype),
                    "cv": jnp.zeros((B, enc_len, cfg.n_kv_heads, cfg.head_dim), rt.dtype),
                }
            unit[f"p{j}"] = c
        caches.append(
            jax.tree.map(
                lambda l: jnp.broadcast_to(l, (seg.repeats,) + l.shape), unit
            )
        )
    return caches


def init_stack_pool(
    cfg: ArchConfig,
    rt: Runtime,
    specs: Tuple[LayerSpec, ...],
    num_pages: int,
    page_size: int,
) -> List[Any]:
    """Per-layer paged KV pools in the segment layout (attention kinds only).

    Every layer shares one block table per request (the vLLM convention), so
    all pools have identical page geometry; pools are slot-count independent
    — requests share the pool through their block tables.
    """
    segments = build_segments(cfg, specs)
    pools = []
    for seg in segments:
        unit: Dict[str, Any] = {}
        for j, spec in enumerate(seg.unit_specs):
            assert spec.kind in ("attn", "local"), (
                f"paged decode supports attention mixers only, got {spec.kind}"
            )
            unit[f"p{j}"] = attn_mod.init_paged_kv_cache(
                num_pages, page_size, cfg.n_kv_heads, cfg.head_dim, rt.dtype,
                kv_dtype=rt.kv_dtype,
            )
        pools.append(
            jax.tree.map(
                lambda l: jnp.broadcast_to(l, (seg.repeats,) + l.shape), unit
            )
        )
    return pools


def write_prefill_to_pool(
    pools: List[Any], caches: List[Any], table: jax.Array, page_size: int
) -> List[Any]:
    """Scatter one request's prefill KV into its block-table pages.

    ``caches`` is the segment-layout ring-cache pytree collected by
    ``stack_forward(collect_cache=True)`` for a batch of ONE request; each
    entry's explicit ``pos`` array drives placement (pool position = absolute
    token position), so ring-truncated local-layer caches land exactly on
    their surviving window band and invalid entries fall into null page 0.
    ``table``: (P,) int32 page ids for this request.

    Quantized pools (``ksc`` present): each cache row is quantized here,
    exactly once, before landing in its page — same codes + scales the
    chunked/decode write paths would have produced for the same values.
    """
    from repro.kernels.paged_attention import quant

    def scatter(pool, k, v, pos):
        # entries that are invalid OR beyond the table's coverage go to the
        # null page (a clip would clobber the last real page instead)
        valid = (pos >= 0) & (pos // page_size < table.shape[0])
        pid = jnp.where(
            valid,
            table[jnp.clip(pos // page_size, 0, table.shape[0] - 1)],
            0,
        )
        slot = jnp.where(valid, pos % page_size, 0)
        new = dict(pool)
        if "ksc" in pool:
            k_codes, k_sc = quant.kv_quantize(k[0], pool["kp"].dtype)
            v_codes, v_sc = quant.kv_quantize(v[0], pool["vp"].dtype)
            new["kp"] = attn_mod.pool_write(pool["kp"], pid, slot, k_codes)
            new["vp"] = attn_mod.pool_write(pool["vp"], pid, slot, v_codes)
            new["ksc"] = attn_mod.pool_write(pool["ksc"], pid, slot, k_sc)
            new["vsc"] = attn_mod.pool_write(pool["vsc"], pid, slot, v_sc)
        else:
            new["kp"] = attn_mod.pool_write(pool["kp"], pid, slot, k[0])
            new["vp"] = attn_mod.pool_write(pool["vp"], pid, slot, v[0])
        return new

    new_pools: List[Any] = []
    for seg_pool, seg_cache in zip(pools, caches):
        unit: Dict[str, Any] = {}
        for key, pool in seg_pool.items():
            c = seg_cache[key]
            unit[key] = jax.vmap(scatter)(pool, c["k"], c["v"], c["pos"])
        new_pools.append(unit)
    return new_pools


def stack_paged(
    cfg: ArchConfig,
    stack: Params,
    x: jax.Array,
    caches: List[Any],
    tables: jax.Array,
    start: jax.Array,
    q_len: jax.Array,
    rt: Runtime,
    specs: Tuple[LayerSpec, ...],
):
    """T rows per slot through the stack, writing KV into pool pages.

    x: (B, T, d) embedded rows; ``caches`` are page pools (see
    ``init_stack_pool``); ``tables``/``start``/``q_len`` as in
    ``attention_paged``. Returns (x, new_caches). Attention-mixer families
    only (the same families the paged engine serves).

    The pools ride the scan CARRY, stacked over the unit repeats, and each
    layer writes and reads its own slice in place (``layer``): carried as
    scanned inputs and outputs instead, they would need a second,
    pool-sized output buffer in every call.
    """
    segments = build_segments(cfg, specs)
    new_caches: List[Any] = []

    for seg, seg_cache in zip(segments, caches):
        params_seg = segment_params(stack, seg)
        reps = jax.tree.leaves(seg_cache)[0].shape[0]

        def unit_body(carry, xs, _seg=seg):
            h, pools = carry
            unit_p, layer = xs
            pools = dict(pools)
            for j, spec in enumerate(_seg.unit_specs):
                assert spec.kind in ("attn", "local"), spec.kind
                bp = unit_p[f"p{j}"]
                hn = norm_apply(bp["norm1"], h, cfg.norm)
                out, pools[f"p{j}"] = attn_mod.attention_paged(
                    bp["mixer"], hn, pools[f"p{j}"], tables, start, q_len,
                    n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                    head_dim=cfg.head_dim, theta=cfg.rope_theta,
                    window=spec.window, use_kernel=rt.use_paged_kernel,
                    mesh=rt.mesh, layer=layer,
                )
                h = h + out
                h, _ = _ffn_apply(cfg, bp, h, rt)
            return (h, pools), None

        (x, seg_cache), _ = jax.lax.scan(
            unit_body, (x, seg_cache),
            (params_seg, jnp.arange(reps, dtype=jnp.int32)),
        )
        new_caches.append(seg_cache)

    return x, new_caches


def stack_decode(
    cfg: ArchConfig,
    stack: Params,
    x: jax.Array,
    caches: List[Any],
    t: jax.Array,
    rt: Runtime,
    specs: Tuple[LayerSpec, ...],
):
    """One-token decode over ring buffers / recurrent states. x: (B, 1, d);
    ``t`` is the scalar position shared by the whole batch. Returns
    (x, new_caches). The paged pool's decode step is ``stack_paged`` at
    T = 1."""
    segments = build_segments(cfg, specs)
    new_caches: List[Any] = []

    for seg, seg_cache in zip(segments, caches):
        params_seg = segment_params(stack, seg)

        def unit_body(h, xs, _seg=seg):
            unit_p, unit_c = xs
            new_unit_c = {}
            for j, spec in enumerate(_seg.unit_specs):
                bp = unit_p[f"p{j}"]
                c = unit_c[f"p{j}"]
                self_c = c["self"] if (cfg.is_encdec and isinstance(c, dict) and "self" in c) else c
                hn = norm_apply(bp["norm1"], h, cfg.norm)
                if spec.kind in ("attn", "local"):
                    out, self_c = attn_mod.attention_decode(
                        bp["mixer"], hn, self_c, t,
                        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.head_dim, theta=cfg.rope_theta,
                        window=spec.window,
                    )
                elif spec.kind == "mamba":
                    out, self_c = ssm_mod.mamba_decode(bp["mixer"], hn, self_c)
                else:
                    out, self_c = rglru_mod.rglru_decode(bp["mixer"], hn, self_c)
                h = h + out
                if cfg.is_encdec and "cross" in bp:
                    hx = norm_apply(bp["norm_x"], h, cfg.norm)
                    out, _ = _cross_decode(cfg, bp["cross"], hx, c["ck"], c["cv"])
                    h = h + out
                    new_unit_c[f"p{j}"] = {"self": self_c, "ck": c["ck"], "cv": c["cv"]}
                else:
                    new_unit_c[f"p{j}"] = self_c
                h, _ = _ffn_apply(cfg, bp, h, rt)
            return h, new_unit_c

        x, new_seg_cache = jax.lax.scan(unit_body, x, (params_seg, seg_cache))
        new_caches.append(new_seg_cache)

    return x, new_caches


# ------------------------------------------------- pipeline stage apply
# Megatron's f/g operators as explicit custom-vjp pairs, for MANUAL tensor
# parallelism inside the fully-manual pipeline shard_map (where XLA's auto
# SPMD is unavailable). A TP region runs on per-device parameter shards
# between the two markers; activations outside the region are replicated
# over the model axis:
#
#   tp_region_start ("f"): identity forward, psum backward — the replicated
#       activation fans out to tp shard-local computations, so its cotangent
#       is the SUM of the per-shard partials.
#   tp_region_end ("g"): psum forward, identity backward — shard-local
#       partial outputs (row-parallel wo / w_down) combine to the replicated
#       value; the replicated cotangent passes through to every shard.
#
# Skip-connection paths never enter a region, so their cotangents are
# counted exactly once — the invariant that makes per-layer "psum at the
# end" schemes wrong and this pairing right.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tp_start(axis_name, x):
    return x


def _tp_start_fwd(axis_name, x):
    return x, None


def _tp_start_bwd(axis_name, _, ct):
    return (jax.lax.psum(ct, axis_name),)


_tp_start.defvjp(_tp_start_fwd, _tp_start_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tp_end(axis_name, x):
    return jax.lax.psum(x, axis_name)


def _tp_end_fwd(axis_name, x):
    return jax.lax.psum(x, axis_name), None


def _tp_end_bwd(axis_name, _, ct):
    return (ct,)


_tp_end.defvjp(_tp_end_fwd, _tp_end_bwd)


def tp_region_start(x: jax.Array, axis_name: str = "model") -> jax.Array:
    return _tp_start(axis_name, x)


def tp_region_end(x: jax.Array, axis_name: str = "model") -> jax.Array:
    return _tp_end(axis_name, x)


def pipeline_incompatibility(cfg: ArchConfig, tp: int = 1) -> Optional[str]:
    """Why ``cfg`` cannot run the executable pipeline path (None = it can).

    The 1F1B/GPipe runner slices the layer stack at plan boundaries, which
    requires homogeneous param storage (one param group, single-kind
    pattern) and — for tp > 1 — Megatron-divisible attention/dense shapes
    (the manual-TP stage body computes on true shards; the auto-SPMD paths'
    silent replication fallback has no manual equivalent).
    """
    groups = param_groups(cfg)
    if len(groups) != 1 or len(groups[0][0]) != 1:
        return "patterned parameter storage (multi-kind layer unit)"
    if len(set(cfg.pattern)) != 1:
        return "mixed mixer kinds in the layer pattern"
    if cfg.is_encdec or cfg.frontend is not None:
        return "encoder-decoder / frontend architectures"
    if tp > 1:
        kind = param_kind(cfg.pattern[0])
        if kind != "attn":
            return f"tensor parallelism over {kind!r} mixers (attention only)"
        if cfg.n_heads % tp or cfg.n_kv_heads % tp:
            return (
                f"heads ({cfg.n_heads}/{cfg.n_kv_heads}) not divisible by tp={tp}"
            )
        if cfg.ffn_kind == "dense" and cfg.d_ff % tp:
            return f"d_ff={cfg.d_ff} not divisible by tp={tp}"
        if cfg.ffn_kind == "moe":
            return "MoE with tp > 1 (expert parallelism stays on the 2D path)"
    return None


def stage_layer_params(stack: Params) -> Params:
    """Per-layer param tree of a homogeneous stack ({'g0': {'p0': ...}})."""
    assert set(stack) == {"g0"} and set(stack["g0"]) == {"p0"}, (
        "pipeline stages require homogeneous param storage"
    )
    return stack["g0"]["p0"]


def stack_stage_apply(
    cfg: ArchConfig,
    layers: Params,
    x: jax.Array,
    rt: Runtime,
    spec: LayerSpec,
    *,
    tp: int = 1,
    tp_axis: str = "model",
) -> Tuple[jax.Array, jax.Array]:
    """Apply one pipeline stage's contiguous layer slice. Returns (y, aux).

    Runs inside the fully-manual pipeline ``shard_map``: ``layers`` leaves
    are the LOCAL (layers_per_stage, tp-shard) slices of the canonical
    stacked params. Tensor parallelism is manual Megatron — shard-local
    attention heads / MLP columns bracketed by tp_region_start/end (see
    above); the residual stream stays replicated over ``tp_axis``. The
    stage's remat policy (``rt.remat``, from the ParallelPlan) wraps each
    layer; the pipeline runner additionally recomputes the whole stage
    forward from its stored input during backward, so a stage's live
    activations never outlast its tick.

    ``block`` below deliberately mirrors the manual-TP subset of
    ``_mixer_apply``/``_ffn_apply`` (attention + dense/MoE FFN, no caches,
    no fused-kernel routing, no shard_map-based EP — those assume auto-SPMD
    and cannot run in this manual context; make_pipeline_step rejects the
    corresponding TrainConfig flags loudly). A structural change to the
    canonical block must be mirrored here — tests/test_train_3d.py's
    losses-match-single-device check is the tripwire.
    """
    from repro.core.remat import policy_for

    B, S = x.shape[0], x.shape[1]
    positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None, :], (B, 1))

    def block(h, p):
        with _mixer_scope(spec.kind):
            hn = norm_apply(p["norm1"], h, cfg.norm)
            if spec.kind in ("attn", "local"):
                if tp > 1:
                    hn = tp_region_start(hn, tp_axis)
                out, _ = attn_mod.attention_apply(
                    p["mixer"], hn,
                    n_heads=cfg.n_heads // tp, n_kv=cfg.n_kv_heads // tp,
                    head_dim=cfg.head_dim, theta=cfg.rope_theta,
                    window=spec.window, positions=positions, chunk_q=rt.chunk_q,
                )
                if tp > 1:
                    out = tp_region_end(out, tp_axis)
            elif spec.kind == "mamba":
                assert tp == 1, "mamba stages run at tp=1 (see pipeline_incompatibility)"
                out = ssm_mod.mamba_apply(
                    p["mixer"], hn, scan_mode=rt.scan_mode, chunk=rt.ssm_chunk
                )
            else:
                assert tp == 1, "rglru stages run at tp=1"
                out = rglru_mod.rglru_apply(p["mixer"], hn)
            h = h + out
        aux = jnp.zeros((), jnp.float32)
        if cfg.ffn_kind != "none":
            with jax.named_scope("mlp"):
                h2 = norm_apply(p["norm2"], h, cfg.norm)
                if cfg.ffn_kind == "dense":
                    if tp > 1:
                        h2 = tp_region_start(h2, tp_axis)
                    o = mlp_apply(p["ffn"], h2, cfg.mlp_gated)
                    if tp > 1:
                        o = tp_region_end(o, tp_axis)
                else:
                    o, aux = moe_mod.moe_apply(
                        p["ffn"], h2, top_k=cfg.experts_top_k,
                        capacity_factor=cfg.capacity_factor, gated=cfg.mlp_gated,
                    )
                    if "extra_mlp" in p:
                        o = o + mlp_apply(p["extra_mlp"], h2, cfg.mlp_gated)
                h = h + o
        return h, aux

    pol = policy_for(rt.remat)
    body = block if pol is None else pol(block)
    y, auxs = jax.lax.scan(body, x, layers)
    return y, jnp.sum(auxs)


def _cross_decode(cfg: ArchConfig, p: Params, x: jax.Array, ck, cv):
    """Cross-attention for one decode token against cached encoder k/v."""
    B = x.shape[0]
    dtype = x.dtype
    G = cfg.n_heads // cfg.n_kv_heads
    q = (x @ p["wq"].astype(dtype)).reshape(B, cfg.n_kv_heads, G, cfg.head_dim)
    q = q * (cfg.head_dim ** -0.5)
    scores = jnp.einsum("bkgh,bskh->bkgs", q, ck, preferred_element_type=jnp.float32)
    w = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", w, cv).reshape(B, 1, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(dtype), None

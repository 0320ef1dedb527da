"""PartitionSpec rules: tensor parallelism + batch/cache shardings per arch.

Rules (Megatron-style row/column splits, adapted per family):

* attention: wq/wk/wv column-split over head width (only when the head count
  divides the TP size — gemma3's 4 heads and recurrentgemma's 10 stay
  replicated), wo row-split.
* MLP: up/gate column-split on d_ff, down row-split.
* MoE: experts sharded over ``model`` (expert parallelism); router replicated.
* Mamba: column-split on d_inner for in/conv/dt, row-split for x_proj and
  out_proj (the scan is elementwise over d_inner, so it stays local).
* RG-LRU: column-split on the recurrence width; block-diag gates replicated.
* vocab: embedding row-split / head column-split over the padded vocab.

Stacked layer params (leaves under ``stack``/``enc_stack``) carry a leading
scan axis that is never sharded. ZeRO overlays (repro.core.zero) add the
``data`` axis on top of these specs.

Every rule checks divisibility against the mesh and falls back to
replication — a config/mesh combination can therefore always lower, and the
roofline report shows what that fallback costs (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig

# leaf-name classification (see module docstring)
_COL = {"wq", "wk", "wv", "w_up", "w_gate", "in_proj", "dt_proj", "conv_w"}
_ROW = {"wo", "w_down", "out_proj", "w_out", "x_proj"}
_DIM0 = {"A_log", "D", "dt_bias", "lam", "bias_a", "bias_x", "conv_b"}
_REPLICATE = {"scale", "bias", "router", "gate_a", "gate_x"}


def _div(n: int, by: int) -> bool:
    return by > 0 and n % by == 0


def leaf_param_spec(
    path: Tuple[str, ...], shape: Tuple[int, ...], cfg: ArchConfig, tp: int
) -> P:
    name = path[-1]
    stacked = "stack" in path  # leading scan axis
    dims: list = [None] * len(shape)
    body = shape[1:] if stacked else shape
    off = 1 if stacked else 0
    if not body:
        return P(*dims)

    is_moe_leaf = len(body) == 3 and name in ("w_up", "w_gate", "w_down")
    if is_moe_leaf:
        if _div(body[0], tp):
            dims[off] = "model"  # expert parallelism
        return P(*dims)

    if name in _REPLICATE:
        return P(*dims)
    if name == "table":  # embedding (V, d)
        if _div(body[0], tp):
            dims[off] = "model"
        return P(*dims)
    if name == "w" and path[-2] == "head":  # (d, V)
        if _div(body[1], tp):
            dims[off + 1] = "model"
        return P(*dims)

    # head-count guard for attention projections
    if name in ("wq", "wo") and not _div(cfg.n_heads, tp):
        return P(*dims)
    if name in ("wk", "wv") and not _div(cfg.n_kv_heads, tp):
        return P(*dims)

    if name in _COL and len(body) >= 2:
        if _div(body[-1], tp):
            dims[off + len(body) - 1] = "model"
        return P(*dims)
    if name in _ROW and len(body) >= 2:
        if _div(body[0], tp):
            dims[off] = "model"
        return P(*dims)
    if name in _DIM0 or len(body) == 1:
        if _div(body[0], tp):
            dims[off] = "model"
        return P(*dims)
    return P(*dims)


def param_specs(cfg: ArchConfig, params_shape: Any, mesh) -> Any:
    """Spec tree for a params(-shaped) pytree.

    On a 3D training mesh (one with a ``pipe`` axis), stacked layer params
    additionally shard their leading layer axis over ``pipe`` — contiguous
    equal-count stages, exactly the executable ParallelPlan layout: each
    pipe shard holds its stage's layer slice, TP dims unchanged.
    """
    tp = mesh.shape["model"] if "model" in mesh.shape else 1
    pp = mesh.shape["pipe"] if "pipe" in mesh.shape else 1

    def one(path, leaf):
        keys = tuple(
            str(p.key) if hasattr(p, "key") else str(getattr(p, "idx", p))
            for p in path
        )
        spec = leaf_param_spec(keys, tuple(leaf.shape), cfg, tp)
        if (
            pp > 1 and "stack" in keys and leaf.shape
            and _div(leaf.shape[0], pp)
        ):
            dims = list(spec) + [None] * (len(leaf.shape) - len(spec))
            assert dims[0] is None, (keys, spec)
            dims[0] = "pipe"
            spec = P(*dims)
        return spec

    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_axes(mesh, global_batch: int) -> Tuple[str, ...]:
    """Largest prefix of (pod, data) that divides the global batch."""
    axes = [a for a in ("pod", "data") if a in mesh.shape]
    out = []
    size = 1
    for a in axes:
        if global_batch % (size * mesh.shape[a]) == 0:
            out.append(a)
            size *= mesh.shape[a]
    return tuple(out)


def batch_specs(batch_shape: Any, mesh, global_batch: int) -> Any:
    ba = batch_axes(mesh, global_batch)
    bspec = tuple(ba) if ba else None

    def one(leaf):
        dims = [bspec] + [None] * (len(leaf.shape) - 1)
        return P(*dims)

    return jax.tree.map(one, batch_shape)


def microbatch_specs(mb_shape: Any, mesh, mb_batch: int) -> Any:
    """Specs for microbatched arrays (M, B, ...): leading M replicated,
    per-microbatch batch dim over the data axes when divisible."""
    ba = batch_axes(mesh, mb_batch)
    bspec = tuple(ba) if ba else None

    def one(leaf):
        dims = [None, bspec] + [None] * (len(leaf.shape) - 2)
        return P(*dims)

    return jax.tree.map(one, mb_shape)


def cache_specs(cfg: ArchConfig, cache_shape: Any, mesh, global_batch: int) -> Any:
    """Decode-cache shardings.

    kv caches (R, B, S, kv, hd): batch over data axes when divisible; the
    cache SEQUENCE axis shards over ``model`` when kv-heads cannot (MQA) —
    sequence-parallel attention for decode (Pope et al.-style), which is what
    lets a 500k-token cache fit. SSM/RG-LRU states shard their channel dim.
    """
    tp = mesh.shape["model"] if "model" in mesh.shape else 1
    ba = batch_axes(mesh, global_batch)
    bspec = tuple(ba) if ba else None

    def one(path, leaf):
        keys = tuple(
            str(p.key) if hasattr(p, "key") else "" for p in path
        )
        name = keys[-1] if keys else ""
        shape = leaf.shape
        if name == "pos":
            return P(*([None] * len(shape)))
        if name in ("k", "v", "ck", "cv") and len(shape) >= 4:
            # (R, B, S, kv, hd) or (B, S, kv, hd)
            off = len(shape) - 4
            dims = [None] * len(shape)
            if off:
                dims[off] = bspec  # B
            else:
                dims[0] = bspec
            if _div(shape[off + 2], tp):
                dims[off + 2] = "model"       # kv heads
            elif _div(shape[off + 1], tp) and shape[off + 1] >= tp:
                dims[off + 1] = "model"       # sequence-parallel cache
            return P(*dims)
        if name in ("conv", "ssm", "h"):
            # (R, B, *, C) / (R, B, C, s) / (R, B, C): channel dim -> model
            dims = [None] * len(shape)
            dims[1] = bspec
            cdim = 2 if name == "h" else (2 if name == "ssm" else len(shape) - 1)
            if len(shape) > cdim and _div(shape[cdim], tp):
                dims[cdim] = "model"
            return P(*dims)
        if name == "memory" or (len(shape) == 3 and name == ""):
            dims = [bspec] + [None] * (len(shape) - 1)
            return P(*dims)
        dims = [None] * len(shape)
        if shape and bspec and _div(shape[0], _size(mesh, ba)):
            dims[0] = bspec
        return P(*dims)

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def _size(mesh, axes) -> int:
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s


def pool_kv_spec(cfg: ArchConfig, ndim: int, tp: int) -> P:
    """Spec for one paged KV pool leaf ``(..., num_pages, Kv, page, hd)``.

    The pool shards over ``model`` on the KV-HEAD axis (dim -3) — the
    Megatron head split applied to serving memory: each chip stores
    ``Kv / tp`` heads of every page, so KV bytes per chip shrink by the TP
    factor while page ids stay globally valid (the block table is
    replicated). Falls back to replication when the head count doesn't
    divide (same guard as the wk/wv param rule above).
    """
    dims: list = [None] * ndim
    if _div(cfg.n_kv_heads, tp) and tp > 1:
        dims[-3] = "model"
    return P(*dims)


def pool_scale_spec(cfg: ArchConfig, ndim: int, tp: int) -> P:
    """Spec for a quantized pool's scale leaf ``(..., num_pages, Kv, page)``.

    The KV-head axis (dim -2) shards over ``model`` under the same
    divisibility guard as
    :func:`pool_kv_spec` — each chip stores the scales for exactly the
    head slice of pages it holds.
    """
    dims: list = [None] * ndim
    if _div(cfg.n_kv_heads, tp) and tp > 1:
        dims[-2] = "model"
    return P(*dims)


def paged_state_specs(cfg: ArchConfig, state_shape: Any, mesh) -> Any:
    """Spec tree for the paged decode state (``models.lm.init_paged_state``).

    ``caches`` leaves are page pools (head-sharded, see ``pool_kv_spec``)
    plus, under a quantized ``kv_dtype``, their scale buffers (``ksc`` /
    ``vsc``, head-sharded on the same kv-head axis); ``tables``/``lengths`` (and any
    other host-updated slot arrays) are replicated — every chip addresses
    the same page ids.
    """
    tp = mesh.shape["model"] if "model" in mesh.shape else 1

    def one(path, leaf):
        keys = tuple(
            str(p.key) if hasattr(p, "key") else "" for p in path
        )
        if keys[-1] in ("kp", "vp"):
            return pool_kv_spec(cfg, len(leaf.shape), tp)
        if keys[-1] in ("ksc", "vsc"):
            return pool_scale_spec(cfg, len(leaf.shape), tp)
        return P(*([None] * len(leaf.shape)))

    return jax.tree_util.tree_map_with_path(one, state_shape)


def stash_state_specs(state_shape: Any, mesh) -> Any:
    """Spec tree for a materialized activation-stash state (core.stash).

    Inside the pipeline runner the stash rides the shard_map scan carry and
    needs no specs; this helper covers the stash OUTSIDE shard_map — e.g. a
    stacked per-stage view with a leading ``pipe``-degree axis (checkpoint
    dumps, the bench's buffer measurement). The rule mirrors the quantized
    KV pool (PR 6): a leading axis equal to the pipe degree shards over
    ``pipe`` — and codes + scales shard together since both carry it —
    everything else (slot axis, blocks) is replicated.
    """
    pp = mesh.shape["pipe"] if "pipe" in mesh.shape else 1

    def one(leaf):
        dims: list = [None] * len(leaf.shape)
        if pp > 1 and len(leaf.shape) > 0 and leaf.shape[0] == pp:
            dims[0] = "pipe"
        return P(*dims)

    return jax.tree.map(one, state_shape)


def with_sharding(mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )

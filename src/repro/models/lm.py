"""Top-level model: embeddings -> (encoder) -> decoder stack -> logits.

Batch convention (all arrays optional except tokens):
  tokens          (B, S_text) int32         decoder input ids
  labels          (B, S_text) int32         next-token targets, -1 = masked
  frontend_embeds (B, P, d) compute-dtype   stub modality embeddings:
                                            * audio/enc-dec: encoder input
                                            * vlm: patch embeds prepended to text

The VLM forward concatenates [image_embeds; embed(tokens)] so the sequence
length seen by the stack is P + S_text; loss is only taken on text positions.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import stack as stack_mod
from repro.models.layers import (
    Params,
    embed_apply,
    init_embed,
    init_norm,
    logits_apply,
    norm_apply,
    dense_init,
    subkey,
)
from repro.models.runtime import Runtime
from repro.models.stack import LayerSpec, layer_specs


# ----------------------------------------------------------------------- init
def init_params(cfg: ArchConfig, key: jax.Array) -> Params:
    p: Params = {
        "embed": init_embed(subkey(key, "embed"), cfg.vocab_padded, cfg.d_model),
        "stack": stack_mod.init_stack(cfg, subkey(key, "stack"), cross=cfg.is_encdec),
        "final_norm": init_norm(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["head"] = {
            "w": dense_init(subkey(key, "head"), cfg.d_model, cfg.vocab_padded)
        }
    if cfg.is_encdec:
        enc_cfg = _encoder_cfg(cfg)
        p["enc_stack"] = stack_mod.init_stack(enc_cfg, subkey(key, "enc"))
        p["enc_norm"] = init_norm(cfg.norm, cfg.d_model)
    return p


def init_params_as(
    cfg: ArchConfig, key: jax.Array, dtype, shardings=None
) -> Params:
    """``init_params`` with every leaf cast to ``dtype``, in one jitted
    program placed by ``shardings`` (a tree matching the params, or None
    for the default device) — a published-width model is never held in
    f32 on the device."""
    return jax.jit(
        lambda k: jax.tree.map(lambda x: x.astype(dtype), init_params(cfg, k)),
        out_shardings=shardings,
    )(key)


def _encoder_cfg(cfg: ArchConfig) -> ArchConfig:
    import dataclasses

    return dataclasses.replace(
        cfg,
        name=cfg.name + "-enc",
        n_layers=cfg.enc_layers,
        enc_layers=0,
        pattern=("attn",),
        ffn_kind="dense",
        frontend=None,
    )


# ------------------------------------------------------------------- encoder
def encode(cfg: ArchConfig, params: Params, embeds: jax.Array, rt: Runtime) -> jax.Array:
    """Bidirectional encoder over precomputed frontend embeddings."""
    enc_cfg = _encoder_cfg(cfg)
    specs = layer_specs(enc_cfg, seq_len=embeds.shape[1])
    x, _, _ = stack_mod.stack_forward(
        enc_cfg, params["enc_stack"], embeds.astype(rt.dtype), rt, specs,
        causal=False,
    )
    return norm_apply(params["enc_norm"], x, cfg.norm, fused=rt.fused_backward)


# ------------------------------------------------------------------- forward
def _decoder_input(
    cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array], rt: Runtime
) -> Tuple[jax.Array, Optional[jax.Array], int]:
    """Returns (x (B,S,d), memory, n_prefix) — n_prefix = non-text positions."""
    tok = embed_apply(params["embed"], batch["tokens"], rt.dtype)
    memory = None
    n_prefix = 0
    if cfg.frontend == "vision":
        img = batch["frontend_embeds"].astype(rt.dtype)
        tok = jnp.concatenate([img, tok], axis=1)
        n_prefix = img.shape[1]
    elif cfg.is_encdec:
        memory = encode(cfg, params, batch["frontend_embeds"], rt)
    return tok, memory, n_prefix


def forward_hidden(
    cfg: ArchConfig,
    params: Params,
    batch: Dict[str, jax.Array],
    rt: Runtime,
) -> Tuple[jax.Array, jax.Array]:
    """Training forward up to (but not including) the vocab projection.

    Returns (hidden (B, S, d) after the final norm, aux). Splitting here lets
    ``loss_fn`` route the head through the chunked cross-entropy path without
    ever materializing (B, S, V) logits.
    """
    x, memory, _ = _decoder_input(cfg, params, batch, rt)
    specs = layer_specs(cfg, seq_len=x.shape[1], long_variant=rt.long_variant)
    x, aux, _ = stack_mod.stack_forward(
        cfg, params["stack"], x, rt, specs, memory=memory
    )
    x = norm_apply(params["final_norm"], x, cfg.norm, fused=rt.fused_backward)
    return x, aux


def forward(
    cfg: ArchConfig,
    params: Params,
    batch: Dict[str, jax.Array],
    rt: Runtime,
) -> Tuple[jax.Array, jax.Array]:
    """Training forward: logits over the full sequence. Returns (logits, aux)."""
    x, aux = forward_hidden(cfg, params, batch, rt)
    logits = logits_apply(params.get("head"), params["embed"], x, cfg.tie_embeddings)
    return logits, aux


Z_LOSS_DEFAULT = 1e-4


def masked_token_ce(
    ll: jax.Array, logz: jax.Array, labels: jax.Array,
    z_loss: float = Z_LOSS_DEFAULT,
) -> Tuple[jax.Array, jax.Array]:
    """(xent, z_loss) from per-token (label log-lik, logZ); labels -1 masked.

    The one definition of the token loss — shared by ``loss_fn`` (dense and
    chunked-CE heads) and the pipeline trainer's last stage, so the 2D and
    3D paths cannot drift apart.
    """
    mask = (labels >= 0).astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    xent = -(ll * mask).sum() / denom
    zl = z_loss * ((logz**2) * mask).sum() / denom
    return xent, zl


def loss_fn(
    cfg: ArchConfig,
    params: Params,
    batch: Dict[str, jax.Array],
    rt: Runtime,
    z_loss: float = Z_LOSS_DEFAULT,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy (+ router aux + z-loss). labels -1 are masked.

    ``rt.fused_backward`` routes the head through the vocab-chunked CE op
    (repro.kernels.chunked_ce): same loss/grads as the dense path, but the
    (B, S, V) logits and their gradient are never materialized at once.
    """
    h, aux = forward_hidden(cfg, params, batch, rt)
    labels = batch["labels"]
    if cfg.frontend == "vision":  # image prefix positions carry no loss
        n_prefix = batch["frontend_embeds"].shape[1]
        h = h[:, n_prefix:]
    safe = jnp.maximum(labels, 0)
    with jax.named_scope("lm_head"):
        if rt.fused_backward:
            from repro.kernels.chunked_ce import chunked_ce

            w = (
                params["embed"]["table"]
                if cfg.tie_embeddings
                else params["head"]["w"].T
            )
            lab, logz = chunked_ce(h, w, safe, rt.ce_chunk)
            ll = lab - logz
        else:
            logits = logits_apply(
                params.get("head"), params["embed"], h, cfg.tie_embeddings
            )
            logz = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0] - logz
        xent, zl = masked_token_ce(ll, logz, labels, z_loss)
    total = xent + zl + cfg.router_aux_coef * aux
    metrics = {"loss": total, "xent": xent, "aux": aux, "z_loss": zl}
    return total, metrics


# ------------------------------------------------------------- 3D training
def pipeline_fns(cfg: ArchConfig, rt: Runtime, tp: int = 1):
    """(first_fn, stage_fn, last_fn) for ``repro.core.pipeline.pipeline_grads``.

    Splits the training forward at the plan's stage boundaries: stage 0
    embeds (first_fn), every stage applies its layer slice via the manual-TP
    ``stack_stage_apply`` (stage_fn — returns the router-aux loss term so
    MoE aux gradients flow from every stage), and the last stage runs final
    norm + head + masked cross-entropy with z-loss (last_fn), numerically
    identical to ``loss_fn``'s dense path per microbatch. Shared params
    (embed / final_norm / head) are replicated over the pipe axis; tied
    embeddings get their two contributions summed by the runner's psum.

    Loss normalization caveat: the step loss is the uniform mean of
    per-(microbatch, data-shard) masked means. With -1-masked labels whose
    valid-token counts differ across microbatches this weights microbatches
    equally rather than tokens (the microbatched-training standard; a
    global token mean would need the total valid count before any backward
    seeds, i.e. a second pass). Identical across schedules either way — it
    only differs from the 2D single-mean trainer on unevenly-masked
    batches.
    """
    from repro.models.stack import (
        pipeline_incompatibility, stack_stage_apply, stage_layer_params,
    )

    why = pipeline_incompatibility(cfg, tp)
    if why is not None:
        raise ValueError(f"{cfg.name}: {why}")
    kind = cfg.pattern[0]
    window = cfg.sliding_window if kind == "local" else 0
    spec = LayerSpec(kind, window, 0)

    def first_fn(shared: Params, mb: Dict[str, jax.Array]) -> jax.Array:
        return embed_apply(shared["embed"], mb["tokens"], rt.dtype)

    def stage_fn(sp: Params, x: jax.Array):
        y, aux = stack_stage_apply(
            cfg, stage_layer_params(sp), x, rt, spec, tp=tp
        )
        return y, cfg.router_aux_coef * aux

    def last_fn(shared: Params, y: jax.Array, mb: Dict[str, jax.Array]):
        h = norm_apply(shared["final_norm"], y, cfg.norm)
        with jax.named_scope("lm_head"):
            logits = logits_apply(
                shared.get("head"), shared["embed"], h, cfg.tie_embeddings
            )
            labels = mb["labels"]
            safe = jnp.maximum(labels, 0)
            logz = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0] - logz
            xent, zl = masked_token_ce(ll, logz, labels)
            return xent + zl, {"xent": xent, "z_loss": zl}

    return first_fn, stage_fn, last_fn


# ------------------------------------------------------------------- serving
def prefill(
    cfg: ArchConfig,
    params: Params,
    batch: Dict[str, jax.Array],
    rt: Runtime,
    max_len: Optional[int] = None,
    gather_pos: Optional[jax.Array] = None,
    full_cache: bool = False,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Forward over the prompt, returning last-position logits + decode cache.

    ``max_len`` sizes the kv caches for the decode horizon (default: prompt
    length — i.e. ring-buffer reuse from the first generated token).
    ``gather_pos`` (traced scalar) selects which position's logits to return
    instead of the last — the bucketed-prefill path pads prompts to a shape
    bucket on the right and gathers at the true final position.
    ``full_cache`` collects every position for sliding-window layers too
    (cache_len = horizon instead of the window) — required by the paged
    serve engine, whose pool keeps all positions: a window-sized ring would
    drop real in-window tokens whenever the prompt is right-padded past the
    window (bucketed prefill).
    """
    x, memory, _ = _decoder_input(cfg, params, batch, rt)
    S = x.shape[1]
    specs = layer_specs(cfg, seq_len=S, long_variant=rt.long_variant)
    cache_specs = layer_specs(
        cfg, seq_len=max_len or S, long_variant=rt.long_variant
    )
    if full_cache:
        cache_specs = tuple(
            s._replace(cache_len=max_len or S)
            if s.kind in ("attn", "local") else s
            for s in cache_specs
        )
    x, _, caches = stack_mod.stack_forward(
        cfg, params["stack"], x, rt, specs, memory=memory, collect_cache=True,
        cache_specs=cache_specs,
    )
    if gather_pos is None:
        x = x[:, -1:]
    else:
        x = jax.lax.dynamic_slice_in_dim(x, gather_pos, 1, axis=1)
    x = norm_apply(params["final_norm"], x, cfg.norm)
    logits = logits_apply(params.get("head"), params["embed"], x, cfg.tie_embeddings)
    state = {"caches": caches, "t": jnp.array(S, jnp.int32)}
    if memory is not None:
        state["memory"] = memory
    return logits[:, 0], state


def init_decode_state(
    cfg: ArchConfig, params: Params, B: int, seq_len: int, rt: Runtime
) -> Dict[str, Any]:
    """Zero cache sized for a ``seq_len`` context (dry-run / bench entry)."""
    specs = layer_specs(cfg, seq_len=seq_len, long_variant=rt.long_variant)
    enc_len = cfg.frontend_tokens if cfg.is_encdec else 0
    caches = stack_mod.init_stack_cache(cfg, params["stack"], B, rt, specs, enc_len)
    state: Dict[str, Any] = {"caches": caches, "t": jnp.array(seq_len - 1, jnp.int32)}
    if cfg.is_encdec:
        state["memory"] = jnp.zeros((B, enc_len, cfg.d_model), rt.dtype)
    return state


def init_paged_state(
    cfg: ArchConfig,
    B: int,
    rt: Runtime,
    *,
    num_pages: int,
    page_size: int,
    max_len: int,
) -> Dict[str, Any]:
    """Paged decode state: per-layer KV page pools shared by ``B`` slots.

    ``tables`` rows are all-zero (null page) until the serve engine admits a
    request into the slot; ``lengths`` count cached tokens per slot. With
    ``rt.mesh`` set the pools are laid out head-sharded over the ``model``
    axis from the start (``sharding.specs.paged_state_specs``) — each device
    holds ``Kv / tp`` heads of every page — and the slot-addressing arrays
    are committed replicated so host-side ``.at[].set`` updates stay on the
    mesh.
    """
    specs = layer_specs(cfg, seq_len=max_len, long_variant=rt.long_variant)
    table_width = -(-max_len // page_size)

    def build() -> Dict[str, Any]:
        return {
            "caches": stack_mod.init_stack_pool(
                cfg, rt, specs, num_pages, page_size
            ),
            "tables": jnp.zeros((B, table_width), jnp.int32),
            "lengths": jnp.zeros((B,), jnp.int32),
        }

    if rt.mesh is None:
        return build()
    from repro.sharding.specs import paged_state_specs, with_sharding

    shardings = with_sharding(
        rt.mesh, paged_state_specs(cfg, jax.eval_shape(build), rt.mesh)
    )
    # allocate sharded from the start: a pool sized for TP-sharded capacity
    # need never fit on one chip, so no single-device staging copy
    return jax.jit(build, out_shardings=shardings)()


def paged_forward(
    cfg: ArchConfig,
    params: Params,
    caches: List[Any],
    tables: jax.Array,
    tokens: jax.Array,
    start: jax.Array,
    q_len: jax.Array,
    rt: Runtime,
    max_len: int,
    *,
    all_rows: bool = False,
) -> Tuple[jax.Array, List[Any]]:
    """T token rows per slot through the paged pool: the one forward of
    paged serving.

    tokens: (B, T) int32 at absolute positions ``start[b] ..
    start[b] + T - 1``; tables: (B, P) block-table rows; q_len: (B,) valid
    rows per slot (rows past it are right-padding; 0 disables a slot: its
    rows write the null page). Returns (logits, new caches): each slot's
    row ``q_len - 1``, (B, V), picked before the head so a long chunk runs
    a one-row head; or every row, (B, T, V), with ``all_rows``. The caller
    owns ``lengths``:

    * decode step: T = 1, ``start = lengths``, ``q_len = active``; the
      caller then advances ``lengths`` by ``active``.
    * prefill chunk: B = 1, the request's table row. The logits only
      matter on the prompt's final chunk (first-token sampling). With a
      cached prefix adopted from the radix cache the first chunk starts at
      ``start = cached_tokens``, so the shared prefix is only attended.
    * speculative verify: T = k + 1 rows, the pending token then k drafts,
      ``all_rows``. Row t attends exactly ``kpos <= start + t`` (write-
      then-attend, ``attention_paged``) — the band a sequential decode
      step at that position would see — so rows reproduce the sequential
      greedy stream, and rejected rows need no device rollback: their KV
      sits past the committed length, is never attended there, and is
      overwritten before any later attend. ``PagePool.truncate`` is the
      host-side half of the rollback.
    """
    specs = layer_specs(cfg, seq_len=max_len, long_variant=rt.long_variant)
    x = embed_apply(params["embed"], tokens, rt.dtype)            # (B, T, d)
    x, caches = stack_mod.stack_paged(
        cfg, params["stack"], x, caches, tables, start, q_len, rt, specs,
    )
    if not all_rows and x.shape[1] > 1:   # each slot's row q_len - 1: (B, 1, d)
        x = jax.vmap(
            lambda h, i: jax.lax.dynamic_slice_in_dim(h, i, 1)
        )(x, q_len - 1)
    x = norm_apply(params["final_norm"], x, cfg.norm)
    logits = logits_apply(params.get("head"), params["embed"], x, cfg.tie_embeddings)
    return (logits if all_rows else logits[:, 0]), caches


def decode_step(
    cfg: ArchConfig,
    params: Params,
    state: Dict[str, Any],
    token: jax.Array,
    rt: Runtime,
    seq_len: int,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step. token: (B,) int32. Returns (logits (B, V), new state)."""
    specs = layer_specs(cfg, seq_len=seq_len, long_variant=rt.long_variant)
    x = embed_apply(params["embed"], token[:, None], rt.dtype)
    t = state["t"]
    x, caches = stack_mod.stack_decode(
        cfg, params["stack"], x, state["caches"], t, rt, specs
    )
    x = norm_apply(params["final_norm"], x, cfg.norm)
    logits = logits_apply(params.get("head"), params["embed"], x, cfg.tie_embeddings)
    new_state = dict(state, caches=caches, t=t + 1)
    return logits[:, 0], new_state

"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Two execution modes share one inner implementation:

* **local** — all experts on the current device (single-device smoke tests,
  or pure data-parallel runs).
* **expert-parallel** — wrapped in ``jax.shard_map`` over the ``model`` mesh
  axis by the distributed runtime (see ``repro.sharding.specs``): activations
  arrive replicated over ``model``; each device routes *all* local tokens,
  keeps the slots destined for its E/ep local experts, computes, and a final
  ``psum`` over ``model`` re-combines. No gshard one-hot dispatch einsums are
  used — their O(T*E*C*d) mask matmuls would dominate (and falsify) the
  HLO FLOP roofline; sort-based dispatch costs only the real expert FLOPs
  plus an O(T k log(T k)) sort.

Capacity: each expert processes at most C = ceil(cf * T_local * k / E)
tokens; overflow tokens are dropped (their combine weight contribution is 0)
per standard capacity-factor routing.

Shared experts / Arctic's dense-residual path are mathematically folded into
one always-on gated MLP (concatenating independent gated MLPs' hidden units
is exact) handled in the block, not here.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense_init, subkey


def init_moe(
    key: jax.Array, d: int, d_ff: int, n_experts: int, gated: bool
) -> Params:
    def stack(tag: str, d_in: int, d_out: int) -> jax.Array:
        keys = jax.random.split(subkey(key, tag), n_experts)
        return jax.vmap(lambda k: dense_init(k, d_in, d_out))(keys)

    p: Params = {
        "router": dense_init(subkey(key, "router"), d, n_experts),
        "w_up": stack("up", d, d_ff),
        "w_down": stack("down", d_ff, d),
    }
    if gated:
        p["w_gate"] = stack("gate", d, d_ff)
    return p


def capacity(n_tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    return max(1, math.ceil(cf * n_tokens * top_k / n_experts))


def moe_apply(
    p: Params,
    x: jax.Array,
    *,
    top_k: int,
    capacity_factor: float,
    gated: bool,
    axis_name: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    When ``axis_name`` is set, this function runs *inside* shard_map: the
    expert leaves of ``p`` are the local E/ep shard and the output is psum'd.
    """
    B, S, d = x.shape
    dtype = x.dtype
    T = B * S
    xt = x.reshape(T, d)

    E_local = p["w_up"].shape[0]
    if axis_name is None:
        E_total, e0 = E_local, 0
    else:
        ep = jax.lax.axis_size(axis_name)
        E_total = E_local * ep
        e0 = jax.lax.axis_index(axis_name) * E_local

    # ---- routing (identical on every model-shard: router is replicated) ----
    logits = (xt @ p["router"].astype(dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, top_k)                 # (T, k)
    gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)

    # load-balance aux loss (Switch/GShard form), from the full router view
    frac_routed = jnp.mean(
        jax.nn.one_hot(gate_idx, E_total, dtype=jnp.float32), axis=(0, 1)
    )
    aux = E_total * jnp.sum(frac_routed * jnp.mean(probs, axis=0))

    # ---- slot bookkeeping: one slot per (token, choice) --------------------
    n_slots = T * top_k
    slot_expert = gate_idx.reshape(n_slots)                       # global ids
    slot_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)
    slot_w = gate_w.reshape(n_slots)

    local = (slot_expert >= e0) & (slot_expert < e0 + E_local)
    le = jnp.where(local, slot_expert - e0, E_local)              # E_local = trash
    order = jnp.argsort(le, stable=True)
    le_s = le[order]
    tok_s = slot_token[order]
    w_s = slot_w[order]

    # position of each sorted slot within its expert group
    group_start = jnp.searchsorted(le_s, jnp.arange(E_local + 1, dtype=le_s.dtype))
    pos = jnp.arange(n_slots, dtype=jnp.int32) - group_start[
        jnp.clip(le_s, 0, E_local)
    ].astype(jnp.int32)

    C = capacity(T, top_k, E_total, capacity_factor)
    keep = (le_s < E_local) & (pos < C)

    dest = jnp.where(keep, le_s.astype(jnp.int32) * C + pos, E_local * C)
    tok_for_slot = jnp.full((E_local * C + 1,), -1, jnp.int32).at[dest].set(
        jnp.where(keep, tok_s, -1)
    )[:-1]
    w_for_slot = jnp.zeros((E_local * C + 1,), jnp.float32).at[dest].set(
        jnp.where(keep, w_s, 0.0)
    )[:-1]

    # ---- gather -> expert MLPs -> weighted scatter-add ----------------------
    valid = tok_for_slot >= 0
    xin = jnp.where(
        valid[:, None], jnp.take(xt, jnp.clip(tok_for_slot, 0), axis=0), 0
    ).reshape(E_local, C, d)

    up = jnp.einsum("ecd,edf->ecf", xin, p["w_up"].astype(dtype))
    if gated:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"].astype(dtype))) * up
    else:
        h = jax.nn.silu(up)
    y_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dtype))
    y_buf = y_buf.reshape(E_local * C, d) * w_for_slot[:, None].astype(dtype)

    out = (
        jnp.zeros((T + 1, d), dtype)
        .at[jnp.where(valid, tok_for_slot, T)]
        .add(y_buf)[:-1]
    )
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return out.reshape(B, S, d), aux

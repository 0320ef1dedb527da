"""Plain float32 reference of the dense decoder family (Llama-style and
GPT-BigCode-style Granite Code models), in straightforward ``jax.numpy``.

    x = embed[tokens]
    for each layer:  x = x + attn(norm1(x));  x = x + mlp(norm2(x))
    logits = norm_f(x) @ head

* norm: RMSNorm (``norm == "rmsnorm"``) or LayerNorm with bias, eps from
  the configuration; computed in f32.
* attention: causal, grouped-query (``num_key_value_heads`` kv heads shared
  by ``num_attention_heads / num_key_value_heads`` query heads each), RoPE
  on q and k with the half-split rotation (first half of the head against
  the second), queries scaled by ``head_dim ** -0.5``.
* mlp: ``silu(x W_gate) * (x W_up) W_down`` when gated, else
  ``silu(x W_up) W_down``.
* loss: mean next-token cross entropy plus ``z_loss * mean(logZ^2)``.

No kernels, no cache, no batching tricks: one sequence (or one batch) at a
time, a layer at a time, queries in blocks so that the score matrix of a
long sequence fits. Matmuls run under ``precision=HIGHEST``.

``mode="fp8"`` is the control: the same computation with every matmul
operand rounded to float8_e4m3 (per-tensor scale, amax to 448) and
accumulated in f32 — the next precision below the bf16 the configurations
state. It is never run by the benchmark itself, only by the control check.

Weights come in the tree layout the benchmark generates
(``chipbench.weights``); nothing here imports the program.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _round_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    return _round_fp8(x)


# backward: the cotangent is rounded the same way, with its own scale
_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def _q(x, mode: str):
    return _fp8(x) if mode == "fp8" else x


def _mm(a, b, mode: str):
    return jnp.matmul(_q(a, mode), _q(b, mode), precision=HIGHEST)


def _norm(p: Dict[str, Any], x, kind: str, eps: float):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y


def _rope(x, pos, theta: float):
    """x (..., S, heads, hd), pos (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, c: Dict[str, Any], mode: str, q_block: int):
    """h (B, S, d) -> (B, S, d), causal GQA in query blocks."""
    B, S, _ = h.shape
    H, Kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    G = H // Kv
    pos = jnp.arange(S)
    q = _mm(h, p["wq"].astype(jnp.float32), mode).reshape(B, S, H, hd)
    k = _mm(h, p["wk"].astype(jnp.float32), mode).reshape(B, S, Kv, hd)
    v = _mm(h, p["wv"].astype(jnp.float32), mode).reshape(B, S, Kv, hd)
    q = _rope(q, pos, c["rope_theta"]) * hd ** -0.5
    k = _rope(k, pos, c["rope_theta"])
    q = q.reshape(B, S, Kv, G, hd)
    kq, vq = _q(k, mode), _q(v, mode)
    nb = -(-S // q_block)
    qp = jnp.pad(q, ((0, 0), (0, nb * q_block - S), (0, 0), (0, 0), (0, 0)))
    qb = qp.reshape(B, nb, q_block, Kv, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def block(i, qi):
        s = jnp.einsum("bqkgh,btkh->bkgqt", _q(qi, mode), kq, precision=HIGHEST)
        qpos = i * q_block + jnp.arange(q_block)
        mask = pos[None, :] <= qpos[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqt,btkh->bqkgh", _q(w, mode), vq, precision=HIGHEST)

    out = jax.lax.map(lambda a: block(*a), (jnp.arange(nb), qb))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nb * q_block, H * hd)[:, :S]
    return _mm(out, p["wo"].astype(jnp.float32), mode)


def _mlp(p, h, c, mode: str):
    up = _mm(h, p["w_up"].astype(jnp.float32), mode)
    if c["mlp_gated"]:
        a = jax.nn.silu(_mm(h, p["w_gate"].astype(jnp.float32), mode)) * up
    else:
        a = jax.nn.silu(up)
    return _mm(a, p["w_down"].astype(jnp.float32), mode)


def _block(c, mode, q_block, x, lp):
    h = _norm(lp["norm1"], x, c["norm"], c["norm_eps"])
    x = x + _attention(lp["mixer"], h, c, mode, q_block)
    h = _norm(lp["norm2"], x, c["norm"], c["norm_eps"])
    return x + _mlp(lp["ffn"], h, c, mode)


def hidden(c: Dict[str, Any], params, tokens, mode: str = "f32",
           q_block: int = 512, remat: bool = False):
    """Final-normed hidden states (B, S, d) in f32. ``params`` may hold any
    float dtype; each layer's weights are upcast as the layer runs."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    body = functools.partial(_block, c, mode, q_block)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda x, lp: (body(x, lp), None), x,
                        params["stack"]["g0"]["p0"])
    return _norm(params["final_norm"], x, c["norm"], c["norm_eps"])


def head_logits(c, params, h, mode: str = "f32"):
    """(..., d) hidden -> (..., vocab_size) logits in f32."""
    w = params["head"]["w"] if "head" in params else params["embed"]["table"].T
    return _mm(h, w.astype(jnp.float32), mode)[..., : c["vocab_size"]]


@functools.partial(jax.jit, static_argnames=("c_items", "mode"))
def _logits_at(c_items, mode, params, tokens, rows):
    c = dict(c_items)
    h = hidden(c, params, tokens[None], mode)[0]
    return head_logits(c, params, h[rows], mode)


def logits_at(c: Dict[str, Any], params, tokens, rows, mode: str = "f32"):
    """Logits (len(rows), vocab_size) of one sequence at positions ``rows``.
    ``tokens`` should be padded to a few bucket lengths by the caller so
    that few programs compile; causal attention makes the padding inert."""
    return _logits_at(_items(c), mode, params, tokens, rows)


def _items(c):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "mlp_gated", "norm", "norm_eps", "vocab_size")
    return tuple((k, c[k]) for k in keys)


def loss(c: Dict[str, Any], params, tokens, labels, mode: str = "f32",
         z_loss: float = 1e-4, rows: int = 1024):
    """Mean next-token cross entropy + z-loss over all tokens; the head is
    applied in blocks of ``rows`` tokens so the logits never exist whole."""
    h = hidden(c, params, tokens, mode, remat=True)
    d = h.shape[-1]
    rows = min(rows, labels.size)
    h = h.reshape(-1, rows, d)
    lab = labels.reshape(-1, rows)

    @jax.checkpoint
    def part(hb, lb):
        lg = head_logits(c, params, hb, mode)
        logz = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, lb[:, None], axis=-1)[:, 0] - logz
        return jnp.stack([-ll.sum(), (logz ** 2).sum()])

    sums = jax.lax.map(lambda a: part(*a), (h, lab)).sum(0)
    n = labels.size
    return sums[0] / n + z_loss * sums[1] / n


@functools.partial(jax.jit, static_argnames=("c_items", "mode", "z_loss"))
def _loss_and_grad(c_items, mode, z_loss, params, tokens, labels):
    c = dict(c_items)
    return jax.value_and_grad(
        lambda p: loss(c, p, tokens, labels, mode, z_loss))(params)


def loss_and_grad(c, params, tokens, labels, mode: str = "f32",
                  z_loss: float = 1e-4):
    """(loss, grads) with f32 grads in the tree of ``params``."""
    return _loss_and_grad(_items(c), mode, float(z_loss), params, tokens,
                          labels)

"""Fused backward kernels vs oracle gradients.

Covers the three fused paths of the backward tier:
  * flash-attention dq/dk/dv (GQA group sizes, causal, sliding window,
    non-multiple-of-block sequence lengths)
  * fused RMSNorm dx/dscale
  * chunked cross-entropy head (loss + grads vs the dense oracle, plus a
    jaxpr-level assertion that (B, S, V) logits are never materialized)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.kernel

from repro.kernels.chunked_ce import chunked_ce
from repro.kernels.chunked_ce.ref import chunked_ce_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref


# ------------------------------------------------------------ flash attention
def _qkv_cot(B, S, Kv, G, hd, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, S, Kv, G, hd), jnp.float32) * hd**-0.5
    k = jnp.asarray(rng.randn(B, S, Kv, hd), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Kv, hd), jnp.float32)
    cot = jnp.asarray(rng.randn(B, S, Kv, G, hd), jnp.float32)
    return q, k, v, cot


# (B, S, Kv, G, hd, causal, window): GQA sweep, causal on/off, sliding
# windows, and sequence lengths that are not block multiples.
FA_CASES = [
    (1, 128, 2, 1, 32, True, 0),     # MHA-style, block-aligned
    (2, 64, 1, 4, 16, True, 0),      # MQA, group accumulation over G=4
    (1, 128, 2, 2, 32, False, 0),    # non-causal
    (1, 128, 2, 2, 16, True, 32),    # sliding window
    (1, 130, 2, 2, 16, True, 0),     # non-multiple-of-block S
    (1, 250, 1, 2, 16, True, 64),    # non-multiple S + window
    # from S = 512: dead tiles above the diagonal and left of a window band
    (1, 1024, 1, 4, 32, True, 0),    # G=4: the dkv q-block clamp per member
    (1, 1024, 2, 4, 16, True, 200),  # G=4, dead tiles on both band sides
    (1, 640, 1, 2, 128, True, 0),    # head_dim 128
    (1, 520, 1, 4, 16, True, 128),   # non-multiple S, window = one block
    (1, 512, 2, 2, 16, False, 0),    # non-causal: every tile full
]


@pytest.mark.parametrize("case", FA_CASES, ids=[str(c) for c in FA_CASES])
def test_flash_attention_grads_match_ref(case):
    B, S, Kv, G, hd, causal, window = case
    q, k, v, cot = _qkv_cot(B, S, Kv, G, hd, seed=sum(case[:5]))

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, window) * cot)

    def f_ref(q, k, v):
        return jnp.sum(
            flash_attention_ref(q, k, v, causal=causal, window=window) * cot
        )

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gk, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
            err_msg=f"d{name} mismatch for {case}",
        )


def test_flash_attention_bf16_grads_close():
    q, k, v, cot = _qkv_cot(1, 128, 2, 2, 32, seed=9)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    gk = jax.grad(
        lambda q_, k_, v_: jnp.sum(
            flash_attention(q_, k_, v_, True, 0).astype(jnp.float32) * cot
        ),
        argnums=(0, 1, 2),
    )(qb, kb, vb)
    gr = jax.grad(
        lambda q_, k_, v_: jnp.sum(
            flash_attention_ref(q_, k_, v_, causal=True) * cot
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gk, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), atol=5e-2, rtol=5e-2
        )


# ---------------------------------------------------------------- rmsnorm bwd
@pytest.mark.parametrize("shape", [(8, 128), (2, 300, 64), (1, 7, 96)])
def test_rmsnorm_fused_bwd_matches_ref(shape):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    s = jnp.asarray(rng.rand(shape[-1]) + 0.5, jnp.float32)
    cot = jnp.asarray(rng.randn(*shape), jnp.float32)
    gk = jax.grad(
        lambda x_, s_: jnp.sum(rmsnorm(x_, s_) * cot), argnums=(0, 1)
    )(x, s)
    gr = jax.grad(
        lambda x_, s_: jnp.sum(rmsnorm_ref(x_, s_) * cot), argnums=(0, 1)
    )(x, s)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )


# ------------------------------------------------------------------ chunked CE
def _ce_problem(B=2, S=48, d=16, V=1000, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, S, d), jnp.float32)
    w = jnp.asarray(rng.randn(V, d), jnp.float32) * 0.1
    labels = jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32)
    return x, w, labels


@pytest.mark.parametrize("chunk", [128, 256, 1000, 4096])
def test_chunked_ce_matches_dense(chunk):
    x, w, labels = _ce_problem()

    def loss(ce):
        def f(x_, w_):
            ll, logz = ce(x_, w_)
            return jnp.mean(logz - ll) + 1e-4 * jnp.mean(logz**2)

        return f

    lc = loss(lambda x_, w_: chunked_ce(x_, w_, labels, chunk))
    lr = loss(lambda x_, w_: chunked_ce_ref(x_, w_, labels))
    np.testing.assert_allclose(float(lc(x, w)), float(lr(x, w)), rtol=1e-5)
    gc = jax.grad(lc, argnums=(0, 1))(x, w)
    gr = jax.grad(lr, argnums=(0, 1))(x, w)
    for a, b in zip(gc, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for item in vals:
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner)


def _max_intermediate_size(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    sizes = [0]
    for eqn in _iter_eqns(jaxpr.jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "size"):
                sizes.append(int(aval.size))
    return max(sizes)


def test_chunked_ce_backward_never_materializes_logits():
    """No intermediate in the chunked fwd+bwd reaches (B, S, V) size."""
    B, S, d, V, chunk = 2, 64, 16, 1024, 128
    x, w, labels = _ce_problem(B, S, d, V)
    full = B * S * V

    def loss_c(x_, w_):
        ll, logz = chunked_ce(x_, w_, labels, chunk)
        return jnp.mean(logz - ll)

    def loss_d(x_, w_):
        ll, logz = chunked_ce_ref(x_, w_, labels)
        return jnp.mean(logz - ll)

    chunked_max = _max_intermediate_size(jax.grad(loss_c, (0, 1)), x, w)
    dense_max = _max_intermediate_size(jax.grad(loss_d, (0, 1)), x, w)
    assert dense_max >= full  # the oracle DOES materialize logits
    assert chunked_max < full, (chunked_max, full)
    # largest chunked intermediate is the (B, S, chunk) tile or the (V, d)
    # weight grad, whichever is bigger
    assert chunked_max <= max(B * S * chunk, V * d)


def test_chunked_ce_respects_masked_label_convention():
    """Masked (-1) labels are clipped by the caller; grads stay finite."""
    x, w, labels = _ce_problem(seed=4)
    labels = labels.at[:, ::3].set(-1)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)

    def loss(x_, w_):
        ll, logz = chunked_ce(x_, w_, safe, 256)
        return jnp.sum((logz - ll) * mask) / jnp.maximum(mask.sum(), 1.0)

    g = jax.grad(loss, argnums=(0, 1))(x, w)
    for a in g:
        assert np.isfinite(np.asarray(a)).all()


# ------------------------------------------------------- end-to-end train step
def test_fused_backward_train_step_matches_baseline():
    from repro.configs import SURVEY_DEMO, reduced
    from repro.data import DataPipeline
    from repro.optim import get as get_opt
    from repro.train import TrainConfig, make_state, make_train_step

    tiny = reduced(SURVEY_DEMO, n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=2, d_ff=128, vocab_size=512)

    def losses(tc, steps=2):
        opt = get_opt(tc.optimizer, 1e-3)
        state = make_state(tiny, opt, tc, seed=0)
        step = make_train_step(tiny, opt, tc)
        data = DataPipeline(tiny, batch_size=4, seq_len=64, seed=0)
        out = []
        try:
            for _ in range(steps):
                batch = {k: jnp.asarray(v) for k, v in next(data).items()}
                state, m = step(state, batch)
                out.append(float(m["loss"]))
        finally:
            data.close()
        return out

    base = losses(TrainConfig())
    fused = losses(TrainConfig(fused_backward=True))
    np.testing.assert_allclose(base, fused, rtol=2e-4, atol=2e-4)

"""The f32 reference against the program at a tiny width, on the CPU: the
same generated weights give the same logits, loss and gradients, for both
block kinds the configurations use (gated MLP + RMSNorm, plain MLP +
LayerNorm)."""

import numpy as np
import pytest

import tiny  # noqa: F401  (puts src/ and the benchmark on the path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import weights  # noqa: E402
from references import dense_decoder as ref  # noqa: E402


def _setup(arch):
    from repro.configs import get_reduced

    cfg = get_reduced(arch)
    c = {"num_attention_heads": cfg.n_heads,
         "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
         "rope_theta": cfg.rope_theta, "mlp_gated": cfg.mlp_gated,
         "norm": cfg.norm, "norm_eps": 1e-6, "vocab_size": cfg.vocab_size}
    from repro.models import init_params

    struct = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    params = weights.generate(struct, 123, jnp.float32)
    # biases are generated as zeros; give them values so they are tested
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: x + 0.1 if "bias" in jax.tree_util.keystr(p) else x,
        params)
    return cfg, c, params


@pytest.mark.parametrize("arch", ["granite-8b", "granite-34b"])
def test_logits_match_program(arch):
    from repro.models import Runtime, forward

    cfg, c, params = _setup(arch)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 48))
    with jax.default_matmul_precision("highest"):
        prog, _ = forward(cfg, params, {"tokens": jnp.asarray(toks)},
                          Runtime(dtype=jnp.float32))
    rows = jnp.arange(48)
    got = ref.logits_at(c, params, jnp.asarray(toks[0]), rows)
    want = np.asarray(prog[0, :, : cfg.vocab_size])
    err = np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want))
    assert err < 1e-5, err


@pytest.mark.parametrize("arch", ["granite-8b", "granite-34b"])
def test_loss_and_grads_match_program(arch):
    from repro.models import Runtime, loss_fn

    cfg, c, params = _setup(arch)
    rng = np.random.default_rng(1)
    t = rng.integers(0, cfg.vocab_size, (2, 33)).astype(np.int32)
    batch = {"tokens": jnp.asarray(t[:, :-1]), "labels": jnp.asarray(t[:, 1:])}
    with jax.default_matmul_precision("highest"):
        (lp, _), gp = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, Runtime(dtype=jnp.float32)),
            has_aux=True)(params)
    lr, gr = ref.loss_and_grad(c, params, batch["tokens"], batch["labels"])
    assert abs(float(lr) - float(lp)) < 1e-5 * abs(float(lp))
    for a, b in zip(jax.tree_util.tree_leaves(gr), jax.tree_util.tree_leaves(gp)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 1e-4 * max(np.max(np.abs(b)), 1e-12)


def test_fp8_control_differs_from_f32():
    """The control really computes in a lower precision."""
    cfg, c, params = _setup("granite-8b")
    toks = jnp.asarray(np.arange(40) % cfg.vocab_size)
    rows = jnp.arange(40)
    a = np.asarray(ref.logits_at(c, params, toks, rows, "f32"))
    b = np.asarray(ref.logits_at(c, params, toks, rows, "fp8"))
    rel = np.max(np.abs(a - b)) / np.max(np.abs(a))
    assert 1e-3 < rel < 0.5, rel

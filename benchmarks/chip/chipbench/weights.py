"""Weights made by the benchmark from ``--seed``, never by the program.

The program is handed a tree with its own parameter layout (read from
``jax.eval_shape`` of its initializer: shapes only, no values), filled here
leaf by leaf from the seed. The f32 reference regenerates the same values
from the same seed, so it takes nothing the program has made.

Rules, by the leaf's path: ``scale`` ones, ``bias`` zeros, the embedding
table N(0, 0.02^2), every other matrix N(0, 1/fan_in) with fan_in its
second-to-last dimension (a stacked layer axis leads). Leaf ``i`` draws
from a key folded from the seed's low and high 32 bits and ``i``. The seed
enters the programs as an argument, so one compiled program serves every
seed.
"""
from __future__ import annotations

import functools
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02


def _path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def leaf_paths(struct: Any) -> List[Tuple[str, Any]]:
    """[(path, ShapeDtypeStruct)] in tree-flatten order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(struct)
    return [(_path_name(p), s) for p, s in flat]


def _seed_words(seed: int):
    s = int(seed) % (2**64)
    return np.uint32(s % (2**32)), np.uint32(s // (2**32))


def _kind(path: str) -> Tuple[str, float]:
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("scale", "bias"):
        return leaf, 0.0
    return "normal", (EMBED_STD if path.startswith("embed") else -1.0)


def _value(kind: str, std: float, shape, dtype, lo, hi, index):
    """One leaf's value (traceable; ``lo``, ``hi``, ``index`` may be traced)."""
    if kind == "scale":
        return jnp.ones(shape, dtype)
    if kind == "bias":
        return jnp.zeros(shape, dtype)
    key = jax.random.PRNGKey(0)
    for word in (lo, hi, index):
        key = jax.random.fold_in(key, word)
    z = jax.random.normal(key, shape, jnp.float32)
    return (z * (std if std > 0 else shape[-2] ** -0.5)).astype(dtype)


def generate(struct: Any, seed: int, dtype, shardings=None) -> Any:
    """Every leaf of ``struct`` in ``dtype``, in one jitted call placed by
    ``shardings`` (a matching tree, or None for the default device)."""
    paths = leaf_paths(struct)
    treedef = jax.tree_util.tree_structure(struct)

    def build(lo, hi):
        leaves = [_value(*_kind(p), s.shape, dtype, lo, hi, np.uint32(i))
                  for i, (p, s) in enumerate(paths)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(*_seed_words(seed))


@functools.lru_cache(maxsize=None)
def _change_norm_fn(kind: str, std: float):
    return jax.jit(lambda x, lo, hi, i: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)
        - _value(kind, std, x.shape, jnp.float32, lo, hi, i)))))


def change_norms(struct: Any, seed: int, tree: Any) -> List[float]:
    """Per leaf, the norm of ``tree`` minus the generated f32 value: how far
    each leaf has moved from where the seed put it. One leaf at a time."""
    lo, hi = _seed_words(seed)
    out = []
    for i, ((p, _), x) in enumerate(zip(leaf_paths(struct),
                                        jax.tree_util.tree_leaves(tree))):
        out.append(float(_change_norm_fn(*_kind(p))(x, lo, hi, np.uint32(i))))
    return out

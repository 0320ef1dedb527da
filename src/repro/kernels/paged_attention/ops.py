"""Public paged-attention ops: chunked prefill, and decode as its T=1 call.

``paged_prefill_attention`` routes between the Pallas kernel (``kernel.py``)
and the pure-jnp oracle (``ref.py``); the kernel is the TPU path, the oracle
doubles as the fast CPU path (interpret-mode Pallas inside a decode scan is
far slower than one gather + einsum). Both share the exact layout contract
documented in ``ref.py``. ``paged_attention`` (decode) is the T=1 call of
the same op, so a decode step and a one-row verify or prefill chunk run the
same program, bit for bit.

Quantized pools: pass ``k_scale``/``v_scale`` ([L,] N, Kv, page) f32 and both
backends dequantize inside the page gather (see ``quant.py``); the scale
operands shard over the same ``model`` kv-head axis as their pools.

Tensor parallelism: with ``mesh`` set and a divisible KV-head count, the op
runs inside ``shard_map`` over the ``model`` axis — each shard holds
``Kv / tp`` heads of the page pools (``sharding.specs.pool_kv_spec``) and
runs the kernel on its local head slice; the block table and lengths are
replicated, so page ids address the same (head-sliced) pages everywhere.
No collective is needed here: per-kv-head outputs are independent, and the
row-sharded ``wo`` matmul downstream carries the reduce.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attention.kernel import paged_prefill_attention_kernel
from repro.kernels.paged_attention.ref import paged_prefill_attention_ref


def tp_size(mesh) -> int:
    return mesh.shape["model"] if mesh is not None and "model" in mesh.shape else 1


def _pool_specs(k_pages, quantized):
    """shard_map specs of the pool (and scale) operands: the kv-head axis
    (dim -3 of ``([L,] N, Kv, page, hd)``) over ``model``."""
    lead = (None,) * (k_pages.ndim - 3)
    pool = P(*lead, "model", None, None)
    return (pool, pool) + ((P(*lead, "model", None),) * 2 if quantized else ())


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    active=None,
    **kw,
) -> jax.Array:
    """Decode attention: the T=1 call of :func:`paged_prefill_attention`,
    one query per slot at position ``lengths - 1``. q: (B, Kv, G, hd)
    pre-scaled; pools ([L,] N, Kv, page, hd) -> (B, Kv, G, hd). ``active``
    (B,) bool makes inactive slots zero-row calls, which the kernel does
    not walk (their output is garbage by contract). Other keyword
    arguments as for :func:`paged_prefill_attention`."""
    lengths = lengths.astype(jnp.int32)
    q_len = (
        jnp.ones_like(lengths) if active is None
        else active.astype(jnp.int32)
    )
    out = paged_prefill_attention(
        q[:, None], k_pages, v_pages, tables, lengths - 1, q_len, **kw,
    )
    return out[:, 0]


def paged_prefill_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    start: jax.Array,
    q_len: jax.Array,
    *,
    window: int = 0,
    use_kernel: bool = True,
    interpret=None,
    mesh=None,
    k_scale=None,
    v_scale=None,
    layer=None,
) -> jax.Array:
    """Chunked-prefill attention over pool pages.

    q: (B, T, Kv, G, hd) pre-scaled, roped at ``start + t``; pools
    ([L,] N, Kv, page, hd), read at ``layer`` when stacked; per-request
    ``start`` (absolute position of row 0) and ``q_len`` (valid rows).
    Returns (B, T, Kv, G, hd).

    Same tensor-parallel contract as :func:`paged_attention`: the kv-head
    axis shards over ``model`` (q axis 2 here), tables / positions stay
    replicated, and no collective runs inside attention.

    The model's one paged forward (``models.lm.paged_forward``) calls this
    op for every row count: decode at T=1, prefill chunks, and the
    speculative-decoding verify pass, whose drafts are written to the pool
    then attended as T=k+1 rows, row ``t`` seeing ``kpos <= start + t`` —
    so verify correctness is exactly chunked-prefill correctness, no
    separate masking code path.
    """
    quantized = k_scale is not None
    layer = jnp.int32(0) if layer is None else layer
    stacked = k_pages.ndim == 5

    def attend(q_, ly_, tbl_, st_, ln_, kp_, vp_, *sc_):
        ks_, vs_ = sc_ if quantized else (None, None)
        kw = dict(window=window, k_scale=ks_, v_scale=vs_,
                  layer=ly_ if stacked else None)
        if use_kernel:
            return paged_prefill_attention_kernel(
                q_, kp_, vp_, tbl_, st_, ln_, interpret=interpret, **kw
            )
        return paged_prefill_attention_ref(q_, kp_, vp_, tbl_, st_, ln_, **kw)

    args = (q, layer, tables, start, q_len, k_pages, v_pages)
    if quantized:
        args = args + (k_scale, v_scale)
    tp = tp_size(mesh)
    if tp > 1 and q.shape[2] % tp == 0:
        head = P(None, None, "model", None, None)
        in_specs = (head, P(), P(None, None), P(None), P(None)) + _pool_specs(
            k_pages, quantized
        )
        fn = shard_map(
            attend,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=head,
            check_vma=False,
        )
        return fn(*args)
    return attend(*args)

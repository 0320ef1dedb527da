"""The main-path Pallas kernels compile for a TPU v5e chip at granite-8b widths.

Interpret mode accepts block shapes and VMEM footprints that the chip's
compiler refuses, so every other kernel test can pass while the chip path
is broken. Here each kernel is lowered and compiled against a described
(not attached) v5e topology: ``jax.experimental.topologies`` plus the
installed TPU compiler, no device needed. Nothing runs, so these tests say
nothing about results or speed — the interpret-mode parity suites do the
former, the chip the latter.

Widths are granite-8b's (``configs/granite_8b.py``): d_model 4096, 32 query
heads over 8 kv heads (GQA group 4), head_dim 128, KV pages of 16 tokens.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, so a module-level call
would make pytest-xdist workers collect different tests. The persistent
compilation cache is off around these compiles — an entry written for a
described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.blockwise_quant.kernel import (
    quantize_pallas,
    stash_dequantize_pallas,
    stash_quantize_pallas,
)
from repro.kernels.flash_attention.kernel import flash_attention_fwd_flat
from repro.kernels.flash_attention.kernel_bwd import flash_attention_bwd_flat
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_attention.kernel import paged_prefill_attention_kernel
from repro.kernels.rmsnorm.kernel import rmsnorm_bwd_pallas, rmsnorm_pallas

D, H, KV, HD, PAGE = 4096, 32, 8, 128, 16
G = H // KV
POOL_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8,
               "fp8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def one_chip():
    """A SingleDeviceSharding on chip 0 of a described v5e:2x2 host, with
    the persistent compilation cache off while the module's tests run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; the HLO must hold the kernel."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kv_dtype", list(POOL_DTYPES))
@pytest.mark.parametrize("T", [1, 64], ids=["decode", "prefill"])
def test_paged_attention_compiles(one_chip, T, kv_dtype):
    B, N, P = 8, 1024, 64
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pool = S((N, KV, PAGE, HD), POOL_DTYPES[kv_dtype])
    scale = S((N, KV, PAGE), jnp.float32)
    tables, rows = S((B, P), jnp.int32), S((B,), jnp.int32)
    quantized = kv_dtype != "bf16"

    if T == 1:
        def fn(q, kp, vp, tbl, ln, ks, vs):
            return paged_attention(
                q, kp, vp, tbl, ln, use_kernel=True, interpret=False,
                k_scale=ks if quantized else None,
                v_scale=vs if quantized else None,
            )
        _compile(fn, S((B, KV, G, HD), jnp.bfloat16), pool, pool, tables,
                 rows, scale, scale)
    else:
        def fn(q, kp, vp, tbl, st, ln, ks, vs):
            return paged_prefill_attention_kernel(
                q, kp, vp, tbl, st, ln, interpret=False,
                k_scale=ks if quantized else None,
                v_scale=vs if quantized else None,
            )
        _compile(fn, S((B, T, KV, G, HD), jnp.bfloat16), pool, pool, tables,
                 rows, rows, scale, scale)


# (kv heads, G, head_dim, T, horizon pages, pool dtype): shapes whose VMEM the
# kernel has to budget. granite-34b's MQA chunk is 12,288 query rows, tiled;
# phi-3-vision's 32 and moonlight's 16 kv heads in decode are more than the
# K/V buffers hold in one program; a quantized pool at a 128k-token horizon.
WIDTH_CASES = {
    "mqa-g48-chunk": (1, 48, 128, 256, 64, "bf16"),
    "phi3v-decode": (32, 1, 96, 1, 64, "bf16"),
    "moonlight-decode": (16, 1, 128, 1, 64, "bf16"),
    "int8-128k-decode": (KV, G, HD, 1, 8192, "int8"),
    "int8-128k-chunk": (KV, G, HD, 256, 8192, "int8"),
}


@pytest.mark.parametrize("case", list(WIDTH_CASES), ids=list(WIDTH_CASES))
def test_paged_attention_widths_compile(one_chip, case):
    Kv, G_, hd, T, P, kv_dtype = WIDTH_CASES[case]
    B, N = 2, 1024
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pool = S((N, Kv, PAGE, hd), POOL_DTYPES[kv_dtype])
    scale = S((N, Kv, PAGE), jnp.float32)
    rows = S((B,), jnp.int32)
    quantized = kv_dtype != "bf16"
    _compile(
        lambda q, kp, vp, tbl, st, ln, ks, vs: paged_prefill_attention_kernel(
            q, kp, vp, tbl, st, ln, interpret=False,
            k_scale=ks if quantized else None,
            v_scale=vs if quantized else None,
        ),
        S((B, T, Kv, G_, hd), jnp.bfloat16), pool, pool,
        S((B, P), jnp.int32), rows, rows, scale, scale,
    )


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles(one_chip, direction):
    B, Sq = 2, 2048
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    q = S((B * H, Sq, HD), jnp.bfloat16)
    kv = S((B * KV, Sq, HD), jnp.bfloat16)
    if direction == "fwd":
        _compile(
            lambda q, k, v: flash_attention_fwd_flat(
                q, k, v, group=G, interpret=False
            ),
            q, kv, kv,
        )
    else:
        _compile(
            lambda q, k, v, o, lse, do: flash_attention_bwd_flat(
                q, k, v, o, lse, do, group=G, interpret=False
            ),
            q, kv, kv, q, S((B * H, 1, Sq), jnp.float32), q,
        )


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_rmsnorm_compiles(one_chip, direction):
    x = jax.ShapeDtypeStruct((2 * 2048, D), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((D,), jnp.float32, sharding=one_chip)
    if direction == "fwd":
        _compile(lambda x, s: rmsnorm_pallas(x, s, interpret=False), x, s)
    else:
        _compile(
            lambda x, s, g: rmsnorm_bwd_pallas(x, s, g, interpret=False),
            x, s, x,
        )


@pytest.mark.parametrize("codec", ["dynamic", "int8", "fp8"])
def test_blockwise_quant_compiles(one_chip, codec):
    rows, block = 4096, 256
    if codec == "dynamic":
        x = jax.ShapeDtypeStruct((rows * block,), jnp.float32,
                                 sharding=one_chip)
        _compile(lambda x: quantize_pallas(x, interpret=False), x)
        return
    xb = jax.ShapeDtypeStruct((rows, block), jnp.bfloat16, sharding=one_chip)

    def roundtrip(xb):
        codes, scales = stash_quantize_pallas(xb, storage=codec,
                                              interpret=False)
        return stash_dequantize_pallas(codes, scales, dtype=jnp.bfloat16,
                                       interpret=False)

    _compile(roundtrip, xb)

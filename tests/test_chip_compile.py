"""Compile every Pallas kernel for a TPU v5e, without a chip.

Interpret-mode tests cannot see what Mosaic refuses (block shapes that
do not tile (8, 128), VMEM overruns). These cases lower and compile each
kernel for one chip of a described ``v5e:2x2`` topology at the widths a
served model uses: phi3-mini-3.8b (K=32 MHA heads, D=96, which is not a
multiple of the 128 lanes) and, for the paged kernels, llama3.2-1b
(K=8, G=4, D=64). Serving geometry: batch 4, page size 16, 64 pages per
slot (max_len 1024).

The topology is described inside module fixtures, never at import: only
one process may load the TPU library, so a file-level call would make
multi-worker collection disagree.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (decode_attention,
                                            fused_paged_decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul

B, PAGE, PAGES = 4, 16, 64
MAX_LEN = PAGE * PAGES
PHI3 = (32, 1, 96)          # (K, G, D)
LLAMA = (8, 4, 64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _args(kernel, widths, sds):
    K, G, D = widths
    bf16 = jnp.bfloat16
    if kernel == "decode_attention":
        kv = sds((B, K, MAX_LEN, D), bf16)
        return decode_attention, (sds((B, K, G, D), bf16), kv, kv,
                                  sds((), jnp.int32))
    if kernel == "flash_attention":
        S = 512
        kv = sds((1, K, S, D), bf16)
        return flash_attention, (sds((1, K * G, S, D), bf16), kv, kv)
    if kernel == "int8_matmul":
        # the dequant GEMM at phi3-mini's MLP up-projection, one 128-row
        # block of tokens (qeinsum feeds it float32 activations)
        return int8_matmul, (sds((128, 3072), jnp.float32),
                             sds((3072, 8192), jnp.int8),
                             sds((8192,), jnp.float32))
    pool = sds((B * PAGES + 1, PAGE, K, D), bf16)
    table = sds((B, PAGES), jnp.int32)
    per_slot = sds((B,), jnp.int32)
    q = sds((B, K, G, D), bf16)
    if kernel == "paged_decode_attention":
        return paged_decode_attention, (q, pool, pool, table, per_slot)
    new = sds((B, K, D), bf16)
    return fused_paged_decode_attention, (q, new, new, pool, pool, table,
                                          per_slot)


@pytest.mark.parametrize("kernel,widths", [
    ("decode_attention", PHI3),
    ("flash_attention", PHI3),
    ("int8_matmul", PHI3),
    ("paged_decode_attention", PHI3),
    ("fused_paged_decode_attention", PHI3),
    ("paged_decode_attention", LLAMA),
    ("fused_paged_decode_attention", LLAMA),
], ids=lambda p: p if isinstance(p, str) else "K{}G{}D{}".format(*p))
def test_kernel_compiles_for_v5e(kernel, widths, one_chip, no_compile_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _args(kernel, widths, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{kernel} compiled without its Pallas kernel"


def test_long_causal_prefill_attention_fits_for_v5e(one_chip, no_compile_cache):
    """Causal prefill attention at Yi-9B's widths (4 KV heads of 128, 8
    query heads each) for 8 rows of a 2,048-token bucket: chosen by shape,
    the chunked path streams its scores, where the dense form's float32
    (8, 4, 8, 2048, 2048) scores alone take 4 GiB of the chip's 16."""
    from repro.models.layers import attention_core
    B, S, K, G, D = 8, 2048, 4, 8, 128

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    compiled = jax.jit(lambda q, k, v: attention_core(
        q, k, v, causal=True)).lower(
        sds(B, S, K, G, D), sds(B, S, K, D), sds(B, S, K, D)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2**28, temp

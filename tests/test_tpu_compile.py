"""Ahead-of-time compiles of the serve path's Pallas kernel for a TPU
v5e that is described, not attached.

The TPU compiler is installed next to the CPU backend, so these tests
refuse what interpret mode accepts (unaligned slices, VMEM overuse) at
no chip time.  The paged-attention kernel is compiled at TinyLlama-1.1B
decode widths: 8 slots, 32 query heads over 4 KV heads of 64, 16-token
pages, for fp32, int8 and int4 pages and a 4-token verify window.

The topology is described inside a fixture of this file only: loading
the TPU library at import time, or from a second file, would make the
xdist workers collect different tests.
"""
import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention_pallas

B, H, KV, D, PAGE = 8, 32, 4, 64, 16
POOL, PAGES_PER_SLOT = 129, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler otherwise writes its logs under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _args(one_chip, cache_dtype, window_k):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q = sds((B, window_k, H, D) if window_k else (B, H, D), jnp.float32)
    tok = PAGE // 2 if cache_dtype == "int4" else PAGE
    pool_dtype = jnp.float32 if cache_dtype == "fp32" else jnp.int8
    pages = sds((POOL, tok, KV, D), pool_dtype)
    tables = sds((B, PAGES_PER_SLOT), jnp.int32)
    lengths = sds((B,), jnp.int32)
    scales = {}
    if cache_dtype != "fp32":
        scale = sds((POOL, KV, PAGE), jnp.float32)
        scales = {"k_scale": scale, "v_scale": scale}
    return (q, pages, pages, tables, lengths), scales


@pytest.mark.parametrize("cache_dtype,window_k", [
    ("fp32", 0), ("int8", 0), ("int4", 0), ("int4", 4)])
def test_paged_attention_compiles_for_v5e(one_chip, cache_dtype, window_k):
    args, scales = _args(one_chip, cache_dtype, window_k)
    kernel = functools.partial(paged_attention_pallas, interpret=False)
    compiled = jax.jit(kernel).lower(*args, **scales).compile()
    assert "tpu_custom_call" in compiled.as_text()

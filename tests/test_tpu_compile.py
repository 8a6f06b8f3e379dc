"""Ahead-of-time compiles of the serve path's Pallas kernel for a TPU
v5e that is described, not attached.

The TPU compiler is installed next to the CPU backend, so these tests
refuse what interpret mode accepts (unaligned slices, VMEM overuse) at
no chip time.  The paged-attention kernel is compiled at TinyLlama-1.1B
decode widths: 8 slots, 32 query heads over 4 KV heads of 64, 16-token
pages, for fp32, int8 and int4 pages and a 4-token verify window; and at
the chip benchmark's widths (32 slots of 256 pages over an 8,193-page
pool): DeepSeek-R1-Distill-Qwen-1.5B with fp32 pages and Granite-3.0-8B
with int4 pages, whose streamed blocks have to fit the scoped VMEM.

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


def _args(one_chip, cache_dtype, window_k, b=B, h=H, kv=KV, d=D, pool=POOL,
          pages_per_slot=PAGES_PER_SLOT):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q = sds((b, window_k, h, d) if window_k else (b, h, d), jnp.float32)
    tok = PAGE // 2 if cache_dtype == "int4" else PAGE
    pool_dtype = jnp.float32 if cache_dtype == "fp32" else jnp.int8
    pages = sds((pool, tok, kv, d), pool_dtype)
    tables = sds((b, pages_per_slot), jnp.int32)
    lengths = sds((b,), jnp.int32)
    scales = {}
    if cache_dtype != "fp32":
        scale = sds((pool, kv, PAGE), jnp.float32)
        scales = {"k_scale": scale, "v_scale": scale}
    return (q, pages, pages, tables, lengths), scales


@pytest.mark.parametrize("cache_dtype,window_k", [
    ("fp32", 0), ("int8", 0), ("int4", 0), ("int4", 4)])
def test_paged_attention_compiles_for_v5e(one_chip, cache_dtype, window_k):
    args, scales = _args(one_chip, cache_dtype, window_k)
    kernel = functools.partial(paged_attention_pallas, interpret=False)
    compiled = jax.jit(kernel).lower(*args, **scales).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cache_dtype,h,kv", [("fp32", 12, 2),    # R1 1.5B
                                              ("int4", 32, 8)])   # Granite
def test_paged_attention_compiles_at_benchmark_widths(one_chip, cache_dtype,
                                                      h, kv):
    args, scales = _args(one_chip, cache_dtype, 0, b=32, h=h, kv=kv, d=128,
                         pool=32 * 256 + 1, pages_per_slot=256)
    kernel = functools.partial(paged_attention_pallas, interpret=False)
    compiled = jax.jit(kernel).lower(*args, **scales).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_routed_experts_compile_at_lfm2_widths(one_chip):
    """The dropless routed-expert layer at LFM2-8B-A1B's decode widths
    (32 rows, top-4 of 32 experts, 16 held, d 2048, experts 1792 wide,
    bf16): its grouped matmuls lower to the TPU's ``ragged-dot`` ops,
    the names the benchmark's ``moe_roofline.lfm2`` reads."""
    import dataclasses
    from repro.configs import ARCHS
    from repro.models import layers as L
    arch = ARCHS["lfm2-8b-a1b"]
    spec = arch.with_(moe=dataclasses.replace(arch.moe, held=(0, 16)))
    d, f, E = spec.d_model, spec.moe.expert_ff, spec.moe.num_experts

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    p = {"router_w": sds((d, E)), "expert_bias": sds((E,)),
         "experts_wi": sds((16, d, 2 * f)), "experts_wo": sds((16, f, d))}
    compiled = jax.jit(functools.partial(L.routed_experts, spec)).lower(
        p, sds((32, 1, d))).compile()
    assert "ragged-dot" in compiled.as_text()

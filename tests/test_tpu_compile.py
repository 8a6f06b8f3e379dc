"""Ahead-of-time compiles of the serve path's Pallas kernel for a TPU
v5e that is described, not attached.

The TPU compiler is installed next to the CPU backend, so these tests
refuse what interpret mode accepts (unaligned slices, VMEM overuse) at
no chip time.  The paged-attention kernel is compiled at TinyLlama-1.1B
decode widths: 8 slots, 32 query heads over 4 KV heads of 64, 16-token
pages, for fp32, int8 and int4 pages and a 4-token verify window; and at
the chip benchmark's widths (32 slots of 256 pages over an 8,193-page
pool): DeepSeek-R1-Distill-Qwen-1.5B with fp32 pages and Granite-3.0-8B
with int4 pages, whose streamed blocks have to fit the scoped VMEM.  At
LFM2-8B-A1B's widths (heads of 64) one attention layer's decode, the
token scatter and the kernel over lane-dense pools, has to compile with
no op that copies, transposes or pads a whole pool; and the kernel
compiles per device of a TinyLlama pool split over four chips.

The topology is described inside a fixture of this file only: loading
the TPU library at import time, or from a second file, would make the
xdist workers collect different tests.
"""
import functools
import math
import os
import re

import numpy as np
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


#: Ops that move a whole array when applied to one.
MOVES = {"copy", "copy-start", "transpose", "pad"}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_OP = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                 r"([\w\-]+)\(")


def _whole_pool_moves(hlo: str, pool_elems: int) -> list:
    """Names of the ops of optimised ``hlo`` that copy, transpose or pad
    (themselves or inside the fusion they are) an array of at least
    ``pool_elems`` elements."""
    fused, ops, comp = {}, [], None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        op = _OP.match(line)
        if op:
            name, dims, opcode = op.groups()
            fused.setdefault(comp, set()).add(opcode)
            elems = math.prod(int(x) for x in dims.split(",") if x)
            called = re.search(r"calls=%([\w.\-]+)", line)
            ops.append((name, elems, opcode,
                        called.group(1) if called else None))
    return [name for name, elems, opcode, called in ops
            if elems >= pool_elems and (opcode in MOVES or (
                opcode == "fusion" and fused.get(called, set()) & MOVES))]


def test_lfm2_attention_decode_moves_no_whole_pool(one_chip, monkeypatch):
    """One LFM2-8B-A1B attention layer's decode at the benchmark's
    widths (32 slots of 256 pages over 8,193-page fp32 pools, 32/8
    heads of 64): the new token's K/V scatter into the pools in place
    and the paged kernel reads them as they lie.  Pools stored
    (P, 16, 8, 64) instead are converted and zero-padded whole on every
    call."""
    from repro.configs import ARCHS
    from repro.kernels import ops
    from repro.models import lm
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    spec = ARCHS["lfm2-8b-a1b"]
    B, pps = 32, 256
    layout = lm.PagedLayout(num_pages=B * pps + 1, page_size=16,
                            pages_per_slot=pps)
    cache = jax.eval_shape(functools.partial(
        lm.init_paged_cache, spec, B, 4096, layout, jnp.float32))
    kv = next(e for g in cache["groups"] for e in g if "k_pages" in e)
    d, H, KV, D = (spec.d_model, spec.num_heads, spec.num_kv_heads,
                   spec.head_dim)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    p = {"wq": sds((d, H * D), jnp.bfloat16),
         "wk": sds((d, KV * D), jnp.bfloat16),
         "wv": sds((d, KV * D), jnp.bfloat16),
         "wo": sds((H * D, d), jnp.bfloat16),
         "q_norm": sds((D,)), "k_norm": sds((D,))}
    kv = {name: sds(a.shape, a.dtype) for name, a in kv.items()}

    def layer(p, x, pos, kv, tables):
        return lm._attn_decode_paged(spec, p, x, pos, kv, tables,
                                     kind="attn")
    compiled = jax.jit(layer, donate_argnums=(3,)).lower(
        p, sds((B, 1, d), jnp.bfloat16), sds((B,), jnp.int32), kv,
        sds((B, pps), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _whole_pool_moves(hlo, math.prod(kv["k_pages"].shape)) == []


def test_sharded_kernel_compiles_for_tinyllama_tp4(topo, monkeypatch):
    """TinyLlama-1.1B's pools (4 kv heads of 64) split over the four
    chips of a v5e 2x2, one kv head each: every device's share of a page
    is still whole lane tiles, and the kernel compiles per device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.kernels import ops, page_layout
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    shape = page_layout.pool_shape(POOL, PAGE, KV, D, jnp.float32)
    axis = page_layout.kv_axis(shape, D)
    assert shape[-1] == 128 and shape[axis] == 4

    def sds(shp, spec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=NamedSharding(mesh, spec))
    pool = sds(shape, P(*["model" if i == axis else None
                          for i in range(4)]))
    f = functools.partial(ops.paged_attention_sharded, mesh,
                          gather_output=False)
    compiled = jax.jit(f).lower(
        sds((B, H, D), P(None, "model", None)), pool, pool,
        sds((B, PAGES_PER_SLOT), P(None, None), jnp.int32),
        sds((B,), P(None), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()

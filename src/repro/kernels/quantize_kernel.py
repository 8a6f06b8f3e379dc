"""Pallas TPU kernel: fused per-row symmetric quantization.

One pass over x: absmax reduction, scale computation, round+clip to int8 —
the activation-quantization hot path for W8A8 serving (paper: per-tensor /
per-row activation quant).  Avoids materializing the fp copy XLA would
otherwise round-trip through HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _quantize_kernel(x_ref, q_ref, s_ref, *, qmax: int):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def quantize_rowwise_pallas(x: jnp.ndarray, *, bits: int = 8, bm: int = 128,
                            interpret: bool = False):
    """x (M, K) -> (q int8 (M, K), scale f32 (M, 1)). Row-major single pass."""
    M, K = x.shape
    assert M % bm == 0, (M, bm)
    qmax = (1 << (bits - 1)) - 1
    kernel = functools.partial(_quantize_kernel, qmax=qmax)
    return pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, K), jnp.int8),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=f"quantize_rowwise_int{bits}",
    )(x)

"""Seeded weights that come out bit for bit the same wherever they are
made.

Every matrix entry is one of 65,536 evenly spaced values, an integer
times a power of two, so the float32 value is exact and its cast to the
served dtype rounds the same way in every program.  The program's
parameters (``adapters/``) and the reference (``reference/``) each
regenerate the weights from the seed through ``canonical`` and so never
share an array.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# Residual-branch outputs start smaller, as in the usual 0.02/sqrt(2L)
# initialisation: a right shift of 3 is a factor of 8.
_SHIFT = {"o": 3, "down": 3}


def base_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative seed, including seeds wider
    than 32 bits (numpy's seed sequence folds them in)."""
    words = np.random.default_rng(seed).integers(0, 2**32, size=2,
                                                 dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words),
                                    impl="threefry2x32")


def _leaf_key(key, name: str, layer):
    tag = zlib.crc32(name.encode()) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.fold_in(key, tag), layer)


def canonical(key, name: str, layer, shape, dtype=jnp.bfloat16,
              shift: int = 0):
    """Weight ``name`` of ``layer`` (0 for model-wide weights).

    Matrices are uniform on [-2**15, 2**15) * 2**-20 (std 0.018, a
    further 2**-3 for ``o`` and ``down``), and a further 2**-``shift``
    where a configuration asks for it (its ``weight_shift``).  Names
    ending in ``norm`` are RMSNorm offsets ``s`` of a gain ``1 + s``,
    uniform on [-128, 128) * 2**-10."""
    bits = jax.random.bits(_leaf_key(key, name, layer), shape, jnp.uint32)
    ints = (bits >> 16).astype(jnp.int32) - 32768
    if name.endswith("norm"):
        x = (ints >> 8).astype(jnp.float32) * 2.0**-10
    else:
        x = ints.astype(jnp.float32) * 2.0**-(20 + _SHIFT.get(name, 0)
                                              + shift)
    return x.astype(dtype)

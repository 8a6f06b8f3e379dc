"""How a paged KV pool stores its pages: the one place that decides it.

A pool holds one array per layer for K and one for V.  Its present
layout is (P, page, KV, D): page P's token t, kv head h at
``[P, t, h, :]``.  Where head_dim is under the 128 lanes of a TPU
vector register, that layout's minor dim fills half a lane tile, and
XLA stores such an array with another axis minor rather than waste half
its bytes; the paged kernel, which copies a page whole and needs its
minor dim to fill the lanes, then gets every pool converted and
zero-padded on every call.

So a pool whose head_dim divides 128 is stored LANE-DENSE where it can
be, head-major: (P, KV, page·D/128, 128), row i of head h of page P
holding tokens ``g·i .. g·i + g - 1`` (g = 128 / D) side by side, token
``g·i + s`` in lanes ``[s·D, (s+1)·D)``.  Its bytes are those of the
present layout, but its default tiled layout has no padding, so XLA
keeps it as it is.  Each kv head's page is a whole number of
(sublane, 128) tiles of the pool's dtype, so the pool still splits over
its kv heads (tensor parallelism) and every device's share stays
lane-dense.  The rule looks at the shapes and the dtype alone
(``lane_dense_rows``); quantized pools, whose int8 values pair with
per-(token, head) scale pages, and every shape it refuses keep the
present layout.

A pool's shape says which layout it has: a lane-dense pool's minor dim
is 128, which is never the head_dim it stores.  Code that reads or
writes pools goes through ``pool_dims`` / ``kv_axis`` for the geometry,
``pages_from_pool`` / ``pages_to_pool`` for whole pages and
``write_tokens`` for token rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: Lanes of a TPU vector register: the minor dim a page copy fills.
LANES = 128


def lane_dense_rows(page: int, head_dim: int, dtype) -> int:
    """Rows of ``LANES`` that one kv head's page fills when its pool is
    stored lane-dense, or 0 where the pool keeps the (P, page, KV, D)
    layout: head_dim already fills whole lane tiles, does not divide
    them, the dtype is not a float, or a head's page·D values do not
    fill whole (sublane tile, 128) tiles of the dtype."""
    dtype = jnp.dtype(dtype)
    if (head_dim % LANES == 0 or LANES % head_dim
            or not jnp.issubdtype(dtype, jnp.floating)):
        return 0
    rows, sublanes = page * head_dim // LANES, 32 // dtype.itemsize
    return rows if rows and rows % sublanes == 0 else 0


def pool_shape(num_pages: int, page: int, kv: int, head_dim: int,
               dtype) -> tuple:
    """The shape ``init_paged_cache`` gives a float K or V pool."""
    rows = lane_dense_rows(page, head_dim, dtype)
    if rows:
        return (num_pages, kv, rows, LANES)
    return (num_pages, page, kv, head_dim)


def is_lane_dense(shape, head_dim: int) -> bool:
    """Whether a pool of ``shape`` storing heads of ``head_dim`` is
    lane-dense (its minor dim is then 128, never head_dim)."""
    return shape[-1] != head_dim


def pool_dims(shape, head_dim: int) -> tuple:
    """(token dim, kv heads) of one page of a pool of ``shape``: the
    token dim is the page size, or half of it for nibble-packed int4."""
    if is_lane_dense(shape, head_dim):
        return shape[-2] * LANES // head_dim, shape[-3]
    return shape[-3], shape[-2]


def kv_axis(shape, head_dim: int) -> int:
    """The axis of a pool of ``shape`` that indexes kv heads."""
    return 1 if is_lane_dense(shape, head_dim) else 2


def pages_from_pool(x: jnp.ndarray, head_dim: int) -> jnp.ndarray:
    """Whole pages ``x`` (..., *one pool page) as (..., page, KV, D):
    gathered pages of either layout, turned row-major only for the
    pages at hand."""
    if not is_lane_dense(x.shape, head_dim):
        return x
    page, kv = pool_dims(x.shape, head_dim)
    x = x.reshape(*x.shape[:-2], page, head_dim)       # (..., KV, page, D)
    return jnp.swapaxes(x, -3, -2)


def pages_to_pool(x: jnp.ndarray, pool: jnp.ndarray) -> jnp.ndarray:
    """Whole pages ``x`` (..., page, KV, D) in ``pool``'s layout and
    dtype, to be set at page indices of ``pool``."""
    head_dim = x.shape[-1]
    x = x.astype(pool.dtype)
    if not is_lane_dense(pool.shape, head_dim):
        return x
    x = jnp.swapaxes(x, -3, -2)                         # (..., KV, page, D)
    return x.reshape(*x.shape[:-2], pool.shape[-2], LANES)


def write_tokens(pool: jnp.ndarray, rows: jnp.ndarray, tgt_page: jnp.ndarray,
                 tgt_off: jnp.ndarray) -> jnp.ndarray:
    """Scatter token ``rows`` (N, KV, D) into ``pool`` at (``tgt_page``,
    ``tgt_off``) (N,), in place.  In a lane-dense pool each token's
    (KV, D) values land as one D-lane window of its row of every kv
    head, so the rest of the row, another token's, is left as it was."""
    head_dim = rows.shape[-1]
    rows = rows.astype(pool.dtype)
    if not is_lane_dense(pool.shape, head_dim):
        return pool.at[tgt_page, tgt_off].set(rows)
    per_row = LANES // head_dim
    idx = jnp.stack([tgt_page, tgt_off // per_row,
                     tgt_off % per_row * head_dim], axis=-1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=(0, 2),
        scatter_dims_to_operand_dims=(0, 2, 3))
    return jax.lax.scatter(pool, idx.astype(jnp.int32), rows, dnums,
                           mode=jax.lax.GatherScatterMode.FILL_OR_DROP)

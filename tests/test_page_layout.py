"""Lane-dense K/V pools (``kernels/page_layout.py``): where head_dim is
under 128 lanes, a float pool stores each kv head's page as whole rows
of 128 lanes, and the paged kernel reads it in place.

The kernel body runs in interpret mode on lane-dense pools and is held
to ``kernels/ref.py``'s gather oracle over the same pages stored
(P, page, KV, D).  Block-table entries outside a slot's live range
point at a page of NaN, so a kernel that copies one fails.
"""
import importlib
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import page_layout as pl
from repro.kernels import paged_attention as pa
from repro.kernels import ref
from repro.models import lm

ROOT = Path(__file__).resolve().parents[1]

D, PAGE, ENTRIES = 64, 16, 12
MODES = {"full": dict(), "skip": dict(window=40),
         "ring": dict(window=48, ring=True)}
#: (query heads, kv heads): LFM2-8B-A1B's and TinyLlama-1.1B's.
HEADS = {"lfm2": (32, 8), "tinyllama": (32, 4)}


def _lane_dense(x):
    """Row-major pages (P, page, KV, D) as a lane-dense pool."""
    P, page, kv, d = x.shape
    pool = jnp.zeros(pl.pool_shape(P, page, kv, d, x.dtype), x.dtype)
    assert pool.shape[-1] == pl.LANES
    return pl.pages_to_pool(x, pool)


def _case(mode, wq, heads, seed=0):
    """q, row-major pools with the last page NaN, a table and lengths:
    0, 1, mid-page, a page, past a block, a full table (and a wrapped
    ring)."""
    H, KV = HEADS[heads]
    lengths = [0, 1, 23, PAGE, 9 * PAGE + 5, ENTRIES * PAGE]
    if mode == "ring":
        lengths.append(ENTRIES * PAGE + 37)
    B = len(lengths)
    rng = np.random.default_rng(seed)
    n_pages = B * ENTRIES + 2                  # + null page, + poison page
    k = rng.normal(size=(n_pages, PAGE, KV, D)).astype(np.float32)
    v = rng.normal(size=(n_pages, PAGE, KV, D)).astype(np.float32)
    k[-1] = v[-1] = np.nan
    table = rng.permutation(np.arange(1, n_pages - 1)).reshape(B, ENTRIES)
    q = rng.normal(size=(B, wq, H, D) if wq else (B, H, D))
    return (jnp.asarray(q, jnp.float32), jnp.asarray(k), jnp.asarray(v),
            table, np.asarray(lengths))


def _live(length, mode, wq):
    """[lo, hi): the entries a slot streams."""
    if length == 0:
        return 0, 0
    n = pa.live_entries([length], PAGE, ENTRIES, wq=wq, **MODES[mode])[0]
    last = (length - 1) // PAGE
    hi = ENTRIES if mode == "ring" else last + 1
    return (0, n) if mode == "ring" else (hi - n, hi)


@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("wq", [0, 4])
def test_kernel_on_lane_dense_pools_matches_gather_oracle(heads, mode, wq):
    q, k, v, table, lengths = _case(mode, wq, heads)
    poison = k.shape[0] - 1
    dead = table.copy()
    for b, length in enumerate(lengths):
        lo, hi = _live(length, mode, wq)
        dead[b, :lo] = dead[b, hi:] = poison
    want = ref.paged_attention_ref(q, k, v, jnp.asarray(table, jnp.int32),
                                   jnp.asarray(lengths, jnp.int32),
                                   **MODES[mode])
    got = pa.paged_attention_pallas(
        q, _lane_dense(k), _lane_dense(v), jnp.asarray(dead, jnp.int32),
        jnp.asarray(lengths, jnp.int32), interpret=True, **MODES[mode])
    assert got.shape == want.shape
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
    assert float(jnp.max(jnp.abs(got[0]))) == 0.0    # length 0 -> zeros


def test_gather_oracle_reads_either_layout():
    q, k, v, table, lengths = _case("full", 4, "lfm2", seed=1)
    args = (jnp.asarray(table, jnp.int32), jnp.asarray(lengths, jnp.int32))
    want = ref.paged_attention_ref(q, k, v, *args)
    got = ref.paged_attention_ref(q, _lane_dense(k), _lane_dense(v), *args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("page,d,dtype,rows", [
    (16, 64, jnp.float32, 8),      # LFM2, TinyLlama: one (8, 128) tile
    (32, 32, jnp.float32, 8),
    (16, 128, jnp.float32, 0),     # head_dim fills the lanes already
    (8, 64, jnp.float32, 0),       # half a tile per head
    (16, 96, jnp.float32, 0),      # does not divide 128
    (16, 64, jnp.bfloat16, 0),     # bf16 tiles are 16 rows
    (32, 64, jnp.bfloat16, 16),
    (64, 64, jnp.int8, 0),         # quantized pools keep their layout
])
def test_storage_rule(page, d, dtype, rows):
    assert pl.lane_dense_rows(page, d, dtype) == rows
    shape = pl.pool_shape(9, page, 4, d, dtype)
    assert shape == ((9, 4, rows, 128) if rows else (9, page, 4, d))
    assert pl.pool_dims(shape, d) == (page, 4)
    assert pl.kv_axis(shape, d) == (1 if rows else 2)


def test_pad_path_only_for_refused_shapes():
    """A shape the rule refuses (8-token pages at head_dim 64) still runs,
    zero-padded to 128 lanes, and agrees with the oracle; a lane-dense
    pool is never padded."""
    rng = np.random.default_rng(3)
    B, H, KV, page, n = 3, 8, 2, 8, 6
    k = jnp.asarray(rng.normal(size=(B * n + 1, page, KV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B * n + 1, page, KV, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    table = jnp.arange(1, B * n + 1, dtype=jnp.int32).reshape(B, n)
    lengths = jnp.asarray([5, 21, 48], jnp.int32)
    assert pl.lane_dense_rows(page, D, jnp.float32) == 0

    def prims(pages):
        jaxpr = jax.make_jaxpr(lambda *a: pa.paged_attention_pallas(
            *a, interpret=True))(q, pages, pages, table, lengths)
        return str(jaxpr)

    assert " pad[" in prims(k)
    got = pa.paged_attention_pallas(q, k, v, table, lengths, interpret=True)
    want = ref.paged_attention_ref(q, k, v, table, lengths)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
    big = jnp.zeros(pl.pool_shape(B * n + 1, 16, KV, D, jnp.float32))
    assert " pad[" not in prims(big)


def test_token_writes_and_page_moves_round_trip():
    """``write_tokens`` into a lane-dense pool stores what a row-major
    scatter stores, leaving the row's other token as it was, and
    ``pages_to_pool`` / ``pages_from_pool`` invert each other."""
    rng = np.random.default_rng(4)
    pages = jnp.asarray(rng.normal(size=(7, PAGE, 8, D)), jnp.float32)
    pool = _lane_dense(pages)
    np.testing.assert_array_equal(
        np.asarray(pl.pages_from_pool(pool, D)), np.asarray(pages))
    rows = jnp.asarray(rng.normal(size=(5, 8, D)), jnp.float32)
    tgt_page = jnp.asarray([1, 1, 3, 6, 2])
    tgt_off = jnp.asarray([0, 1, 15, 6, 9])
    want = pages.at[tgt_page, tgt_off].set(rows)
    got = pl.pages_from_pool(pl.write_tokens(pool, rows, tgt_page, tgt_off),
                             D)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name,want", [("lfm2-8b-a1b-ep2", 6),
                                       ("r1-distill-qwen-1.5b", 0),
                                       ("granite-3.0-8b-pp2-int4kv", 0)])
def test_benchmark_configurations_lane_dense_layers(name, want):
    """The chip benchmark's configurations at their published widths and
    pools (shapes only): LFM2-8B-A1B's six attention layers (heads of 64,
    fp32 pages) are stored lane-dense; R1 (heads of 128) and Granite
    (int4 pages) keep the (P, page, KV, D) layout."""
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / f"{name}.json").read_text())
    adapter = importlib.import_module(f"chipbench.adapters.{cfg['reference']}")
    spec = adapter.program_spec(cfg)
    prog = cfg["program"]
    pps = prog["max_seq"] // prog["page_size"]
    layout = lm.PagedLayout(num_pages=prog["slots"] * pps + 1,
                            page_size=prog["page_size"], pages_per_slot=pps)
    cache = jax.eval_shape(lambda: lm.init_paged_cache(
        spec, prog["slots"], prog["max_seq"], layout, prog["cache_dtype"]))
    assert lm.lane_dense_layers(cache, spec.head_dim) == want

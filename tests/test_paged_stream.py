"""The paged decode kernel streams each slot's live block-table entries
and nothing else, and the engine counts what it streams.

Entries outside a slot's live range point at a page of NaN (NaN scales
for int8/int4 pools): the kernel must still match the gather reference
run on a clean table, which it cannot if it copies such a page even
once (a dead page's values would enter ``e @ v`` as 0 x NaN).  Poisoning
the first or the last live entry instead must turn the slot's output to
NaN, so the streamed range is exactly ``live_entries`` long.  The
kernel body runs in interpret mode; ``kernels/ref.py`` is the oracle.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import paged_attention as pa
from repro.quant.quantize import (lane_major_scales, pack_int4,
                                  quantize_kv_int4, quantize_kv_int8)

H, KV, D, PAGE, ENTRIES = 4, 2, 128, 16, 40
MODES = {"full": dict(), "skip": dict(window=40),
         "ring": dict(window=48, ring=True)}
TOL = {"fp32": 1e-5, "int8": 1e-5, "int4": 1e-4}


def _span(mode, wq):
    """Entries a slot's live range may hold in ``mode``."""
    if mode != "skip":
        return ENTRIES
    return min(ENTRIES, -(-(MODES["skip"]["window"] + max(wq, 1) - 1)
                          // PAGE) + 1)


def _live(length, mode, wq):
    """[lo, hi): the entries the kernel has to stream for one slot."""
    if length == 0:
        return 0, 0
    last = (length - 1) // PAGE
    if mode == "ring":
        return 0, min(ENTRIES, last + 1)
    return max(last - _span(mode, wq) + 1, 0), last + 1


def _lengths(mode, wq, quant):
    """0, 1, a page -1/0/+1, a block -1/0/+1 and a full table, with the
    block the kernel picks for these shapes (and a wrapped ring)."""
    dtype = jnp.float32 if quant == "fp32" else jnp.int8
    tok = PAGE // 2 if quant == "int4" else PAGE
    blk = PAGE * pa.pages_per_block(PAGE, _span(mode, wq), (tok, KV, D),
                                    dtype, quant != "fp32")
    out = [0, 1, PAGE - 1, PAGE, PAGE + 1, blk - 1, blk, blk + 1,
           ENTRIES * PAGE]
    return out + [ENTRIES * PAGE + 100] if mode == "ring" else out


def _pools(rng, n_pages, quant):
    """(k, v, scales) with the last page poisoned."""
    kf = rng.normal(size=(n_pages, PAGE, KV, D)).astype(np.float32)
    vf = rng.normal(size=(n_pages, PAGE, KV, D)).astype(np.float32)
    if quant == "fp32":
        kf[-1] = vf[-1] = np.nan
        return jnp.asarray(kf), jnp.asarray(vf), {}
    kf, vf = jnp.asarray(kf), jnp.asarray(vf)
    if quant == "int8":
        (k, ks), (v, vs) = quantize_kv_int8(kf), quantize_kv_int8(vf)
    else:
        (k, ks), (v, vs) = quantize_kv_int4(kf), quantize_kv_int4(vf)
        k, v = pack_int4(k, axis=1), pack_int4(v, axis=1)
    ks, vs = lane_major_scales(ks), lane_major_scales(vs)
    ks, vs = ks.at[-1].set(jnp.nan), vs.at[-1].set(jnp.nan)
    return k, v, {"k_scale": ks, "v_scale": vs}


def _case(mode, wq, quant, seed=0):
    lengths = _lengths(mode, wq, quant)
    B = len(lengths)
    rng = np.random.default_rng(seed)
    n_pages = B * ENTRIES + 2                  # + null page, + poison page
    k, v, scales = _pools(rng, n_pages, quant)
    table = rng.permutation(np.arange(1, n_pages - 1)).reshape(B, ENTRIES)
    shape = (B, wq, H, D) if wq else (B, H, D)
    q = jnp.asarray(rng.normal(size=shape), jnp.float32)
    return q, k, v, scales, table, lengths


def _run(q, k, v, scales, table, lengths, mode, impl):
    f = (ref.paged_attention_ref if impl == "ref" else
         lambda *a, **kw: pa.paged_attention_pallas(*a, **kw,
                                                    interpret=True))
    return f(q, k, v, jnp.asarray(table, jnp.int32),
             jnp.asarray(lengths, jnp.int32), **MODES[mode], **scales)


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("wq", [0, 4])
@pytest.mark.parametrize("quant", ["fp32", "int8", "int4"])
def test_kernel_never_reads_past_the_live_range(quant, wq, mode):
    q, k, v, scales, table, lengths = _case(mode, wq, quant)
    poison = k.shape[0] - 1
    dead = table.copy()
    for b, length in enumerate(lengths):
        lo, hi = _live(length, mode, wq)
        dead[b, :lo] = dead[b, hi:] = poison
    want = _run(q, k, v, scales, table, lengths, mode, "ref")
    got = _run(q, k, v, scales, dead, lengths, mode, "pallas")
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) <= TOL[quant]
    assert float(jnp.max(jnp.abs(got[0]))) == 0.0    # length 0 -> zeros


@pytest.mark.parametrize("mode", list(MODES))
def test_live_entries_is_the_streamed_range(mode):
    q, k, v, scales, table, lengths = _case(mode, 0, "fp32", seed=1)
    poison = k.shape[0] - 1
    counts = pa.live_entries(lengths, PAGE, ENTRIES, wq=0, **MODES[mode])
    ranges = [_live(n, mode, 0) for n in lengths]
    assert list(counts) == [hi - lo for lo, hi in ranges]
    for edge in (0, -1):                       # first, then last live entry
        hit = table.copy()
        for b, (lo, hi) in enumerate(ranges):
            if hi > lo:
                hit[b, range(lo, hi)[edge]] = poison
        got = _run(q, k, v, scales, hit, lengths, mode, "pallas")
        nan = np.asarray(jnp.any(jnp.isnan(got), axis=(1, 2)))
        assert list(nan) == [c > 0 for c in counts]


@pytest.mark.parametrize("spec_k", [1, 3])
def test_engine_counts_the_pages_the_kernel_streams(spec_k):
    """The engine's host-side count equals ``live_entries`` of the
    lengths the kernel gets on the device (``pos + K``), call by call."""
    import jax
    from repro.configs import ASSIGNED
    from repro.models import lm
    from repro.serve.backend import SingleDeviceBackend
    from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                       SchedulerConfig)
    spec = ASSIGNED["granite-3-8b"].scaled_down(layers=2, width=64,
                                                vocab=128)
    params = lm.init(jax.random.PRNGKey(0), spec)
    sched = SchedulerConfig(max_slots=3, page_size=8, max_seq=96,
                            num_pages=40, spec_k=spec_k)
    backend = SingleDeviceBackend(params, spec, sched)
    want = {"kv_pages_streamed": 0, "kv_pages_table": 0}
    decode = backend.decode

    def counted(tokens, active, lens=None):
        K = tokens.shape[1]
        n_entries = backend.cache["block_tables"].shape[1]
        lengths = np.asarray(backend.cache["pos"]) + K
        want["kv_pages_streamed"] += int(pa.live_entries(
            lengths, 8, n_entries, wq=K if K > 1 else 0).sum())
        want["kv_pages_table"] += lengths.size * n_entries
        return decode(tokens, active, lens)
    backend.decode = counted
    eng = ContinuousBatchingEngine(params, spec, sched, backend=backend)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, 128, size=5 + 9 * i).astype(np.int32),
                    12 + 5 * i) for i in range(5)]
    eng.run(reqs)
    assert want["kv_pages_table"] > want["kv_pages_streamed"] > 0
    assert {k: eng.stats[k] for k in want} == want

"""Multi-device paged-serving backend tests.

The tensor-parallel ``ShardedPagedBackend`` partitions the KV page
pools (and lane-major int8/int4 scale pages) over the KV-head dim of
the ``model`` mesh axis AND the weights column/row-parallel over the
same axis, keeps block tables replicated host state, and runs the
paged attention per shard under ``shard_map``.  Sharded weights change
matmul reduction order (per-shard partials + one psum), so the parity
contract vs ``SingleDeviceBackend`` is a TOLERANCE BAND on the greedy
stream (``tolerance.assert_close_tokens`` — matching-prefix fraction),
not bitwise identity; only the odd-KV replicate fallback, which keeps
weights replicated too, still promises exact tokens.

jax locks the device count at first init, so these run in subprocesses
over 8 forced host devices (``forced_devices.run_on_devices``).
"""
import pytest

from forced_devices import run_on_devices

PRELUDE = """
import numpy as np, jax, jax.numpy as jnp
from tolerance import assert_close_tokens
from repro.configs import ASSIGNED
from repro.models import lm
from repro.serve.backend import (ShardedPagedBackend, SingleDeviceBackend,
                                 make_backend)
from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                   SchedulerConfig)

spec = ASSIGNED['granite-3-8b'].scaled_down(layers=2, width=64, vocab=128)
params = lm.init(jax.random.PRNGKey(0), spec)

def shared_prefix_reqs(seed=0, n=5, vocab=128):
    # two templates + suffixes: exercises full-page sharing, mid-page
    # CoW, and (under a tight pool) preemption + recompute requeue
    rng = np.random.default_rng(seed)
    t1 = rng.integers(0, vocab, size=20).astype(np.int32)
    t2 = rng.integers(0, vocab, size=25).astype(np.int32)
    reqs = []
    for i in range(n):
        t = (t1, t2)[i % 2]
        suf = rng.integers(0, vocab,
                           size=int(rng.integers(4, 11))).astype(np.int32)
        reqs.append(Request(i, np.concatenate([t, suf]),
                            int(rng.integers(3, 7))))
    return reqs

def run_engine(tp, cache_dtype, num_pages=24, page_size=16, slots=3,
               max_seq=96, reqs=None, spec=spec, params=params):
    cfg = SchedulerConfig(max_slots=slots, page_size=page_size,
                          max_seq=max_seq, num_pages=num_pages,
                          cache_dtype=cache_dtype,
                          enable_prefix_cache=True)
    backend = make_backend(params, spec, cfg, devices=tp)
    eng = ContinuousBatchingEngine(params, spec, cfg, backend=backend)
    rs = reqs if reqs is not None else shared_prefix_reqs()
    done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                    for r in rs])
    eng.alloc.check()
    return done, eng
"""


@pytest.mark.parametrize("cache_dtype", ["fp32", "int8", "int4"])
def test_sharded_backend_token_parity(cache_dtype):
    """tp=2 and tp=4 sharded engines stay within the tolerance band of
    the single-device outputs on a shared-prefix workload (full-page
    sharing + mid-page CoW + suffix prefill), for every cache dtype;
    pools AND weights really shard, per-device weight bytes drop below
    0.6x the replicated baseline, and every page reference unwinds."""
    out = run_on_devices(PRELUDE + f"""
base, base_eng = run_engine(1, {cache_dtype!r})
assert base_eng.stats['prefix_hit_tokens'] > 0
rep_bytes = base_eng.backend.param_bytes_per_device()
for tp in (2, 4):
    done, eng = run_engine(tp, {cache_dtype!r})
    assert eng.backend.pools_sharded, 'pools failed to shard'
    assert eng.backend.weights_sharded, 'weights failed to shard'
    assert eng.backend.tp == tp
    # the pool entry really is partitioned over the model axis
    entry = eng.backend.cache['groups'][0][0]
    kspec = entry['k_pages'].sharding.spec
    assert kspec[2] == 'model', f'KV dim not sharded: {{kspec}}'
    bspec = eng.backend.cache['block_tables'].sharding.spec
    assert all(s is None for s in bspec), f'block tables sharded: {{bspec}}'
    # a projection weight really is split (column-parallel wq)
    wq = eng.backend.params['groups'][0]['wq']
    assert 'model' in tuple(wq.sharding.spec), wq.sharding.spec
    # per-device weight traffic <= 0.6x replicated (norms/biases stay
    # replicated, so the ratio lands near 1/tp but above it)
    dev_bytes = eng.backend.param_bytes_per_device()
    assert dev_bytes <= 0.6 * rep_bytes, (dev_bytes, rep_bytes)
    for a, b in zip(base, done):
        assert_close_tokens(a.tokens, b.tokens, context=f'tp={{tp}} {{a.uid}}')
print('OK')
""")
    assert "OK" in out


def test_sharded_backend_lane_dense_pools():
    """Heads of 64 (4 kv heads, 16-token pages) store their fp32 pools
    lane-dense (``kernels/page_layout.py``): tp=2 and tp=4 shard them on
    their kv-head axis, one kv head a device at tp=4, and stay within the
    tolerance band of the single-device outputs."""
    out = run_on_devices(PRELUDE + """
from repro.kernels import page_layout
wide = ASSIGNED['granite-3-8b'].scaled_down(layers=2, width=256, vocab=128)
wide_params = lm.init(jax.random.PRNGKey(1), wide)
base, base_eng = run_engine(1, 'fp32', spec=wide, params=wide_params)
assert base_eng.stats['kv_lane_dense_layers'] == wide.num_layers
for tp in (2, 4):
    done, eng = run_engine(tp, 'fp32', spec=wide, params=wide_params)
    assert eng.backend.pools_sharded, 'pools failed to shard'
    entry = eng.backend.cache['groups'][0][0]
    shape = entry['k_pages'].shape
    assert page_layout.is_lane_dense(shape, wide.head_dim), shape
    assert entry['k_pages'].sharding.spec[1] == 'model', shape
    assert eng.stats['kv_lane_dense_layers'] == wide.num_layers
    for a, b in zip(base, done):
        assert_close_tokens(a.tokens, b.tokens, context=f'tp={tp} {a.uid}')
print('OK')
""")
    assert "OK" in out


def test_sharded_backend_preemption_parity_int4():
    """A pool too small for all admitted contexts forces preemption on
    both backends; the weight-sharded int4 engine stays within the
    tolerance band of the single-device engine and unwinds every
    reference (the recompute-requeue path crosses admit/release/CoW on
    sharded pools).  Preemption COUNTS stay exactly equal: the
    allocator's page arithmetic depends on lengths, not token values."""
    out = run_on_devices(PRELUDE + """
rng = np.random.default_rng(2)
T = rng.integers(0, 128, size=16).astype(np.int32)
reqs = [Request(i, np.concatenate(
    [T, rng.integers(0, 128, size=6).astype(np.int32)]), 12)
    for i in range(4)]
base, e1 = run_engine(1, 'int4', num_pages=11, page_size=8, slots=4,
                      max_seq=48, reqs=reqs)
done, e2 = run_engine(2, 'int4', num_pages=11, page_size=8, slots=4,
                      max_seq=48, reqs=reqs)
assert e1.stats['preemptions'] >= 1 and e2.stats['preemptions'] >= 1
assert e1.stats['preemptions'] == e2.stats['preemptions']
for a, b in zip(base, done):
    assert_close_tokens(a.tokens, b.tokens, context=f'uid={a.uid}')
e2.prefix_cache.flush(); e2.alloc.check()
assert e2.alloc.free_pages == e2.layout.num_pages - 1
print('OK')
""")
    assert "OK" in out


def test_odd_kv_heads_fall_back_to_replication():
    """A KV-head count the model axis does not divide must WARN and
    replicate the pools AND the weights (no crash, no shard_map) — the
    fallback keeps the exact bitwise token contract, so this stays
    ``np.array_equal``, not the tolerance band.  The warning fires once
    per (name, shape): a second engine over the same spec adds none."""
    out = run_on_devices(PRELUDE + """
import warnings
spec1 = spec.with_(num_kv_heads=1)          # MQA: kv=1, tp=2 cannot divide
params1 = lm.init(jax.random.PRNGKey(0), spec1)
rng = np.random.default_rng(1)
reqs = [Request(i, rng.integers(0, 128,
        size=int(rng.integers(12, 30))).astype(np.int32), 5)
        for i in range(4)]
base, _ = run_engine(1, 'int8', reqs=reqs, spec=spec1, params=params1)
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter('always')
    done, eng = run_engine(2, 'int8', reqs=reqs, spec=spec1, params=params1)
msgs = [str(x.message) for x in w]
assert any('divisible' in m and 'replicating' in m for m in msgs), msgs
assert not eng.backend.pools_sharded and eng.backend.mesh is None
assert not eng.backend.weights_sharded
for a, b in zip(base, done):
    assert np.array_equal(a.tokens, b.tokens)
# per-(name, shape) dedup: the same degradation re-created in a new
# engine (fresh ShardingRules instance) must NOT warn again
with warnings.catch_warnings(record=True) as w2:
    warnings.simplefilter('always')
    run_engine(2, 'int8', reqs=reqs[:1], spec=spec1, params=params1)
again = [str(x.message) for x in w2 if 'replicating' in str(x.message)]
assert not again, again
print('OK')
""")
    assert "OK" in out


def test_sharded_spec_decode_token_parity():
    """Self-speculative decoding over the weight-sharded backend: the
    tp=2 engine with spec_k=4 verify windows (multi-query paged
    attention per shard under shard_map) stays within the tolerance
    band of the single-device NON-speculative greedy output, for every
    cache dtype — speculation and sharding compose, and acceptance is
    still self-consistent (every emitted token is the verify program's
    own argmax), and its draft acceptance stays within the band of the
    drafter replayed over the single-device greedy streams."""
    out = run_on_devices(PRELUDE + """
from tolerance import assert_acceptance_in_band
# decode budgets long enough that greedy streams reach their
# repetitive tails — otherwise the n-gram table never proposes and
# nothing would actually be speculated
rng = np.random.default_rng(0)
T = rng.integers(0, 128, size=16).astype(np.int32)
reqs = [Request(i, np.concatenate(
    [T, rng.integers(0, 128, size=5 + i).astype(np.int32)]), 14)
    for i in range(4)]

def run_spec(tp, cache_dtype, spec_k):
    cfg = SchedulerConfig(max_slots=3, page_size=16, max_seq=96,
                          num_pages=24, cache_dtype=cache_dtype,
                          enable_prefix_cache=True, spec_k=spec_k)
    backend = make_backend(params, spec, cfg, devices=tp)
    eng = ContinuousBatchingEngine(params, spec, cfg, backend=backend)
    done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                    for r in reqs])
    eng.alloc.check()
    return done, eng

for cache_dtype in ('fp32', 'int8', 'int4'):
    base, _ = run_spec(1, cache_dtype, 1)
    done, eng = run_spec(2, cache_dtype, 4)
    assert eng.backend.pools_sharded
    assert eng.stats['spec_steps'] > 0 and eng.stats['spec_accepted'] > 0, \
        (cache_dtype, eng.stats)
    for a, b in zip(base, done):
        assert_close_tokens(a.tokens, b.tokens,
                            context=f'{cache_dtype} uid={a.uid}')
    assert_acceptance_in_band(eng.stats, reqs, base, 4, context=cache_dtype)
print('OK')
""")
    assert "OK" in out


def test_per_device_budget_scales_pool():
    """make_layout(tp=N): the same per-device byte budget addresses ~N x
    more pages (each device stores only its KV-head slice of a page),
    and plan_for_layout(tp=) prices the per-device share."""
    out = run_on_devices("""
from repro.configs import ASSIGNED
from repro.serve.paged_cache import make_layout, plan_for_layout
spec = ASSIGNED['granite-3-8b'].scaled_down(layers=2, width=64, vocab=128)
budget = 2e6
l1 = make_layout(spec, max_seq=256, page_size=16, kv_budget_bytes=budget)
l4 = make_layout(spec, max_seq=256, page_size=16, kv_budget_bytes=budget,
                 tp=4)
# band, not exact: num_pages floors budget/page_bytes independently
assert 4 * l1.num_pages <= l4.num_pages < 4 * (l1.num_pages + 1), \
    (l1.num_pages, l4.num_pages)
p1 = plan_for_layout(spec, l1, 'int4')
p4 = plan_for_layout(spec, l4, 'int4', tp=4)
assert abs(p4.page_bytes * 4 - p1.page_bytes) < 1e-6
print('OK')
""")
    assert "OK" in out


@pytest.mark.parametrize("cache_dtype", ["fp32", "int8", "int4"])
def test_sharded_backend_swap_roundtrip_and_parity(cache_dtype):
    """Host-tier swap on the tp=2 sharded pool: (a) a swap_out blob
    scattered back into DIFFERENT pages gathers byte-identically (the
    per-shard gather reassembles the GLOBAL page host-side, so the
    blob is layout-independent), and (b) an engine under pool pressure
    that swaps instead of preempting stays within the tolerance band
    of the single-device no-swap output, with the host pool drained."""
    out = run_on_devices(PRELUDE + f"""
cfg = SchedulerConfig(max_slots=3, page_size=16, max_seq=96, num_pages=24,
                      cache_dtype={cache_dtype!r})
backend = make_backend(params, spec, cfg, devices=2)
eng = ContinuousBatchingEngine(params, spec, cfg, backend=backend)
# write real KV into some pages via a normal admission
rng = np.random.default_rng(0)
eng.submit(Request(0, rng.integers(0, 128, size=40).astype(np.int32), 4))
eng.step()
pages = list(eng.slots[0].pages)
assert len(pages) >= 2
blob = eng.backend.swap_out(pages)
spare = [p for p in range(1, 24) if p not in pages][:len(pages)]
eng.backend.swap_in(blob, spare)
back = eng.backend.swap_out(spare)
for a, b in zip(jax.tree_util.tree_leaves(blob),
                jax.tree_util.tree_leaves(back)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

# engine-level parity: tight pool forces the swap tier under tp=2
rng = np.random.default_rng(1)
reqs = [Request(i, rng.integers(1, 128,
                size=int(rng.integers(12, 28))).astype(np.int32), 16)
        for i in range(5)]

def go(tp, host_bytes):
    cfg = SchedulerConfig(max_slots=3, page_size=8, max_seq=64, num_pages=12,
                          cache_dtype={cache_dtype!r},
                          host_pool_bytes=host_bytes, debug_invariants=True)
    backend = make_backend(params, spec, cfg, devices=tp)
    eng = ContinuousBatchingEngine(params, spec, cfg, backend=backend)
    done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                    for r in reqs])
    eng.alloc.check()
    return sorted(done, key=lambda c: c.uid), eng

base, _ = go(1, None)
done, eng2 = go(2, 50e6)
assert eng2.backend.pools_sharded
assert eng2.stats['swap_outs'] > 0, eng2.stats
assert len(eng2.host_pool) == 0
for a, b in zip(base, done):
    assert_close_tokens(a.tokens, b.tokens,
                        context=f'{cache_dtype} uid={{a.uid}}')
print('OK')
""")
    assert "OK" in out

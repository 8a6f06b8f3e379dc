"""Serving package: static ``engine.generate`` + the continuous-batching
``ContinuousBatchingEngine`` over a refcounted paged KV cache.

The stack is a HOST/DEVICE split: the scheduler (admission, prefix
store, lazy growth, the evict→swap→preempt escalation) is pure host
state and drives a ``backend.PagedKVBackend`` for every device
interaction.  Two backends
ship — ``SingleDeviceBackend`` (one device holds the whole pool) and
``ShardedPagedBackend`` (tensor-parallel: pools partitioned over the
KV-head dim of the ``model`` mesh axis, block tables replicated,
Pallas paged attention invoked per shard via ``shard_map``; weights
sharded column-parallel for wq/wk/wv/w_gate/w_up and row-parallel for
wo/w_down over the same axis, so per-shard attention consumes
per-shard QKV natively and each sublayer reduces with ONE psum).

Above the backends sits the DATA-PARALLEL axis: ``router.PrefixRouter``
fronts N fully independent scheduler+backend replicas
(``router.make_replicas`` slices ``jax.devices()`` into disjoint
tp-sized groups), rendezvous-hashing each prompt's page-aligned
template prefix to a replica so a template's prefix pages stay hot on
ONE pool, with occupancy-aware overflow spill and steal-from-deepest
rebalance on replica drain.

The router doubles as the fleet's HEALTH CHECKER and the stack is
FAULT-AWARE end to end: ``faults.ChaosBackend`` wraps any backend and
injects seeded deterministic faults (permanent crash-on-step, latency
spikes, NaN-logit corruption) through the real serve surface;
``step()`` evicts a replica after ``fail_after`` consecutive step
exceptions or a missed ``heartbeat_s`` and migrates BOTH its queued
and admitted work to survivors (``export_active`` resume records —
zero requests lost even on a crash mid-decode; ``add()`` rejoins a
recovered replica).  The request lifecycle is typed: ``Request``
carries a ``deadline_s`` (late queued work sheds, never admits) and a
NaN retry budget; every ``Completion`` reports ``ok`` / ``shed`` /
``failed``.  With a ``router.ServeSLO`` policy (distilled from
``core.latency.predict_serve_throughput``) ``submit`` applies
analytical BACKPRESSURE — hashed-target TTFT violation spills,
fleet-wide violation sheds — and ``core.latency.serve_availability`` /
``failover_recovery_cost`` model degraded capacity, load multiplier
and migrate-vs-reprefill recovery cost for the same fleet.

With ``SchedulerConfig.spec_k > 1`` the engine decodes SELF-
SPECULATIVELY: each slot drafts up to ``spec_k - 1`` tokens from its
own context (n-gram prompt lookup, ``serve.spec_decode`` — no second
model), one multi-query paged decode step verifies the whole window
(``models.lm.decode_window_paged`` -> the K-query Pallas kernel), and
greedy acceptance commits the matching prefix plus a bonus token.
Emissions are token-for-token the ``spec_k = 1`` greedy engine —
speculation changes how many tokens an iteration commits, never which.

With ``SchedulerConfig.prefill_chunk_tokens > 0`` admission is
CHUNKED: every iteration spends at most that many (bucket-padded)
prefill tokens, long prompts stream in across iterations co-scheduled
with decode (each chunk is a suffix prefill whose prefix is the chunks
already written — the same program admission with a prefix-cache hit
runs, no new kernel), and a partially-prefilled slot holds its pages
but decodes nothing until its last chunk lands.  Chunking bounds the
per-iteration admission work, which is what caps the p99 inter-token
latency spike a long prompt's one-shot admission inflicts on every
live decoder — the open-loop Poisson benchmark gate
(``serve_throughput.py --open-loop``) measures exactly that trade
(p50/p99 TTFT + ITL, goodput under SLO) against
``core.latency.predict_serve_throughput(chunk_tokens=)``'s analytical
decomposition.  Outputs stay token-for-token the unchunked engine's:
like speculation, chunking changes the scheduling of work, never the
per-slot decode math.  Chunked admission composes with every cell
below — prefix hits shrink the suffix the chunks cover, spec windows
start after the final chunk, preempted victims re-chunk on recompute,
and both backends reuse the ``admit_prefix`` jit cache
(``PagedKVBackend.prefill_chunk``).

HOST MEMORY is a first-class serving tier: with
``SchedulerConfig.host_pool_bytes`` set, the scheduler owns a
byte-budgeted ``paged_cache.HostPagePool`` and allocation pressure
escalates evict → SWAP → preempt — a victim's pages (packed pools +
lane-major scale pages, any cache dtype) gather to host DRAM over the
h2d link as a ``ParkedKV`` blob instead of being thrown away, and its
re-admission scatters them back and prefills ONE token, token-identical
to the recompute path it replaces.  The same pool PARKS idle
multi-turn sessions (``Request.session``): a finished turn holds its
slot idle on device, rejoins in place when the next turn extends it,
and parks to host after ``idle_park_iterations`` or under pressure.
Shared prefix pages are refcount-safe — parking COPIES them, never
steals them from other holders.  ``core.latency.swap_vs_recompute``
prices the trade (whole pages round-trip over ``h2d_bw x u_h2d`` vs
re-prefill FLOPs over the roofline — int4 pages move ~1/8 the fp32
bytes, which is what pulls swap under recompute on the paper's
boards), ``HardwareSpec.host_mem_capacity`` bounds the tier, and
``calibration.Observation(kind="h2d")`` fits ``u_h2d`` from measured
transfers.  The ``--swap`` multi-turn benchmark gate holds device pool
bytes EQUAL and requires higher admitted occupancy and lower p99 TTFT
than recompute-only, with token-identical outputs across the swap
(fp32/int8/int4, single-device and tp=2 — the tp pool swaps per-shard
and reassembles host-side).

SLIDING-WINDOW KV is a third memory tier-style axis: on a uniformly
``attn_local`` stack (every KV-holding layer windowed — gemma3 reduced
to its local layers; one block table serves all layers, so a single
global layer disqualifies ring eviction and ``paged_cache.ring_window``
auto-falls back to mask-only) each slot's block table becomes a RING
of ``ring_pages(window, page, spec_k) = ceil((window+spec_k-1)/page)+1``
entries: per-slot KV is O(window) for UNBOUNDED streams, the write
head recycles an exclusive out-of-window page in place (zero allocator
traffic) and releases — never frees — a shared prefix page that falls
out of the window, and the Pallas kernels stream only the ring's
entries (flat windowed tables get the same O(window) traffic: the
kernel streams only a slot's last O(window) live entries).  ``SchedulerConfig.windowed_kv``: ``None``
auto-detects, ``False`` forces the mask-only reference (same windowed
attention math, full-attention memory — the ``--window`` gate
baseline), ``True`` asserts the stack qualifies.  Sessions park/rejoin
and spec-k rollbacks compose (the ring's +1 straddle page is what
keeps a rolled-back verify window inside never-recycled entries), and
``core.analytical.mean_pages_held`` / ``core.latency`` clamp held
pages and attended context at the window, so
``predict_serve_throughput(window=)`` predicts the concurrency jump
the ``--window`` gate measures.

Paged KV precision support matrix (``SchedulerConfig.cache_dtype`` x
parallelism axes x decode mode) — every cell is exercised by tier-1
tests / the CI serve smokes (prefill, decode, prefix-cache, CoW per
cell; sharded cells add preemption + recompute parity in
tests/test_serve_backend_multidevice.py; routed cells in
tests/test_serve_router.py + the ``--dp`` benchmark gate; spec-decode
cells assert token identity with the non-speculative engine in
tests/test_spec_decode.py and the ``--spec-decode`` benchmark gate;
chunked-prefill cells assert token identity plus the per-iteration
budget bound in tests/test_serve_scheduler.py and the ``--open-loop``
benchmark gate; fault-tolerance cells in tests/test_serve_faults.py
and the ``--chaos`` benchmark gate; swap/park cells assert token
identity across swap-out/swap-in per dtype in
tests/test_serve_scheduler.py, tp=2 in
tests/test_serve_backend_multidevice.py, and the ``--swap`` gate;
sliding-window cells assert ring-vs-flat-oracle and kernel parity per
dtype incl. verify windows across the ring wrap in
tests/test_quantized_paged_attention.py, engine token identity vs the
mask-only reference + static windowed generate in
tests/test_serve_scheduler.py, the windowed int4 launcher smoke in
tests/test_launch_serve.py, and the ``--window`` gate):

=========  ====================  =======================  ==============
dtype      single device         tp-sharded (tp=2/4):     dp replicas
           (tp=1, dp=1)          KV pools + weights       (router)
=========  ====================  =======================  ==============
``fp32``   yes (all 4 paths;     yes — within tolerance   yes — within
           spec_k windows        band of tp=1 (psum       band of dp=1
           identical to          order may flip argmax    (replica
           greedy)               near-ties; matching-     choice only
                                 prefix fraction >= 0.9)  changes batch
                                                          composition)
``int8``   yes (all 4 paths)     yes — within band        yes — within
                                                          band
``int4``   yes (nibble-packed    yes — within band        yes — within
           pages; mid-byte       (packed pools + scale    band (CI:
           splits RMW-preserve   pages shard on the       dp=2 x tp=2
           the neighbour token)  KV-head dim; spec_k      int4 smoke)
                                 gate in CI)
``any`` +  yes (ring tables,     ring param is static     composes (the
sliding    token-identical to    on both backends'        ring is
window     the mask-only         jits; kernel parity      per-slot host
(ring KV)  reference; spec-k +   per dtype in tier-1)     state, router
           sessions compose)                              unaffected)
=========  ====================  =======================  ==============

Fault-tolerance matrix (chaos mode x backend x dp — every cell through
the REAL serve surface, ``ChaosBackend`` wrapping the cell's backend):

===============  =====================  ================================
chaos mode       single replica         dp fleet (health-checked router)
===============  =====================  ================================
crash-on-step    ``ReplicaFault`` on    replica evicted after
(permanent)      every later device     ``fail_after`` step faults;
                 call; mid-admission    queued + admitted work migrates
                 crash restores the     (zero lost, tokens identical to
                 queue head             the no-fault dp=1 run; CI
                                        ``--chaos`` gate: goodput
                                        recovers >= 0.5x same-window
                                        dp=1 post failover)
latency spike    outputs unchanged      heartbeat deadline
(sleep)          (byte-identical)       (``heartbeat_s``) evicts a
                                        wedged-not-crashing replica
NaN logits       typed ``failed`` (no   same guard per replica; retry
(ok-flag zero)   garbage committed)     recompute is token-identical
===============  =====================  ================================

Both backends feed the NaN guard the same way: ``decode`` returns
``(out, n_emit, ok)`` with ``ok`` computed on-device from the step's
logits, so silent corruption is caught before any token commits.  The
hypothesis fuzz (tests/test_serve_faults.py) drives crash-at-arbitrary-
iteration over the dp fleet; survivor allocator refcounts balance
after every failover.

Tolerance band = per-request matching-prefix fraction >= 0.9
(``tests/tolerance.assert_close_tokens``): the sharded psum reduces in
a different order than single-device adds, so greedy streams may fork
at an argmax near-tie.  KV-head counts the model axis does not divide
fall back to replicated pools AND replicated weights with a
once-per-(name, shape) warning — that cell keeps the old bitwise
token-for-token contract (nothing reduces across shards).  dp
replicas compose with any tp cell: each replica owns a disjoint
device slice, a private pool and prefix store, and the router never
reaches past ``submit``/``step``/``queue``/``num_active``.

Quantized pages store per-token-per-head f32 scales next to the int8
pools in LANE-MAJOR (P, KV, page) layout — the token dim rides the
lane dim, so one page's scales occupy a single (8, 128) f32 tile on
real TPU instead of tile-padding per token (the PR-3 caveat, closed);
int4 packs two adjacent tokens per byte along the pool token dim (~8x
fewer page bytes than fp32, 62-73% below fp16-equivalent accounting
depending on head_dim).  On TPU all three dtypes dispatch to the same
Pallas decode kernel (``kernels/paged_attention.py``), which
dequantizes int8 / unpacks int4 in VMEM inside the online-softmax loop
— ``benchmarks/kernel_bench.py`` reports the page-byte ratios (0.27x
fp32 for int8, 0.14x for int4 at head_dim 64) plus the physical scale
tile bytes of both layouts; ``benchmarks/serve_throughput.py
--cache-dtype int4 --prefix`` gates output equivalence end to end,
``--devices N`` gates the sharded backend against single-device
outputs (tolerance band + per-device weight bytes <= 0.6x replicated)
while reporting measured vs ``predict_serve_throughput(tp=N)``
per-device page-pool occupancy, and ``--dp R`` gates the routed fleet
(prefix-aware beats random routing on prefix-cache hits, aggregate
decode tokens/s >= 1.6x dp=1) next to the analytical tp x dp cluster
grid (``core.latency.serve_cluster_grid``: tokens/s/device and
cost-per-million-tokens per cell).
"""
from repro.serve.backend import (PagedKVBackend, ShardedPagedBackend,
                                 SingleDeviceBackend, make_backend)
from repro.serve.engine import ServeConfig, generate, load_quantized, make_prefill_step, make_serve_step
from repro.serve.faults import ChaosBackend, ChaosSchedule, ReplicaFault
from repro.serve.paged_cache import (HostPagePool, PageAllocator, ParkedKV,
                                     PrefixCache, PrefixMatch, copy_page,
                                     make_layout, pages_needed,
                                     plan_for_layout)
from repro.serve.router import (PrefixRouter, ServeSLO, make_replicas,
                                pick_replica, route_key)
from repro.serve.scheduler import (Completion, ContinuousBatchingEngine,
                                   Request, SchedulerConfig)
from repro.serve.spec_decode import NGramDraftTable

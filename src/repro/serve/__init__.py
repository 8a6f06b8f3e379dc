"""Serving: the static ``engine.generate`` and the continuous-batching
``ContinuousBatchingEngine`` over a refcounted paged KV cache.

The scheduler (``scheduler.py``) is host state: admission, the prefix
store, lazy page growth and the evict -> swap -> preempt escalation.
It makes every device call through a ``backend.PagedKVBackend``:
``SingleDeviceBackend`` holds the whole pool on one device, and
``ShardedPagedBackend`` shards pools and weights tensor-parallel over
the KV-head dim.  ``router.PrefixRouter`` fronts dp independent
replicas, routes each prompt by its page-aligned template prefix, and
migrates a failed replica's work to survivors; ``faults.ChaosBackend``
injects seeded faults through the same surface.  Speculative decoding,
chunked prefill, the host swap tier, multi-turn sessions and ring pages
for sliding-window stacks are ``SchedulerConfig`` fields.  The tier-1
tests hold each to a reference: token-identical outputs, or within
``tests/tolerance.py``'s band where sharded reductions reorder sums.
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

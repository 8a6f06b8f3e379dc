"""Paged-KV serving backends: the DEVICE half of the host/device split.

The continuous-batching scheduler (``serve/scheduler.py``) is pure host
state — refcounted ``PageAllocator``, hash-indexed ``PrefixCache``,
slot/queue bookkeeping — and drives the device through the
``PagedKVBackend`` interface below: admit (full or suffix prefill),
one batched decode step, copy-on-write page copies, slot release,
block-table writes, and page gather/scatter for the host swap tier
(``swap_out`` / ``swap_in`` — parked slots round-trip their pages
byte-identically through host DRAM instead of re-prefilling).  Everything the device side owns (the page-pool
pytree, the jitted step functions, where the arrays live and how they
are sharded) is a backend concern the scheduler never sees.

Two backends ship:

* ``SingleDeviceBackend`` — the PR-1..3 behaviour: one device holds the
  whole pool; module-level jits (shared compile cache across engine
  instances) run the fused admission / decode steps.

* ``ShardedPagedBackend`` — tensor-parallel paged serving for the
  edge-cluster scenario (several small accelerators behind one
  scheduler).  The KV page pools and their lane-major int8/int4 scale
  pages are partitioned over the ``model`` mesh axis along the KV-HEAD
  dim (``parallel.sharding.ShardingRules.cache_entry_pspec``); block
  tables and per-slot positions stay replicated host state, and the
  paged-attention op runs PER SHARD under ``shard_map``
  (``kernels.ops.paged_attention_sharded`` — the Pallas kernel on TPU).
  The WEIGHTS shard too: wq/wk/wv and mlp_wi column-parallel, wo and
  mlp_wo row-parallel (``ShardingRules.param_pspec``), so per-shard
  attention consumes per-shard QKV natively, the head-sharded
  attention output flows straight into row-parallel wo, and GSPMD
  inserts the megatron block's single psum per sublayer — no
  replicated-weight gathers anywhere on the decode path.  What tp buys
  is per-device KV capacity (each device stores ceil(KV/tp) heads of
  every page, so the same per-device byte budget addresses ~tp x more
  pages — ``make_layout(tp=)``), 1/tp of the decode-loop KV traffic,
  AND 1/tp of the per-device weight traffic + FLOPs
  (``core.latency.mixed_iteration_cost(tp=)``); small-batch decode is
  weight-traffic-bound, so the weight split is the per-device
  bandwidth relief.  The parity contract is a TOLERANCE BAND, not
  bitwise identity: psum reduction order differs from the
  single-device program, so greedy streams may diverge after an
  argmax near-tie (tests/tolerance.py's ``assert_close_tokens`` bands
  the matching prefix).  KV-head counts the axis does not divide fall
  back to FULLY replicated state — pools AND weights — which keeps the
  old exact token-for-token contract (clear warning, no crash): the
  engine still runs, it just gains no capacity.
"""
from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, SingleDeviceSharding

from repro.core.model_config import ModelSpec
from repro.models import lm
from repro.serve import paged_cache as pc
from repro.serve.spans import NO_SPANS


# Module-level jits (spec/impl/mesh static): every engine instance — and
# every benchmark repetition — shares one compile cache instead of
# retracing per-instance closures.  All steps return sampled token ids,
# not logits, so only (B,)-sized arrays ever cross to the host.

@functools.partial(jax.jit, static_argnames=("spec", "impl", "ring"),
                   donate_argnums=(2,))
def _admit_fn(params, batch, cache, slot, true_len, bt_row, *, spec, impl,
              ring=False):
    """Fused cold admission (no cached prefix): prefill the
    (bucket-padded) prompt, scatter its KV into the slot's pages,
    install the block-table row, and sample the first token.  One jit
    call per admission (retraces only per prompt bucket).  Needs no
    mesh: the prefill math runs replicated on every backend, and GSPMD
    partitions the scatter into sharded pools on its own.

    ``ring=True``: ``bt_row`` is a RING of R entries — absolute prompt
    page q scatters into entry ``q % R`` when it lies within the final
    window horizon (the last R pages), and routes to the null page
    otherwise (the sliding window can never read it); padding pages
    past ``true_len`` also go to null so they never collide with a live
    ring entry.

    Conv layers' state at the prompt's true end lands in the slot's row
    of ``cache["conv"]``; routed-expert layers' rows per held expert
    replace ``cache["expert_rows"]``."""
    rows = []
    logits, pre = lm.prefill(params, spec, batch,
                             max_seq=batch["tokens"].shape[1],
                             impl=impl, true_len=true_len, expert_rows=rows)
    page = lm.paged_page_size(cache, spec.head_dim)
    n = batch["tokens"].shape[1] // page          # prompt pages (static)
    if ring:
        R = bt_row.shape[0]
        apg = jnp.arange(n)
        last_pg = (true_len - 1) // page
        keep = (apg > last_pg - R) & (apg <= last_pg)
        pv = jnp.where(keep, bt_row[apg % R], pc.NULL_PAGE)
    else:
        pv = bt_row[:n]
    new_groups = pc.scatter_prompt_pages(cache["groups"], pre["groups"],
                                         pv, page)
    new_cache = {
        **cache,
        "pos": cache["pos"].at[slot].set(true_len),
        "block_tables": cache["block_tables"].at[slot].set(bt_row),
        "groups": new_groups,
    }
    states = [e["conv_state"][0] for g in pre["groups"] for e in g
              if "conv_state" in e]
    if states:
        new_cache["conv"] = [c.at[slot].set(st)
                             for c, st in zip(cache["conv"], states)]
    _put_expert_rows(new_cache, rows)
    return jnp.argmax(logits[0, 0]), new_cache


def _put_expert_rows(cache, rows) -> None:
    """Install one call's rows per held expert (a list, one (H,) array
    per routed layer) as ``cache["expert_rows"]``."""
    if rows:
        cache["expert_rows"] = jnp.stack(rows)


def _keep_idle_state(cache, conv, active) -> None:
    """Keep the conv state of slots that were not decoded: a mid-prefill
    slot carries its state between chunks, and an idle session slot
    rejoins from it."""
    if conv:
        live = active[:, None, None] > 0
        cache["conv"] = [jnp.where(live, new, old)
                         for new, old in zip(cache["conv"], conv)]


@functools.partial(jax.jit,
                   static_argnames=("spec", "n_prefix_pages", "mesh", "ring"),
                   donate_argnums=(2,))
def _admit_prefix_fn(params, batch, cache, slot, prefix_len, true_len,
                     bt_row, *, spec, n_prefix_pages, mesh=None, ring=False):
    """Fused warm admission: prefill only the prompt SUFFIX against the
    slot's cached prefix pages (``lm.prefill_paged``) and sample the
    first token.  Retraces per (suffix bucket, prefix-page bucket).
    ``ring=True`` follows the ring entry mapping for both the prefix
    gather and the suffix scatter (see ``lm.prefill_paged``)."""
    rows = []
    logits, new_cache = lm.prefill_paged(
        params, spec, batch["tokens"], cache, slot, bt_row, prefix_len,
        true_len, n_prefix_pages=n_prefix_pages, ring=ring, mesh=mesh,
        expert_rows=rows)
    _put_expert_rows(new_cache, rows)
    return jnp.argmax(logits[0, 0]), new_cache


@functools.partial(jax.jit,
                   static_argnames=("spec", "mesh", "shard_params", "ring"),
                   donate_argnums=(1,))
def _decode_fn(params, cache, tokens, active, *, spec, mesh=None,
               shard_params=False, ring=False):
    rows, conv = [], cache.get("conv")
    # idle slots are routed nowhere, so the expert counters count only
    # the slots this step decodes
    logits, cache = lm.decode_step(params, spec, cache, tokens, mesh=mesh,
                                   shard_params=shard_params, ring=ring,
                                   valid=active > 0, expert_rows=rows)
    _put_expert_rows(cache, rows)
    _keep_idle_state(cache, conv, active)
    # pin inactive slots at pos 0 so their (clamped) block-table lookups
    # stay on the null page indefinitely
    cache["pos"] = cache["pos"] * active
    # per-slot finite-logits flag: argmax over a NaN/inf row is garbage
    # the host cannot detect from the sampled id alone, so the flag —
    # not the logits — crosses to the host and the scheduler fails the
    # slot instead of committing the token
    finite = jnp.all(jnp.isfinite(logits[:, 0]), axis=-1).astype(jnp.int32)
    return jnp.argmax(logits[:, 0], axis=-1), finite, cache


@functools.partial(jax.jit,
                   static_argnames=("spec", "mesh", "shard_params", "ring"),
                   donate_argnums=(1,))
def _decode_window_fn(params, cache, tokens, active, lens, *, spec,
                      mesh=None, shard_params=False, ring=False):
    """Fused speculative verify step: score a K-token window per slot
    (last committed token + K-1 drafts), greedy-accept drafts ON DEVICE,
    and advance each slot's pos by exactly the emitted count — the
    rollback that keeps rejected-draft KV outside the valid context.
    Returns (out (B, K) greedy tokens per window position, n_emit (B,)
    how many of them are committed: accepted drafts + the bonus token,
    finite (B,) 1 where every REAL window position's logits are finite).
    Acceptance compares the drafted token at window position j+1 with
    the verified argmax at position j, so every emitted token is
    token-for-token what sequential greedy decode would produce.
    """
    pos0 = cache["pos"]
    rows = []
    logits, cache = lm.decode_window_paged(params, spec, cache, tokens,
                                           lens, ring=ring, mesh=mesh,
                                           shard_params=shard_params,
                                           expert_rows=rows)
    _put_expert_rows(cache, rows)
    out = jnp.argmax(logits, axis=-1)                       # (B, K)
    K = tokens.shape[1]
    j = jnp.arange(K - 1)
    ok = (tokens[:, 1:] == out[:, :-1]) & (j[None] < lens[:, None] - 1)
    accepted = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
    n_emit = (accepted + 1) * active
    cache["pos"] = (pos0 + n_emit) * active                 # pin inactive at 0
    # finite check over the real window positions only (padded positions
    # score pad tokens — their logits never commit)
    pos_ok = jnp.all(jnp.isfinite(logits), axis=-1)         # (B, K)
    mask = jnp.arange(K)[None, :] < lens[:, None]
    finite = jnp.all(jnp.where(mask, pos_ok, True),
                     axis=1).astype(jnp.int32)
    return out, n_emit, finite, cache


@jax.jit
def _gather_pages_fn(cache, pv):
    """Device half of swap-OUT: gather the listed pages' rows from every
    pool entry (k/v pages plus the lane-major scale pages of quantized
    dtypes) across all layers.  No donation — the pool keeps its pages
    until the host copy lands and the allocator releases them, so a
    shared prefix page is never pulled out from under another holder.
    Retraces once per power-of-two page-count bucket (the caller pads
    ``pv`` with the null page)."""
    out = []
    for cg in cache["groups"]:
        out.append([{name: entry[name][pv] for name in entry}
                    for entry in cg])
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages_fn(cache, rows, pv):
    """Device half of swap-IN: scatter host page rows back into (fresh)
    physical pages of every pool entry.  The same GSPMD story as the
    admission scatters: with tensor-parallel pools each device writes
    only its KV-head slice of every row, so the transfer is per-shard
    without any backend-specific code.  Padded trailing entries of
    ``pv`` target the null page, whose content is never consumed."""
    new_groups = []
    for cg, rg in zip(cache["groups"], rows):
        new_layers = []
        for entry, src in zip(cg, rg):
            new_entry = dict(entry)
            for name in entry:
                new_entry[name] = entry[name].at[pv].set(src[name])
            new_layers.append(new_entry)
        new_groups.append(new_layers)
    return {**cache, "groups": new_groups}


#: Counters of the routed-expert layers, summed over layers and calls:
#: rows the held experts computed, distinct held experts each layer
#: call hit, and routed-layer calls.
EXPERT_COUNTERS = ("moe_rows", "moe_experts_hit", "moe_layer_calls")


class PagedKVBackend:
    """Interface the scheduler drives; implementations own the device
    cache pytree and the jitted steps.  All token returns are host ints
    / numpy — the scheduler never touches device arrays."""

    spec: ModelSpec
    layout: lm.PagedLayout
    plan: Any                      # analytical PagedCachePlan
    cache: Any                     # device pytree (pools + block tables)
    tp: int = 1                    # tensor-parallel degree (1 = single)
    _spans = NO_SPANS              # the owning engine's span recorder

    def bind_spans(self, recorder) -> None:
        """Record this backend's device calls as spans of ``recorder``
        (the owning engine's ``spans.SpanRecorder``), and a routed-expert
        spec's ``EXPERT_COUNTERS`` in the recorder's ``stats``; until an
        engine binds one, they record nothing.  ``kv_lane_dense_layers``
        in ``stats`` counts the attention layers whose pools this
        backend's cache stores lane-dense (``kernels.page_layout``)."""
        self._spans = recorder
        recorder.stats["kv_lane_dense_layers"] = lm.lane_dense_layers(
            self.cache, self.spec.head_dim)
        if self.spec.moe is not None:
            for name in EXPERT_COUNTERS:
                recorder.stats.setdefault(name, 0)

    def _count_experts(self) -> None:
        """Add the last call's rows per held expert (the cache's
        ``expert_rows``) to the bound engine's counters."""
        stats = getattr(self._spans, "stats", None)
        if stats is None or "expert_rows" not in self.cache:
            return
        rows = np.asarray(self.cache["expert_rows"])
        stats["moe_rows"] += int(rows.sum())
        stats["moe_experts_hit"] += int(np.count_nonzero(rows))
        stats["moe_layer_calls"] += rows.shape[0]

    def admit_full(self, padded_tokens: np.ndarray, slot: int,
                   true_len: int, bt_row: np.ndarray) -> int:
        """Cold prefill of a bucket-padded prompt into ``slot``; returns
        the sampled first token."""
        raise NotImplementedError

    def admit_prefix(self, padded_suffix: np.ndarray, slot: int,
                     prefix_len: int, true_len: int, bt_row: np.ndarray,
                     *, n_prefix_pages: int) -> int:
        """Suffix-only prefill against cached prefix pages."""
        raise NotImplementedError

    def prefill_chunk(self, padded_chunk: np.ndarray, slot: int,
                      prefix_len: int, true_len: int, bt_row: np.ndarray,
                      *, n_prefix_pages: int) -> int:
        """One chunk of a CHUNKED prefill: write the chunk's KV at
        absolute positions [prefix_len, prefix_len + true_len) of
        ``slot``, attending the chunk's queries over the gathered pages
        already written (prefix-cache hits + earlier chunks).  This is
        the same suffix-prefill program as ``admit_prefix`` — chunking
        the budget is a SCHEDULER policy, not a new device path — but
        the returned greedy token is meaningful only for the FINAL
        chunk (where it seeds decoding); intermediate chunks' sampled
        tokens are discarded by the caller."""
        raise NotImplementedError

    def decode(self, tokens: np.ndarray, active: np.ndarray,
               lens: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One batched decode step over a K-token window.

        ``tokens`` is (B, K): each active slot's last committed token
        followed by up to K-1 speculatively drafted tokens; ``lens``
        (B,) counts the real window positions per slot (None means the
        plain non-speculative step: K == 1, one token per slot).
        Returns ``(out, n_emit, ok)``: ``out`` (B, K) the greedy token
        at every verified window position, ``n_emit`` (B,) how many of
        them each slot commits this step (always 1 on the K=1 path,
        accepted drafts + 1 under speculation), and ``ok`` (B,) a
        finite-logits flag per slot — 0 means the slot's logits held
        NaN/inf this step (corrupted weights or KV) and its sampled
        tokens are garbage the scheduler must NOT commit (the NaN
        guard fails the slot instead).  K=1 with ``lens=None`` runs
        the exact pre-speculative program.
        """
        raise NotImplementedError

    def copy_page(self, src_page: int, dst_page: int) -> None:
        """Copy one physical page (all layers, k/v and scales) — the
        copy-on-write step for mid-page prefix reuse."""
        raise NotImplementedError

    def release_slot(self, slot: int) -> None:
        """Reset a finished/preempted slot's block table and position."""
        raise NotImplementedError

    def write_block_entries(self,
                            updates: Sequence[Tuple[int, int, int]]) -> None:
        """Install lazily-grown decode pages: (slot_row, page_idx,
        page_id) triples into the replicated block tables."""
        raise NotImplementedError

    def swap_out(self, page_ids: Sequence[int]) -> Any:
        """Gather the listed pages (all layers, k/v pools + scale pages)
        into a host numpy pytree — the device->host leg of parking a
        slot's KV in the host memory tier.  Pure read: the device pages
        are untouched; the scheduler frees its references afterwards."""
        raise NotImplementedError

    def swap_in(self, blob: Any, page_ids: Sequence[int]) -> None:
        """Scatter a previously gathered blob into ``page_ids`` (freshly
        allocated pages, one per blob row).  Byte-identical round trip
        with ``swap_out``, so a parked slot resumes token-identically;
        block table and pos are restored by the one-token suffix prefill
        that rejoins the slot (the existing admission path)."""
        raise NotImplementedError

    def host_page_bytes(self) -> int:
        """Host bytes one GLOBAL page occupies when parked (all layers,
        k/v pools + scale pages; for tp pools this is the assembled
        cross-shard page, not one device's slice) — what the scheduler
        charges against ``HostPagePool.capacity_bytes`` before paying
        for a gather."""
        raise NotImplementedError


class SingleDeviceBackend(PagedKVBackend):
    """The whole page pool on one device (the PR-1..3 serve path)."""

    #: Mesh handed to the jitted steps; None on a single device.
    mesh = None
    #: True when _place() committed column/row-parallel weight
    #: shardings (the sharded backend with dividable head counts).
    weights_sharded = False

    def __init__(self, params: Any, spec: ModelSpec, cfg, device=None):
        # ``device``: the one device this backend's params and pool are
        # committed to (a dp replica's own chip); None leaves them on
        # the default device
        self.params, self.spec, self.cfg = params, spec, cfg
        self.device = device
        # Uniformly sliding-window stacks get a RING block table bounded
        # at O(window) pages per slot (unless cfg.windowed_kv forces the
        # mask-only reference); everything else keeps the flat layout.
        self.window = pc.ring_window(spec, getattr(cfg, "windowed_kv", None))
        self.ring = self.window > 0
        self.layout = pc.make_layout(
            spec, max_seq=cfg.max_seq, page_size=cfg.page_size,
            num_pages=cfg.num_pages, kv_budget_bytes=cfg.kv_budget_bytes,
            cache_dtype=cfg.cache_dtype, max_slots=cfg.max_slots,
            tp=self.tp, window=self.window,
            spec_k=getattr(cfg, "spec_k", 1))
        self.plan = pc.plan_for_layout(spec, self.layout, cfg.cache_dtype)
        self.cache = self._init_cache()
        self._place()
        self._admit = functools.partial(_admit_fn, spec=spec,
                                        impl=cfg.attention_impl,
                                        ring=self.ring)
        self._admit_pref = functools.partial(_admit_prefix_fn, spec=spec,
                                             mesh=self.mesh, ring=self.ring)
        self._decode = functools.partial(_decode_fn, spec=spec,
                                         mesh=self.mesh,
                                         shard_params=self.weights_sharded,
                                         ring=self.ring)
        self._decode_window = functools.partial(_decode_window_fn, spec=spec,
                                                mesh=self.mesh,
                                                shard_params=self.weights_sharded,
                                                ring=self.ring)

    def _init_cache(self):
        """Build the paged device cache — written straight onto
        ``self.device`` when one was given, so a replica's pool never
        passes through the default device; subclasses override to
        create it already laid out across their devices."""
        build = functools.partial(lm.init_cache, self.spec,
                                  self.cfg.max_slots, self.cfg.max_seq,
                                  self.cfg.cache_dtype, paged=self.layout)
        if self.device is None:
            return build()
        return jax.jit(build, out_shardings=SingleDeviceSharding(
            self.device))()

    def _place(self) -> None:
        """Commit the params to ``self.device`` (subclasses place them
        with their shardings instead)."""
        if self.device is not None:
            self.params = jax.device_put(self.params, self.device)

    def param_bytes_per_device(self) -> int:
        """Bytes of weight state ONE device holds (= per-device weight
        traffic of a decode step, since decode streams every weight
        once).  Uses the committed shardings' ``shard_shape``, so it
        measures what sharding actually achieved: replicated leaves
        (norms, the odd-KV fallback) count in full, column/row-split
        leaves count their slice."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self.params):
            sh = getattr(leaf, "sharding", None)
            if sh is not None:
                shape = sh.shard_shape(leaf.shape)
            else:
                shape = leaf.shape
            total += int(np.prod(shape)) * leaf.dtype.itemsize
        return total

    def admit_full(self, padded_tokens, slot, true_len, bt_row) -> int:
        with self._spans.span("backend.prefill"):
            tok0, self.cache = self._admit(
                self.params, {"tokens": jnp.asarray(padded_tokens)},
                self.cache, jnp.int32(slot), jnp.int32(true_len),
                jnp.asarray(bt_row))
            tok0 = int(tok0)
            self._count_experts()
            return tok0

    def admit_prefix(self, padded_suffix, slot, prefix_len, true_len,
                     bt_row, *, n_prefix_pages) -> int:
        with self._spans.span("backend.prefill"):
            tok0, self.cache = self._admit_pref(
                self.params, {"tokens": jnp.asarray(padded_suffix)},
                self.cache, jnp.int32(slot), jnp.int32(prefix_len),
                jnp.int32(true_len), jnp.asarray(bt_row),
                n_prefix_pages=n_prefix_pages)
            tok0 = int(tok0)
            self._count_experts()
            return tok0

    def prefill_chunk(self, padded_chunk, slot, prefix_len, true_len,
                      bt_row, *, n_prefix_pages) -> int:
        # the chunk program IS the suffix-prefill program (prefix = the
        # rows already written), so both backends — this one and the
        # tensor-parallel subclass — reuse the admit_prefix jit cache
        return self.admit_prefix(padded_chunk, slot, prefix_len, true_len,
                                 bt_row, n_prefix_pages=n_prefix_pages)

    def decode(self, tokens, active, lens=None):
        # dispatch ends when the jitted call returns (inputs moved, the
        # program enqueued); the wait is the device's compute and the
        # results' copy back
        span = self._spans.span
        if tokens.shape[1] == 1 and lens is None:
            # the pre-speculative path, byte-identical program: K=1 must
            # bitwise-reproduce the sequential engine
            with span("backend.decode_dispatch"):
                nxt, ok, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(active))
            with span("backend.decode_wait"):
                out = (np.asarray(nxt)[:, None],
                       np.asarray(active, np.int32), np.asarray(ok))
                self._count_experts()
                return out
        with span("backend.decode_dispatch"):
            out, n_emit, ok, self.cache = self._decode_window(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(active), jnp.asarray(lens))
        with span("backend.decode_wait"):
            out = np.asarray(out), np.asarray(n_emit), np.asarray(ok)
            self._count_experts()
            return out

    def copy_page(self, src_page: int, dst_page: int) -> None:
        with self._spans.span("backend.page_table"):
            self.cache = pc.copy_page(self.cache, src_page, dst_page)

    def release_slot(self, slot: int) -> None:
        with self._spans.span("backend.page_table"):
            self.cache = pc.release_slot(self.cache, slot)

    def write_block_entries(self, updates) -> None:
        with self._spans.span("backend.page_table"):
            rows = jnp.asarray([u[0] for u in updates], jnp.int32)
            cols = jnp.asarray([u[1] for u in updates], jnp.int32)
            vals = jnp.asarray([u[2] for u in updates], jnp.int32)
            bt = self.cache["block_tables"]
            self.cache["block_tables"] = bt.at[rows, cols].set(vals)

    @staticmethod
    def _pad_page_vec(page_ids) -> np.ndarray:
        """Pow2-bucket a page-id vector (null-page padded) so the swap
        jits compile once per bucket, like the admission buckets."""
        n = 1
        while n < len(page_ids):
            n *= 2
        pv = np.full((n,), pc.NULL_PAGE, np.int32)
        pv[:len(page_ids)] = page_ids
        return pv

    def swap_out(self, page_ids) -> Any:
        with self._spans.span("backend.swap"):
            k = len(page_ids)
            pv = self._pad_page_vec(page_ids)
            rows = _gather_pages_fn(self.cache, jnp.asarray(pv))
            # device_get assembles sharded pools from their addressable
            # shards host-side — each device ships only its KV-head
            # slice, so the tp transfer is per-shard with no collective
            host = jax.device_get(rows)
            if len(pv) != k:
                host = jax.tree_util.tree_map(lambda a: a[:k].copy(), host)
            return host

    def swap_in(self, blob, page_ids) -> None:
        with self._spans.span("backend.swap"):
            k = len(page_ids)
            pv = self._pad_page_vec(page_ids)
            if len(pv) != k:
                pad = len(pv) - k
                blob = jax.tree_util.tree_map(
                    lambda a: np.concatenate(
                        [a, np.zeros((pad,) + a.shape[1:], a.dtype)]), blob)
            self.cache = _scatter_pages_fn(self.cache, blob,
                                           jnp.asarray(pv))

    def host_page_bytes(self) -> int:
        return sum(int(leaf.nbytes) // int(leaf.shape[0])
                   for leaf in jax.tree_util.tree_leaves(
                       self.cache["groups"]))


class ShardedPagedBackend(SingleDeviceBackend):
    """Tensor-parallel paged serving: pools sharded over the KV-head dim
    of the ``model`` mesh axis, weights column/row-parallel over the
    same axis, block tables replicated, attention per shard.  See the
    module docstring for the tolerance/capacity contract."""

    def __init__(self, params: Any, spec: ModelSpec, cfg,
                 tp: Optional[int] = None,
                 devices: Optional[List] = None):
        from repro.parallel.sharding import ShardingRules
        if spec.num_conv_layers():
            raise NotImplementedError(
                f"{spec.name}: tensor-parallel serving has no layout for "
                "per-slot conv state; serve it with devices=1")
        devices = devices if devices is not None else jax.devices()
        tp = tp if tp is not None else len(devices)
        if tp < 2:
            raise ValueError(f"ShardedPagedBackend needs tp >= 2, got {tp} "
                             "(use SingleDeviceBackend)")
        if len(devices) < tp:
            raise RuntimeError(
                f"tp={tp} needs {tp} devices, have {len(devices)} — on CPU "
                "run under XLA_FLAGS=--xla_force_host_platform_device_count=N")
        self.tp = tp
        self._mesh = jax.make_mesh((1, tp), ("data", "model"),
                                   devices=devices[:tp],
                                   axis_types=(AxisType.Auto,) * 2)
        self.rules = ShardingRules(self._mesh, spec)
        super().__init__(params, spec, cfg)

    def _init_cache(self):
        """Create the pool pytree SHARDED FROM BIRTH: a tp-scaled global
        pool is ~tp x one device's free KV memory, so materializing it
        unsharded on the default device first (then resharding) would
        OOM the exact deployments tp exists for.  ``jit`` with
        ``out_shardings`` writes each device's KV-head slice in place;
        shapes come from ``eval_shape`` so nothing big ever lives
        unsharded."""
        build = lambda: super(ShardedPagedBackend, self)._init_cache()
        abstract = jax.eval_shape(build)
        self.pools_sharded = self.rules.paged_pools_sharded(abstract)
        csh = self.rules.cache_shardings(abstract)
        return jax.jit(build, out_shardings=csh)()

    def _place(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        if getattr(self, "pools_sharded", False):
            # column/row-parallel weights over the same "model" axis as
            # the pools: per-shard QKV feeds per-shard attention, the
            # head-sharded output reduces through row-parallel wo with
            # one psum, and per-device weight bytes drop ~1/tp — the
            # bandwidth relief small-batch decode is bound by
            self.params = jax.device_put(
                self.params, self.rules.param_shardings(self.params))
            self.weights_sharded = True
        else:
            # odd-KV fallback: pools replicate, so weights replicate
            # too and every matmul executes the exact single-device
            # program — this branch keeps the bitwise parity contract
            rep = NamedSharding(self._mesh, P())
            self.params = jax.device_put(self.params, rep)

    @property
    def mesh(self):
        # shard_map attention only when the pools actually shard — the
        # odd-KV fallback replicates them, and a shard_map over
        # replicated pools would recompute every head on every device
        # AND break GQA head grouping per shard
        if getattr(self, "pools_sharded", False):
            return self._mesh
        return None


def make_backend(params: Any, spec: ModelSpec, cfg, *,
                 devices: int = 1,
                 device_list: Optional[List] = None) -> PagedKVBackend:
    """Backend factory the launcher and the benchmark use: ``devices``
    == 1 is the single-device pool, > 1 the tensor-parallel backend (KV pools
    AND weights sharded) over the first ``devices`` jax devices —
    or over an explicit ``device_list`` (the dp router hands each
    replica its own disjoint slice; a one-device slice commits the
    single-device backend to that device)."""
    if devices <= 1:
        return SingleDeviceBackend(
            params, spec, cfg,
            device=device_list[0] if device_list else None)
    return ShardedPagedBackend(params, spec, cfg, tp=devices,
                               devices=device_list)

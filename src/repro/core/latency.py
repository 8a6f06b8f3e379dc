"""Latency breakdown — paper §III-B, equations (10)-(14).

Faithful implementation: each stage divides a byte/FLOP count by the
corresponding (bandwidth x utilization).  ``breakdown()`` reproduces the
paper's edge-device analysis; ``roofline_terms()`` is the same arithmetic
specialized to the TPU pod target (compute / HBM / ICI), used by
EXPERIMENTS.md §Roofline next to the compiled-HLO numbers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

from repro.core.analytical import (Analysis, PagedCachePlan,
                                   conv_state_bytes, effective_slots,
                                   expected_accepted_tokens,
                                   mean_pages_held, mixed_iteration_flops,
                                   streamed_params, tp_shards_kv,
                                   tp_shards_weights)
from repro.core.hardware import HardwareSpec
from repro.core.model_config import ModelSpec
from repro.core.precision import PrecisionSpec


@dataclass
class LatencyBreakdown:
    compute: float
    memory: float
    storage_io: float
    h2d: float
    network: float
    # fine-grained compute split (paper §III-B "fine-grained breakdown")
    per_op: Dict[str, float]

    @property
    def end_to_end(self) -> float:
        """Paper's end-to-end: serial sum of all stages (cold start)."""
        return self.compute + self.memory + self.storage_io + self.h2d + self.network

    @property
    def steady_state(self) -> float:
        """Warm inference: weights resident, max of overlap-able stages."""
        return max(self.compute, self.memory) + self.network


def breakdown(an: Analysis, hw: HardwareSpec, precision: PrecisionSpec,
              per_op_flops: Dict[str, float] | None = None) -> LatencyBreakdown:
    """Equations (10)-(14) for one analyzed cell on one device."""
    weight_bytes = an.params * precision.bytes_per_param
    flops = an.step_flops
    eff_flops = hw.flops_at(precision.name) * hw.u_compute

    t_comp = flops / eff_flops                                    # eq. 10
    t_mem = an.memory.total / (hw.mem_bw * hw.u_memory)           # eq. 11
    t_io = weight_bytes / (hw.storage_bw * hw.u_storage)          # eq. 12
    t_h2d = weight_bytes / (hw.h2d_bw * hw.u_h2d)                 # eq. 13
    kv_shard = an.shape.seq_len * an.spec.d_model * precision.act_bytes
    t_net = kv_shard / (hw.net_bw * hw.u_net)                     # eq. 14

    per_op = {}
    if per_op_flops:
        for name, f in per_op_flops.items():
            per_op[name] = f / eff_flops
    return LatencyBreakdown(t_comp, t_mem, t_io, t_h2d, t_net, per_op)


def arithmetic_intensity(an: Analysis, precision: PrecisionSpec) -> float:
    """FLOPs per byte of memory traffic (paper: 'well under 1' on edge)."""
    bytes_moved = an.params * precision.bytes_per_param + an.memory.kv_cache
    return an.step_flops / max(1.0, bytes_moved)


@dataclass
class IterationCost:
    """One continuous-batching scheduler iteration (mixed prefill+decode).

    ``compute_s`` and ``memory_s`` overlap on real hardware, so the
    iteration time is their max — decode is memory-bound on edge
    (weights re-read every step), prefill adds a compute term.
    ``decode_tokens`` counts tokens COMMITTED (under speculative decode
    one iteration commits the accepted window, so it can exceed the
    live-slot count); ``flops``/``bytes_moved`` carry the raw CLUSTER
    totals the times were derived from, for the eq.-(15) energy model.
    ``collective_s`` is the per-iteration all-reduce time of the
    weight-sharded tensor-parallel path (zero on one device): it
    overlaps neither compute nor the weight stream on edge
    interconnects, so the iteration rooflines over all three terms.
    """
    compute_s: float
    memory_s: float
    decode_tokens: float           # useful tokens emitted this iteration
    flops: float = 0.0
    bytes_moved: float = 0.0
    collective_s: float = 0.0

    @property
    def iteration_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def tokens_per_s(self) -> float:
        return self.decode_tokens / self.iteration_s if self.iteration_s else 0.0


def mixed_iteration_cost(spec: ModelSpec, hw: HardwareSpec,
                         precision: PrecisionSpec, plan: PagedCachePlan, *,
                         prefill_tokens: int, decode_slots: int,
                         avg_context: float, cached_prefix_tokens: int = 0,
                         params: float | None = None,
                         tp: int = 1, spec_k: int = 1,
                         acceptance_rate: float = 0.0,
                         chunk_tokens: int | None = None,
                         window: int = 0) -> IterationCost:
    """Analytical cost of one scheduler iteration — predicts continuous
    batching throughput from the same roofline terms as ``breakdown()``.

    Memory term: weights stream once per iteration (shared by every slot
    in the batch — the whole point of iteration-level batching; of a
    routed layer's held experts only those the iteration's rows are
    expected to hit, ``analytical.streamed_params``) plus the per-slot
    conv state read and written, plus the
    paged KV actually touched: ``avg_context`` tokens per live decode
    slot and the prefill tokens written once.  ``plan.bytes_per_token``
    carries the cache dtype (``plan_for_layout(..., cache_dtype=)``):
    int8 pages move ~1/4 and nibble-packed int4 ~1/8 the fp32 bytes
    (plus per-token-per-head f32 scales — ``analytical.
    KV_CACHE_DTYPES``), which is exactly the in-kernel-dequant traffic
    the Pallas paged kernel streams.  ``cached_prefix_tokens`` are
    prefix-cache hits: their projections/MLP are skipped entirely
    (see ``mixed_iteration_flops``) and their KV is READ from shared
    pages instead of recomputed and written — the per-token page bytes
    move once either way, so only the FLOP term drops.

    ``tp`` models the tensor-parallel sharded backend (``plan`` holding
    the GLOBAL per-page bytes): the page pools are partitioned over the
    KV-head dim, so each device moves 1/tp of the KV bytes per
    iteration, and the WEIGHTS shard column/row-parallel over the same
    axis (``analytical.tp_shards_weights``) — per-device weight traffic
    AND FLOPs divide by tp, which is the per-device bandwidth relief
    small-batch decode is bound by.  The price is a COLLECTIVE term:
    the megatron block all-reduces a (tokens, d_model) f32 activation
    twice per layer (after attention-wo and after mlp_wo; a ring moves
    2(tp-1)/tp of the payload per device), charged against the board's
    network link — on 1 GbE edge clusters this term caps tp scaling
    well below linear, exactly the behaviour the interconnect
    deserves.  A ``tp`` that does not divide the head counts (or the
    MLP hidden dim) falls back to replication in the corresponding
    layer of the stack, so the matching term here divides by nothing
    either.  ``flops``/``bytes_moved`` on the result stay CLUSTER
    totals (the fleet does the same work, just spread out), so the
    energy model prices all tp devices, not one shard.

    ``spec_k`` > 1 models self-speculative decoding: every live slot
    verifies a ``spec_k``-token window per iteration, so the FLOP term
    charges ``spec_k`` positions per slot (rejected drafts still
    compute), while the MEMORY term barely moves — the weights stream
    once per iteration regardless and the multi-query paged kernel
    reads each context page once for all window queries (the extra
    window rows written are noise next to the context read).  What
    changes is the USEFUL-token count: one window commits
    ``expected_accepted_tokens(acceptance_rate, spec_k)`` tokens, so
    on the memory-bound decode roofline tokens/s scales almost
    linearly with the acceptance rate — the whole speculative bet.

    ``chunk_tokens`` mirrors the scheduler's CHUNKED-PREFILL budget
    (``SchedulerConfig.prefill_chunk_tokens``): per iteration the
    engine admits at most ``chunk_tokens`` of prefill work, carrying
    the remainder of a long prompt across iterations, so the analytical
    iteration clamps ``prefill_tokens`` to the same cap.  That bounds
    the compute term a co-scheduled decode iteration can absorb — the
    inter-token-latency spike of an unchunked long-prompt admission —
    at the price of more admission iterations per request (TTFT), the
    exact trade ``predict_serve_throughput`` decomposes.

    ``window`` > 0 models the ring-paged sliding-window cache: each
    decode slot STREAMS at most ``window`` context tokens of KV per
    step (the kernel skips fully-out-of-window pages and the ring
    never holds more) and its attention spans the same bound, so both
    the per-slot KV byte term and the decode FLOP context clamp at the
    window — decode page traffic goes O(context) → O(window), which on
    the memory-bound decode roofline is the whole win.
    """
    from repro.core import blocks
    if chunk_tokens is not None:
        if chunk_tokens <= 0:
            raise ValueError("chunk_tokens must be positive when given")
        prefill_tokens = min(prefill_tokens, chunk_tokens)
    if window > 0:
        avg_context = min(avg_context, float(window))
    if tp > 1 and getattr(plan, "tp", 1) > 1:
        raise ValueError(
            f"plan already holds per-device bytes (built with tp="
            f"{plan.tp}); pass the global plan or drop the tp= argument "
            "— dividing twice would overstate throughput")
    P = params if params is not None else streamed_params(
        spec, prefill_tokens + decode_slots * spec_k)
    flops = mixed_iteration_flops(spec, prefill_tokens,
                                  decode_slots * spec_k,
                                  avg_context, cached_prefix_tokens)
    kv_global = plan.bytes_per_token * (
        decode_slots * (avg_context + spec_k - 1)
        + prefill_tokens + cached_prefix_tokens)
    # conv layers read and write each decoding slot's state once
    kv_global += 2 * conv_state_bytes(spec, decode_slots)
    kv_dev = kv_global / (tp if tp_shards_kv(spec, tp) else 1)
    weight_bytes = P * precision.bytes_per_param
    w_div = tp if tp_shards_weights(spec, tp) else 1
    emitted = decode_slots * expected_accepted_tokens(acceptance_rate, spec_k)
    # weight-only quantized GEMV unpacks/rescales per use: charge the
    # dequant overhead as extra compute work (time AND flop energy)
    eff_flops = flops * precision.dequant_overhead
    t_comp = (eff_flops / w_div) / (hw.flops_at(precision.name)
                                    * hw.u_compute)
    t_mem = (weight_bytes / w_div + kv_dev) / (hw.mem_bw * hw.u_memory)
    t_coll = 0.0
    if w_div > 1:
        # 2 psums/layer over the live (tokens, d_model) f32 activations
        tokens = prefill_tokens + decode_slots * spec_k
        coll_bytes = (2 * spec.num_layers * tokens * spec.d_model * 4.0
                      * 2 * (tp - 1) / tp)
        t_coll = coll_bytes / (hw.net_bw * hw.u_net)
    return IterationCost(t_comp, t_mem, emitted,
                         flops=eff_flops,
                         bytes_moved=weight_bytes + kv_global,
                         collective_s=t_coll)


def predict_serve_throughput(spec: ModelSpec, hw: HardwareSpec,
                             precision: PrecisionSpec, plan: PagedCachePlan,
                             *, slots: int, avg_prompt: float,
                             avg_new: float, prefix_hit_rate: float = 0.0,
                             admission: str = "lazy",
                             tp: int = 1, dp: int = 1, spec_k: int = 1,
                             acceptance_rate: float = 0.0,
                             chunk_tokens: int | None = None,
                             parked_context_tokens: float | None = None,
                             window: int = 0) -> Dict[str, float]:
    """Steady-state continuous batching vs static-batch throughput.

    Static batching pads every slot to the batch max and holds slots
    until the LAST request finishes; continuous batching refills slots
    immediately, so its steady state keeps its live slots at the mean
    context.  ``prefix_hit_rate`` is the fraction of prompt tokens
    served from the prefix store (``analytical.prefix_hit_rate``) —
    those skip prefill FLOPs; ``admission`` ("lazy" | "conservative")
    sets how many slots the page pool actually sustains
    (``analytical.effective_slots``) — lazy allocation holds only the
    pages written so far, so the same pool carries more concurrent
    requests.  Returns tokens/sec for both plus the ratio.

    ``spec_k``/``acceptance_rate`` model self-speculative decoding on
    the continuous engine (the static baseline stays sequential): each
    iteration verifies a ``spec_k``-token window per slot and commits
    ``expected_accepted_tokens(acceptance_rate, spec_k)`` of them — the
    result gains ``expected_tokens_per_step`` and the speculative
    amortization shows up directly in ``continuous_tokens_per_s``.

    Every prediction also carries ``energy_j_per_token`` — the
    eq.-(15) dynamic energy of one iteration plus the board's static
    draw over its duration, per committed token
    (``core.energy.serve_energy_per_token``) — so the paper's 35-50%
    INT4 energy-reduction claim is checkable against the same serve
    operating point the throughput numbers describe.

    ``tp`` is the tensor-parallel degree of the sharded paged backend
    (``plan`` stays the GLOBAL pool): per-device KV traffic AND weight
    traffic/FLOPs drop to 1/tp with the megatron collective charged
    against the network link (see ``mixed_iteration_cost``), and the
    result gains per-device page-pool terms — ``per_device_pool_bytes``
    (each device's KV-head slice of the whole pool) and
    ``per_device_pool_occupancy`` (identical on every device: a page's
    rows span all shards, so occupancy is a property of the block
    tables, which are replicated host state).

    ``dp`` is the data-parallel replica count (``serve/router.py``):
    replicas are fully independent engines, so aggregate throughput is
    dp x the per-replica rate and the cluster serves dp x the slots —
    dp>1 adds ``dp``/``aggregate_tokens_per_s``/``cluster_slots``.
    Whenever the cluster has more than one device (tp>1 or dp>1) the
    result also carries ``tokens_per_s_per_device`` (the scaling-
    efficiency number: collectives and replicated leaves pull it below
    the dp=tp=1 rate) and ``cost_per_million_tokens`` (amortized
    device-hours at ``hw.cost_per_hour`` plus electricity from the
    energy model at ``ELECTRICITY_USD_PER_KWH``).  The tp=1, dp=1 cell
    is byte-identical to the pre-cluster model.

    ``chunk_tokens`` models the scheduler's chunked-prefill budget and
    turns on the latency DECOMPOSITION.
    Every call returns ``predicted_itl_s`` (steady-state inter-token
    latency: one mixed iteration per committed window token),
    ``predicted_itl_worst_s`` (the iteration a co-scheduled admission
    burst lands in — unchunked that burst is the request's whole
    uncached suffix, chunked it is capped at ``chunk_tokens``; this is
    the p99-ITL spike chunking exists to flatten) and
    ``predicted_ttft_s`` (admission iterations to first token:
    one burst iteration unchunked, ``ceil(suffix/chunk_tokens)``
    chunk-capped iterations chunked — the TTFT price of the flatter
    tail).  With ``chunk_tokens`` set the steady-state iteration also
    clamps its amortized prefill to the budget, and the result echoes
    ``chunk_tokens``/``prefill_chunks_per_request``.

    ``parked_context_tokens`` models the host swap tier
    (``SchedulerConfig.host_pool_bytes``): a returning multi-turn
    session whose KV was parked at that context length pays
    ``swap_in_s`` (scatter its pages back over ``h2d_bw x u_h2d``)
    plus one admission iteration instead of re-prefilling the whole
    context — the result gains the ``swap_vs_recompute`` keys plus
    ``predicted_resume_ttft_s`` / ``predicted_recompute_ttft_s`` and
    ``swap_cheaper`` (1.0/0.0).

    ``window`` > 0 models the ring-paged sliding-window engine
    (``SchedulerConfig.windowed_kv``) against the SAME full-attention
    static baseline: each slot's held pages clamp at the O(window) ring
    bound — so ``effective_slots`` (and with it admitted concurrency at
    fixed pool bytes) multiplies — and each decode step streams at most
    ``window`` tokens of KV.  The result echoes ``window`` and
    ``ring_pages_per_slot``.
    """
    avg_ctx = avg_prompt + avg_new / 2
    live = effective_slots(plan, slots, avg_prompt, avg_new, admission,
                           window=window, spec_k=spec_k)
    hit = avg_prompt * min(1.0, max(0.0, prefix_hit_rate))
    # continuous: amortized one prefill per finished request per avg_new steps
    cont = mixed_iteration_cost(
        spec, hw, precision, plan,
        prefill_tokens=int((avg_prompt - hit) * live / max(1.0, avg_new)),
        decode_slots=int(round(live)), avg_context=avg_ctx,
        cached_prefix_tokens=int(hit * live / max(1.0, avg_new)), tp=tp,
        spec_k=spec_k, acceptance_rate=acceptance_rate,
        chunk_tokens=chunk_tokens, window=window)
    # static: same decode roofline but slots idle in the drain tail --
    # useful-token rate scales by mean/max occupancy (~avg/(2*avg) for a
    # uniform length spread) and every context pads to the batch max.
    stat = mixed_iteration_cost(
        spec, hw, precision, plan,
        prefill_tokens=int(avg_prompt * slots / max(1.0, 2 * avg_new)),
        decode_slots=slots, avg_context=avg_prompt + avg_new, tp=tp)
    static_tps = stat.tokens_per_s * 0.5
    from repro.core.energy import serve_energy_per_token
    out = {"continuous_tokens_per_s": cont.tokens_per_s,
           "static_tokens_per_s": static_tps,
           "speedup": cont.tokens_per_s / max(1e-12, static_tps),
           "effective_slots": live,
           "prefix_hit_rate": min(1.0, max(0.0, prefix_hit_rate)),
           "energy_j_per_token": serve_energy_per_token(
               cont.flops, cont.bytes_moved, cont.iteration_s,
               cont.decode_tokens, hw, precision)}
    # TTFT/ITL decomposition: the admission-burst iteration is the
    # steady-state batch plus the prefill work ONE arriving request
    # lands in a single iteration (whole uncached suffix unchunked,
    # chunk_tokens-capped chunked).
    suffix = max(0.0, avg_prompt - hit)
    burst = int(min(suffix, chunk_tokens) if chunk_tokens else suffix)
    n_chunks = (-(-int(suffix) // int(chunk_tokens))
                if chunk_tokens and suffix else 1) or 1
    worst = mixed_iteration_cost(
        spec, hw, precision, plan, prefill_tokens=max(1, burst),
        decode_slots=int(round(live)), avg_context=avg_ctx, tp=tp,
        spec_k=spec_k, acceptance_rate=acceptance_rate, window=window)
    per_tok = expected_accepted_tokens(acceptance_rate, spec_k)
    out["predicted_itl_s"] = cont.iteration_s / per_tok
    out["predicted_itl_worst_s"] = worst.iteration_s / per_tok
    out["predicted_ttft_s"] = n_chunks * worst.iteration_s
    if chunk_tokens:
        out["chunk_tokens"] = float(chunk_tokens)
        out["prefill_chunks_per_request"] = float(n_chunks)
    if parked_context_tokens is not None:
        rec = swap_vs_recompute(spec, hw, precision, plan,
                                context_tokens=parked_context_tokens)
        out["parked_context_tokens"] = float(parked_context_tokens)
        out.update({k: v for k, v in rec.items() if k != "cheaper"})
        out["swap_cheaper"] = 1.0 if rec["cheaper"] == "swap" else 0.0
        # resume = scatter the pages back + the one-token rejoin
        # iteration; recompute = the full-context prefill + the same
        # admission iteration (the burst term already priced above)
        out["predicted_resume_ttft_s"] = rec["swap_in_s"] + worst.iteration_s
        out["predicted_recompute_ttft_s"] = (rec["reprefill_s"]
                                             + worst.iteration_s)
    if window > 0:
        from repro.serve.paged_cache import ring_pages
        out["window"] = float(window)
        out["ring_pages_per_slot"] = float(
            ring_pages(window, plan.page_size, spec_k))
    if spec_k > 1:
        out["spec_k"] = float(spec_k)
        out["acceptance_rate"] = min(1.0, max(0.0, acceptance_rate))
        out["expected_tokens_per_step"] = expected_accepted_tokens(
            acceptance_rate, spec_k)
    if tp > 1:
        held = mean_pages_held(avg_prompt, avg_new, plan.page_size, admission)
        kv_shard = tp if tp_shards_kv(spec, tp) else 1
        out["tp"] = float(tp)
        out["per_device_pool_bytes"] = plan.total_bytes / kv_shard
        out["per_device_pool_occupancy"] = min(
            1.0, live * held / max(1.0, plan.usable_pages))
    if dp > 1:
        out["dp"] = float(dp)
        out["aggregate_tokens_per_s"] = dp * cont.tokens_per_s
        out["cluster_slots"] = dp * live
    if tp > 1 or dp > 1:
        devices = tp * dp
        agg = dp * cont.tokens_per_s
        out["tokens_per_s_per_device"] = agg / devices
        out["cost_per_million_tokens"] = cost_per_million_tokens(
            agg, devices, out["energy_j_per_token"], hw)
    return out


#: Electricity price the cost model charges the energy term at ($/kWh).
ELECTRICITY_USD_PER_KWH = 0.25


def cost_per_million_tokens(aggregate_tokens_per_s: float, devices: int,
                            energy_j_per_token: float,
                            hw: HardwareSpec) -> float:
    """$ per 1M tokens of a cluster: amortized device-hours
    (``hw.cost_per_hour`` per device, all devices billed for the wall
    time 1M tokens take at the aggregate rate) plus electricity for
    the energy the model says those tokens dissipate."""
    if aggregate_tokens_per_s <= 0:
        return float("inf")
    device_usd = (devices * hw.cost_per_hour / 3600.0) \
        / aggregate_tokens_per_s * 1e6
    energy_usd = energy_j_per_token * 1e6 \
        * ELECTRICITY_USD_PER_KWH / 3.6e6
    return device_usd + energy_usd


def serve_cluster_grid(spec: ModelSpec, hw: HardwareSpec,
                       precision: PrecisionSpec, plan: PagedCachePlan, *,
                       slots: int, avg_prompt: float, avg_new: float,
                       tps=(1, 2, 4), dps=(1, 2),
                       **predict_kw) -> list:
    """The tp x dp serve sweep: one ``predict_serve_throughput`` cell
    per (tp, dp), each row annotated with tp/dp/devices and — for every
    cell, including tp=1, dp=1 — the per-device rate and
    cost-per-million-tokens, so cluster shapes compare on one axis:
    what does a million tokens cost, and how much of each device's
    dp=tp=1 rate survives the collectives.  tp values that don't
    divide the head counts still appear (the fallback replicates, the
    row shows no win) — silent omission would read as 'not modelled'.
    """
    rows = []
    for tp in tps:
        for dp in dps:
            cell = predict_serve_throughput(
                spec, hw, precision, plan, slots=slots,
                avg_prompt=avg_prompt, avg_new=avg_new, tp=tp, dp=dp,
                **predict_kw)
            agg = cell.get("aggregate_tokens_per_s",
                           cell["continuous_tokens_per_s"])
            devices = tp * dp
            row = dict(cell)
            row.update({
                "tp": tp, "dp": dp, "devices": devices,
                "aggregate_tokens_per_s": agg,
                "tokens_per_s_per_device": agg / devices,
                "cost_per_million_tokens": cost_per_million_tokens(
                    agg, devices, cell["energy_j_per_token"], hw),
            })
            rows.append(row)
    return rows


def failover_recovery_cost(spec: ModelSpec, hw: HardwareSpec,
                           precision: PrecisionSpec, plan: PagedCachePlan,
                           *, context_tokens: float) -> Dict[str, float]:
    """Cost of moving ONE mid-flight request off a dead replica, both
    ways the serve stack could pay it — EdgeProfiler's own traffic
    methodology (bytes over a link vs FLOPs over a roofline) applied to
    failover:

    * **migrate** — ship the request's KV pages to a survivor over the
      board link: ``context_tokens x plan.bytes_per_token`` bytes at
      ``net_bw x u_net``.  ``plan`` carries the cache dtype, so int4
      resume state moves ~1/8 the fp32 bytes — quantization flips
      which regime is cheap, not just how cheap it is.
    * **re-prefill** — recompute the context from the resume record's
      token ids on the survivor (what ``export_active`` migration
      actually does today): the full prefill FLOPs at the device's
      effective rate, dequant overhead included.

    Returns both times, the cheaper regime's name, and ``recovery_s``
    (the min — what a transport-equipped fleet would pay).  On 1 GbE
    edge boards (rpi/jetson class) int4 migration wins by orders of
    magnitude; on ICI-linked accelerators with huge matmul rates,
    re-prefill can win — the crossover is the point of modelling it.
    """
    if context_tokens < 0:
        raise ValueError("context_tokens must be >= 0")
    migrate_bytes = context_tokens * plan.bytes_per_token
    migrate_s = migrate_bytes / (hw.net_bw * hw.u_net)
    flops = (mixed_iteration_flops(spec, int(context_tokens), 0, 0.0)
             * precision.dequant_overhead)
    reprefill_s = flops / (hw.flops_at(precision.name) * hw.u_compute)
    return {"migrate_bytes": migrate_bytes, "migrate_s": migrate_s,
            "reprefill_flops": flops, "reprefill_s": reprefill_s,
            "cheaper": "migrate" if migrate_s <= reprefill_s
            else "reprefill",
            "recovery_s": min(migrate_s, reprefill_s)}


def swap_vs_recompute(spec: ModelSpec, hw: HardwareSpec,
                      precision: PrecisionSpec, plan: PagedCachePlan,
                      *, context_tokens: float) -> Dict[str, float]:
    """Cost of PARKING one slot's KV in host DRAM vs re-prefilling it —
    the analytical crossover behind the scheduler's evict→swap→preempt
    escalation and idle-session parking (``SchedulerConfig.
    host_pool_bytes``):

    * **swap** — move the slot's pages over the host link, both ways:
      whole pages (``ceil(context/page_size)``, the transfer
      granularity the backend gathers/scatters at) at
      ``h2d_bw x u_h2d``, charged for the round trip — park now, pay
      the scatter again at resume.  ``plan`` carries the cache dtype,
      so int4 pages move ~1/8 the fp32 bytes over the SAME link:
      quantization is what pulls the swap tier under the recompute
      line on the paper's edge boards.
    * **re-prefill** — recompute the context from the resume record's
      token ids (what preemption pays today): full prefill FLOPs at
      the device's effective rate, dequant overhead included — same
      term as ``failover_recovery_cost``, which prices the NETWORK
      flavour of this trade.

    Returns the leg times, the round trip, the recompute time, which
    regime is cheaper, and ``host_capacity_contexts`` — how many such
    parked contexts ``hw.host_mem_capacity`` holds, the host-memory
    axis the support matrix now carries.
    """
    if context_tokens < 0:
        raise ValueError("context_tokens must be >= 0")
    pages = -(-int(context_tokens) // plan.page_size) if context_tokens else 0
    swap_bytes = pages * plan.page_bytes
    bw = hw.h2d_bw * hw.u_h2d
    swap_out_s = swap_bytes / bw
    swap_in_s = swap_bytes / bw
    swap_s = swap_out_s + swap_in_s
    flops = (mixed_iteration_flops(spec, int(context_tokens), 0, 0.0)
             * precision.dequant_overhead)
    reprefill_s = flops / (hw.flops_at(precision.name) * hw.u_compute)
    return {"swap_bytes": swap_bytes,
            "swap_out_s": swap_out_s, "swap_in_s": swap_in_s,
            "swap_s": swap_s,
            "reprefill_flops": flops, "reprefill_s": reprefill_s,
            "cheaper": "swap" if swap_s <= reprefill_s else "reprefill",
            "host_capacity_contexts": (hw.host_mem_capacity / swap_bytes
                                       if swap_bytes else float("inf"))}


def serve_availability(spec: ModelSpec, hw: HardwareSpec,
                       precision: PrecisionSpec, plan: PagedCachePlan, *,
                       slots: int, avg_prompt: float, avg_new: float,
                       dp: int, failed: int,
                       offered_tokens_per_s: float | None = None,
                       **predict_kw) -> Dict[str, float]:
    """Fleet capacity and goodput with ``failed`` of ``dp`` replicas
    dead.

    Replicas are independent engines behind the router, so degraded
    capacity is simply the survivors' aggregate rate; what failure
    actually costs a serve fleet is (a) the LOAD MULTIPLIER — the dead
    replicas' traffic lands on ``dp - failed`` survivors, so each one
    sees ``dp / (dp - failed)`` of its share, and (b) the one-time
    RECOVERY of every mid-flight request (``failover_recovery_cost``
    at the mean failover context, times the dead replicas' live
    slots).  With ``offered_tokens_per_s`` given, ``goodput`` is the
    offered load clipped to degraded capacity — the fraction the
    degraded fleet still serves inside its SLO budget, matching how
    the open-loop driver counts goodput.
    """
    if dp < 1:
        raise ValueError("dp must be >= 1")
    if not 0 <= failed < dp:
        raise ValueError(f"failed={failed} must be in [0, dp={dp})")
    survivors = dp - failed

    def _agg(d: Dict[str, float]) -> float:
        return d.get("aggregate_tokens_per_s", d["continuous_tokens_per_s"])

    base = predict_serve_throughput(
        spec, hw, precision, plan, slots=slots, avg_prompt=avg_prompt,
        avg_new=avg_new, dp=dp, **predict_kw)
    degraded = predict_serve_throughput(
        spec, hw, precision, plan, slots=slots, avg_prompt=avg_prompt,
        avg_new=avg_new, dp=survivors, **predict_kw)
    cap0, cap1 = _agg(base), _agg(degraded)
    # mean failover context: prompt fully written, half the output
    # committed when the replica died
    ctx = avg_prompt + avg_new / 2
    rec = failover_recovery_cost(spec, hw, precision, plan,
                                 context_tokens=ctx)
    live = effective_slots(plan, slots, avg_prompt, avg_new,
                           predict_kw.get("admission", "lazy"))
    out = {"dp": float(dp), "failed": float(failed),
           "survivors": float(survivors),
           "aggregate_tokens_per_s": cap0,
           "degraded_tokens_per_s": cap1,
           "capacity_fraction": cap1 / max(1e-12, cap0),
           "load_multiplier": dp / survivors,
           "failover_context_tokens": ctx,
           "failover_requests": failed * live,
           "recovery_s_per_request": rec["recovery_s"],
           "recovery_s_total": failed * live * rec["recovery_s"],
           **{f"recovery_{k}": v for k, v in rec.items()}}
    if offered_tokens_per_s is not None:
        good = min(offered_tokens_per_s, cap1)
        out["offered_tokens_per_s"] = offered_tokens_per_s
        out["goodput_tokens_per_s"] = good
        out["goodput_fraction"] = good / max(1e-12, offered_tokens_per_s)
    return out


@dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def bound(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)  # type: ignore[arg-type]


def roofline_terms(step_flops_per_device: float, hbm_bytes_per_device: float,
                   collective_bytes_per_device: float, hw: HardwareSpec,
                   links: int = 4) -> RooflineTerms:
    """Assignment constants: per-chip peak, HBM BW, ICI links."""
    return RooflineTerms(
        compute_s=step_flops_per_device / hw.peak_flops,
        memory_s=hbm_bytes_per_device / hw.mem_bw,
        collective_s=collective_bytes_per_device / (hw.net_bw * links),
    )

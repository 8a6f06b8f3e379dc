"""EdgeProfiler analytical model (paper §III) — faithful + generalized.

``paper_*`` functions are the literal equations (7)-(9) for the vanilla
MHA transformer the paper assumes.  ``analyze()`` is the generalized form
driven by ``core.blocks`` so it covers every assigned architecture, both
inference and training, single-device and sharded.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

from repro.core import blocks
from repro.core.model_config import ModelSpec, ShapeSpec
from repro.core.precision import PrecisionSpec


# ---------------------------------------------------------------------------
# Paper-faithful equations (7)-(9)
# ---------------------------------------------------------------------------

def paper_param_count(L: int, H: int, I: int, V: int) -> float:
    """Eq. (7): P = L·4H² + L·2HI + 2VH."""
    return L * 4 * H * H + L * 2 * H * I + 2 * V * H


def paper_flops_per_token(L: int, H: int, I: int, S: int) -> float:
    """Eq. (8): FLOPs/token = L(6H² + 4HS + 4HI + 4IH + 9H)."""
    return L * (6 * H * H + 4 * H * S + 4 * H * I + 4 * I * H + 9 * H)


def paper_memory(P: float, B: float, S: int, H: int, L: int) -> float:
    """Eq. (9): M = P·B + S·H·B + 2L·S·H·B."""
    return P * B + S * H * B + 2 * L * S * H * B


# ---------------------------------------------------------------------------
# Generalized analysis
# ---------------------------------------------------------------------------

@dataclass
class MemoryBreakdown:
    weights: float = 0.0
    activations: float = 0.0
    kv_cache: float = 0.0
    optimizer: float = 0.0
    gradients: float = 0.0

    @property
    def total(self) -> float:
        return (self.weights + self.activations + self.kv_cache
                + self.optimizer + self.gradients)


@dataclass
class CollectiveBreakdown:
    """Per-device collective bytes per step (analytical prediction)."""
    dp_grad: float = 0.0           # gradient all-reduce / reduce-scatter
    tp_act: float = 0.0            # TP activation all-reduce / all-gather
    ep_a2a: float = 0.0            # MoE all-to-all (dispatch + combine)
    sp_softmax: float = 0.0        # seq-parallel softmax stat exchange

    @property
    def total(self) -> float:
        return self.dp_grad + self.tp_act + self.ep_a2a + self.sp_softmax


@dataclass
class Analysis:
    """Everything EdgeProfiler derives for one (model, shape, precision[, mesh])."""
    spec: ModelSpec
    shape: ShapeSpec
    params: int
    params_active: int
    flops_per_token: float         # useful forward flops (top-k MoE)
    flops_dispatch_per_token: float  # what dense-dispatch HLO executes
    step_flops: float              # full step (train: fwd+bwd; serve: fwd)
    model_flops: float             # assignment: 6·N·D (dense) / 6·N_active·D (MoE)
    memory: MemoryBreakdown = field(default_factory=MemoryBreakdown)
    collectives: CollectiveBreakdown = field(default_factory=CollectiveBreakdown)
    hbm_traffic: float = 0.0       # bytes moved per step per device (roofline)

    def as_dict(self) -> Dict[str, float]:
        return {
            "params": self.params, "params_active": self.params_active,
            "flops_per_token": self.flops_per_token,
            "step_flops": self.step_flops, "model_flops": self.model_flops,
            "mem_weights": self.memory.weights, "mem_acts": self.memory.activations,
            "mem_kv": self.memory.kv_cache, "mem_opt": self.memory.optimizer,
            "mem_grad": self.memory.gradients, "mem_total": self.memory.total,
            "coll_dp": self.collectives.dp_grad, "coll_tp": self.collectives.tp_act,
            "coll_ep": self.collectives.ep_a2a, "coll_sp": self.collectives.sp_softmax,
            "coll_total": self.collectives.total, "hbm_traffic": self.hbm_traffic,
        }


# ---------------------------------------------------------------------------
# Paged-KV serving: cache sizing + mixed prefill/decode iteration model
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PagedCachePlan:
    """Sizing of a block-table paged KV cache inside a byte budget.

    One logical page covers ``page_size`` token positions across ALL
    attention layers (each layer owns its own k/v pool slice of the
    page), so ``page_bytes`` already sums over layers.  Page 0 is the
    reserved null page inactive slots point at, hence ``usable_pages``.

    ``tp`` > 1 marks the byte fields as the PER-DEVICE share of a
    KV-head-sharded pool (``plan_paged_cache(tp=)``) — consumers that
    take their own ``tp`` knob (``latency.mixed_iteration_cost`` /
    ``predict_serve_throughput``) reject such plans instead of
    silently dividing the pool bytes twice.
    """
    page_size: int
    num_pages: int
    page_bytes: float              # bytes per page across all attn layers
    bytes_per_token: float         # page_bytes / page_size
    tp: int = 1                    # >1: byte fields are per-device shares

    @property
    def usable_pages(self) -> int:
        return max(0, self.num_pages - 1)

    @property
    def max_tokens(self) -> int:
        return self.usable_pages * self.page_size

    @property
    def total_bytes(self) -> float:
        return self.num_pages * self.page_bytes


# Stored bytes per KV value + whether per-token-per-head f32 scales ride
# along, per paged-cache dtype.  int4 nibble-packs two tokens per byte
# (0.5 B/value); quantized layouts carry one f32 scale per token per kv
# head per k/v pool — the overhead that keeps the paper's "4-bit cuts
# memory 60-70%" claim honest instead of a naive 8x.  Scale pages are
# stored LANE-MAJOR (P, KV, page) — the token dim rides the 128-wide
# lane dim, so one page's scales occupy a single (8, 128) f32 tile on
# real TPU and the physical scale traffic matches this logical KV*4
# B/token accounting to within one tile of padding
# (``scale_page_tile_bytes`` quantifies both layouts).
KV_CACHE_DTYPES = {"fp32": (4.0, False), "int8": (1.0, True),
                   "int4": (0.5, True)}


def scale_page_tile_bytes(kv_heads: int, page_size: int,
                          layout: str = "lane_major") -> float:
    """PHYSICAL f32 bytes one quantized page's scale block occupies on
    TPU after Mosaic pads the trailing two dims to the (8, 128) f32
    tile.  ``lane_major`` is the shipped (KV, page) layout (token dim
    on the lanes: one tile for page_size <= 128 and kv_heads <= 8);
    ``row_major`` is the pre-lane-major (page, KV, 1) layout whose
    per-token (KV, 1) blocks each padded to a full tile — the gap this
    helper exists to show (e.g. KV=2, page=16: 64 KiB -> 4 KiB)."""
    def _pad(n: int, m: int) -> int:
        return -(-n // m) * m
    if layout == "lane_major":
        return _pad(kv_heads, 8) * _pad(page_size, 128) * 4.0
    if layout == "row_major":
        return page_size * _pad(kv_heads, 8) * _pad(1, 128) * 4.0
    raise ValueError(f"layout {layout!r} (want lane_major | row_major)")


def tp_shards_kv(spec: ModelSpec, tp: int) -> bool:
    """True iff a model-axis of size ``tp`` actually shards the paged KV
    pools (divides both head counts) — the same policy
    ``parallel.sharding.ShardingRules.cache_entry_pspec`` enforces.
    Non-divisible counts replicate the pools, so per-device byte/traffic
    models must NOT divide by tp for them."""
    return tp > 1 and spec.num_kv_heads % tp == 0 and spec.num_heads % tp == 0


def tp_shards_weights(spec: ModelSpec, tp: int) -> bool:
    """True iff the sharded backend also splits the WEIGHTS column/row-
    parallel at this tp.  The backend gates weight sharding on the
    pools sharding (the odd-KV fallback keeps everything replicated for
    the bitwise contract), and the megatron split additionally wants
    the MLP hidden dim divisible so mlp_wi/mlp_wo pair up — per-device
    weight traffic and FLOPs divide by tp exactly when this holds."""
    return tp_shards_kv(spec, tp) and spec.d_ff % tp == 0


def kv_cache_dtype_bytes(cache_dtype: str):
    """(bytes per stored KV value, scales present) for a paged-cache
    dtype name — the one mapping every byte-accounting consumer
    (layout sizing, iteration model, benchmarks) shares."""
    try:
        return KV_CACHE_DTYPES[cache_dtype]
    except KeyError:
        raise ValueError(f"cache dtype {cache_dtype!r} "
                         f"(want {sorted(KV_CACHE_DTYPES)})") from None


def page_bytes(spec: ModelSpec, page_size: int, bytes_per: float = 2.0,
               quantized_scales: bool = False, tp: int = 1) -> float:
    """Bytes of one page across all attention layers (k and v pools).

    ``bytes_per`` is the stored element width (1.0 for int8 pages, 0.5
    for nibble-packed int4); ``quantized_scales`` adds the
    per-token-per-head f32 scale arrays the quantized layouts carry
    (see ``KV_CACHE_DTYPES``).  With ``tp`` > 1 this is the PER-DEVICE
    share of one page under tensor-parallel serving: the pools are
    partitioned over the KV-head dim, so each device stores KV/tp
    heads of every page — but ONLY when tp divides both head counts.
    A non-divisible count replicates the pools on every device
    (``parallel.sharding.ShardingRules.cache_entry_pspec`` fallback),
    so the per-device share stays the full page; pricing it as a
    shard here would let budget-driven layouts overshoot the device
    by up to tp x.  The single source of truth for the paged layout's
    footprint — budget fitting and layout-matching plans both derive
    from it.
    """
    kv = spec.num_kv_heads
    if tp_shards_kv(spec, tp):
        kv //= tp
    row = kv * spec.head_dim * bytes_per
    if quantized_scales:
        row += kv * 4.0
    return 2.0 * spec.num_attention_layers() * page_size * row


def plan_paged_cache(spec: ModelSpec, budget_bytes: float,
                     page_size: int = 16, bytes_per: float = 2.0,
                     quantized_scales: bool = False,
                     tp: int = 1) -> PagedCachePlan:
    """Fit the largest page pool into ``budget_bytes``.

    ``budget_bytes`` is a PER-DEVICE budget; with ``tp`` > 1 each
    device holds only its KV-head slice of every page, so the same
    per-device budget addresses ~tp x more logical pages — the
    capacity win tensor-parallel paged serving exists for.  The
    returned plan's byte fields stay per-device.
    """
    pb = page_bytes(spec, page_size, bytes_per, quantized_scales, tp=tp)
    num_pages = int(budget_bytes // pb)
    if num_pages < 2:
        raise ValueError(
            f"KV budget {budget_bytes:.0f} B < 2 pages "
            f"({pb:.0f} B/page) for {spec.name}")
    return PagedCachePlan(page_size=page_size, num_pages=num_pages,
                          page_bytes=pb, bytes_per_token=pb / page_size,
                          tp=tp if tp_shards_kv(spec, tp) else 1)


def kv_budget(device_bytes: float, mem: MemoryBreakdown,
              reserve_frac: float = 0.05) -> float:
    """KV byte budget left after weights + activations (+ safety margin),
    the paper's §IV deployment constraint expressed for the serve path."""
    free = device_bytes * (1.0 - reserve_frac) - mem.weights - mem.activations
    if free <= 0:
        raise ValueError(
            f"no KV budget: weights+activations {mem.weights + mem.activations:.0f} B "
            f"exceed device {device_bytes:.0f} B")
    return free


def expected_experts_hit(spec: ModelSpec, rows: float) -> float:
    """Expected distinct held experts one routed layer touches when
    ``rows`` tokens are routed top-k over all ``num_experts`` uniformly:
    each held expert is missed by a row with probability 1 - k/E, so
    held * (1 - (1 - k/E)**rows)."""
    m = spec.moe
    if m is None:
        return 0.0
    return m.num_held * (1.0 - (1.0 - m.top_k / m.num_experts) ** rows)


def streamed_params(spec: ModelSpec, rows: float) -> float:
    """Parameters one step reads when ``rows`` tokens pass every layer:
    all weights outside the routed experts (the leading dense layers'
    FFNs among them), plus in each MoE layer only the held experts the
    rows are expected to hit (``expected_experts_hit``).  A dense spec
    reads its logical parameter count."""
    P = blocks.param_count(spec, padded=False)
    m = spec.moe
    if m is None:
        return float(P)
    n_moe = sum(1 for i in range(spec.num_layers) if spec.moe_layer(i))
    per_expert = 3 * spec.d_model * m.expert_ff
    held = m.num_held if m.held is not None else m.num_experts
    return float(P - n_moe * (held - expected_experts_hit(spec, rows))
                 * per_expert)


def conv_state_bytes(spec: ModelSpec, slots: int) -> float:
    """Float32 per-slot state of the gated short-convolution layers:
    the last ``conv_width - 1`` conv inputs of each conv layer."""
    return (4.0 * slots * spec.num_conv_layers()
            * max(spec.conv_width - 1, 0) * spec.d_model)


def mixed_iteration_flops(spec: ModelSpec, prefill_tokens: int,
                          decode_slots: int, avg_context: float,
                          cached_prefix_tokens: int = 0) -> float:
    """Useful FLOPs of ONE continuous-batching iteration that prefills
    ``prefill_tokens`` prompt tokens and decodes one token for each of
    ``decode_slots`` live slots at mean context ``avg_context``.

    ``cached_prefix_tokens`` models prefix-cache hits: those tokens run
    NO projections/MLP (their KV is read from shared pages), while the
    prefilled suffix tokens attend over a context that starts at the
    cached length — so hits remove the per-token matmul FLOPs entirely
    and shift the suffix attention span, exactly what
    ``models.lm.prefill_paged`` executes.
    """
    fl = 0.0
    if prefill_tokens:
        mean_ctx = cached_prefix_tokens + prefill_tokens // 2
        fl += blocks.forward_flops_per_token(spec, mean_ctx) * prefill_tokens
    if decode_slots:
        fl += blocks.forward_flops_per_token(
            spec, int(avg_context)) * decode_slots
    return fl


def expected_accepted_tokens(acceptance_rate: float, spec_k: int) -> float:
    """Expected tokens COMMITTED per speculative decode window.

    A window verifies the last committed token plus ``spec_k - 1``
    drafted tokens; greedy acceptance commits the matching draft prefix
    plus one bonus token, so with i.i.d. per-draft acceptance
    probability ``a`` the emitted count is truncated-geometric:
    ``E = 1 + a + a^2 + ... + a^(K-1) = (1 - a^K) / (1 - a)``.
    ``spec_k = 1`` (or a = 0) is the plain decode step: exactly one
    token.  This is the amortization factor speculative decoding buys
    on the memory-bound decode roofline — the weights and the slot's
    KV pages stream ONCE per window regardless of how many tokens it
    commits.
    """
    if spec_k < 1:
        raise ValueError(f"spec_k must be >= 1, got {spec_k}")
    a = min(1.0, max(0.0, acceptance_rate))
    if a >= 1.0:
        return float(spec_k)
    return (1.0 - a ** spec_k) / (1.0 - a)


# ---------------------------------------------------------------------------
# Prefix caching + admission occupancy (serve accounting)
# ---------------------------------------------------------------------------

def expected_prefix_hit_tokens(num_requests: int, num_templates: int,
                               template_tokens: int, page_size: int) -> float:
    """Expected cached-prefix tokens per request for a templated
    workload: ``num_requests`` prompts drawn from ``num_templates``
    shared prefixes of ``template_tokens`` tokens each.

    The first request per template prefills it (cold); every later
    request hits the template's FULL pages.  Sharing is page-granular:
    a template's mid-page remainder sits in a page alongside each
    request's own suffix, so it only reuses on an exact-prompt
    extension (copy-on-write), never across requests with differing
    suffixes — hence the floor to ``page_size``.
    """
    if num_requests <= 0:
        return 0.0
    full = (template_tokens // page_size) * page_size
    warm = max(0, num_requests - num_templates)
    return full * warm / num_requests


def prefix_hit_rate(num_requests: int, num_templates: int,
                    template_tokens: int, avg_prompt: float,
                    page_size: int) -> float:
    """Fraction of prompt tokens served from the prefix store (the
    knob ``predict_serve_throughput`` takes)."""
    hit = expected_prefix_hit_tokens(num_requests, num_templates,
                                     template_tokens, page_size)
    return min(1.0, hit / max(1.0, avg_prompt))


def mean_pages_held(avg_prompt: float, avg_new: float, page_size: int,
                    admission: str = "lazy", window: int = 0,
                    spec_k: int = 1) -> float:
    """Mean pages a request holds over its lifetime.

    ``conservative`` admission reserves pages for prompt+max_new up
    front and holds them until completion; ``lazy`` allocation holds
    pages(prompt + generated so far), which averages half the decode
    span — the occupancy headroom that lets the lazy scheduler admit
    more concurrent requests into the same pool (preemption keeps the
    FCFS head live when the gamble loses).

    ``window`` > 0 models the RING-paged sliding-window cache
    (``serve.paged_cache.ring_window``): a slot never holds more than
    ``ring_pages(window, page_size, spec_k)`` pages no matter how long
    its stream — out-of-window pages are recycled — so held pages clamp
    at that O(window) bound.  This is the term that turns unbounded-
    stream serving from O(context) to O(window) per slot.
    """
    def pages(t: float) -> float:
        return -(-t // page_size)
    if admission == "conservative":
        held = pages(avg_prompt + avg_new)
    elif admission == "lazy":
        held = pages(avg_prompt) + (pages(avg_prompt + avg_new)
                                    - pages(avg_prompt)) / 2.0
    else:
        raise ValueError(f"admission {admission!r}")
    if window > 0:
        from repro.serve.paged_cache import ring_pages
        held = min(held, float(ring_pages(window, page_size, spec_k)))
    return held


def effective_slots(plan: "PagedCachePlan", slots: int, avg_prompt: float,
                    avg_new: float, admission: str = "lazy",
                    window: int = 0, spec_k: int = 1) -> float:
    """Concurrent requests the pool sustains: the slot count capped by
    usable pages over the admission policy's mean held pages (ring-
    clamped when ``window`` > 0 — the windowed engine's concurrency
    multiplier at fixed pool bytes)."""
    held = mean_pages_held(avg_prompt, avg_new, plan.page_size, admission,
                           window=window, spec_k=spec_k)
    return min(float(slots), plan.usable_pages / max(1.0, held))


@dataclass(frozen=True)
class MeshShape:
    """Logical parallelism degrees used for per-device accounting."""
    dp: int = 1
    tp: int = 1
    pods: int = 1

    @property
    def devices(self) -> int:
        return self.dp * self.tp * self.pods

    @property
    def total_dp(self) -> int:
        return self.dp * self.pods


def analyze(spec: ModelSpec, shape: ShapeSpec, precision: PrecisionSpec,
            mesh: MeshShape = MeshShape(), train_dtype_bytes: float = 2.0,
            remat: bool = True, microbatch: int = 0,
            fsdp: bool = False) -> Analysis:
    """Generalized EdgeProfiler analysis for one cell.

    For ``train`` shapes this models the actual train_step (grad-accum,
    remat, AdamW fp32 m/v sharded) — for ``prefill``/``decode`` the serve
    step at the given precision (weight-only quant supported).
    """
    P = blocks.param_count(spec, padded=True)
    P_logical = blocks.param_count(spec, padded=False)
    P_active = blocks.active_param_count(spec)
    S, B = shape.seq_len, shape.global_batch
    d = spec.d_model
    is_train = shape.kind == "train"
    wb = train_dtype_bytes if is_train else precision.bytes_per_param
    ab = train_dtype_bytes if is_train else precision.act_bytes

    # ---- flops -----------------------------------------------------------
    if shape.kind == "decode":
        fpt = blocks.forward_flops_per_token(spec, S)
        fpt_d = blocks.forward_flops_per_token(spec, S, dispatch=True)
        tokens = B                      # one token per sequence per step
        step_flops = fpt * tokens
    else:
        # prefill/train: average context length = S/2 under causal masking
        fpt = blocks.forward_flops_per_token(spec, S // 2)
        fpt_d = blocks.forward_flops_per_token(spec, S // 2, dispatch=True)
        tokens = S * B
        step_flops = fpt * tokens + blocks.encoder_flops(spec) * B
        if is_train:
            step_flops *= 3             # bwd = 2x fwd
            if remat:
                step_flops += fpt * tokens  # recompute fwd inside bwd

    # assignment definition: 6·N·D for training (fwd+bwd); forward-only
    # steps (prefill/decode) do 2·N·D useful matmul FLOPs
    n_active = P_active if spec.moe is not None else P_logical
    model_flops = (6 if is_train else 2) * n_active * tokens

    # ---- memory (per device) ----------------------------------------------
    mem = MemoryBreakdown()
    shard = mesh.devices
    dpx, tp = mesh.total_dp, mesh.tp
    # weights sharded over tp (EP lives inside the tp/model axis); FSDP
    # additionally shards the weight/grad matrices over the data axis and
    # all-gathers per use.  Training gradients use the same layout.
    wshard = tp * (mesh.dp if fsdp else 1)
    mem.weights = P * wb / wshard
    if is_train:
        mb = microbatch or max(1, B // dpx)
        mem.gradients = P * wb / wshard
        mem.optimizer = P * 8.0 / (tp * (mesh.dp if fsdp else min(dpx, 8)))
        # remat keeps one residual per layer per microbatch token
        n_res = spec.num_layers + spec.encoder_layers
        mem.activations = n_res * mb * S * d * train_dtype_bytes / 1  # per device (batch already per-dp)
        if not remat:
            mem.activations *= 8       # rough: all intermediates live
    else:
        mem.activations = B / max(1, dpx) * (1 if shape.kind == "decode" else S) * d * ab * 4
        mem.kv_cache = blocks.cache_bytes(spec, max(1, B // max(1, dpx)), S, bytes_per=2.0) / (
            tp if shape.kind != "decode" or B >= dpx else mesh.devices)
        if B < dpx:                     # long-context: seq-sharded cache
            mem.kv_cache = blocks.cache_bytes(spec, B, S, bytes_per=2.0) / shard

    # ---- HBM traffic per device per step (memory roofline term) ----------
    if shape.kind == "decode":
        # every decode step re-reads all (sharded) weights + the cache once
        mem_t = mem.weights + mem.kv_cache + mem.activations
    else:
        # weights read once per microbatch pass + activations written/read
        passes = 3 if is_train else 1
        mem_t = mem.weights * passes + mem.activations * 2 + mem.kv_cache
    hbm_traffic = mem_t

    # ---- collectives per device per step ----------------------------------
    coll = CollectiveBreakdown()
    if is_train and dpx > 1:
        # ring all-reduce of bf16 grads: 2·(n-1)/n · sharded-bytes
        coll.dp_grad = 2 * (dpx - 1) / dpx * (P * wb / tp)
    if tp > 1:
        # per TP-sharded layer: all-reduce of (tokens_per_device, d) twice
        tok_dev = tokens / max(1, dpx)
        n_tp_layers = sum(1 for k in spec.layer_kinds() if not k.startswith("sl"))
        per = 2 * (tp - 1) / tp * tok_dev * d * ab
        coll.tp_act = per * 2 * n_tp_layers * (3 if is_train else 1)
    if spec.moe is not None and tp > 1:
        tok_dev = tokens / max(1, dpx)
        n_moe = sum(1 for i in range(spec.num_layers) if spec.moe_layer(i))
        coll.ep_a2a = (2 * tok_dev * spec.moe.top_k * d * ab * n_moe
                       * (3 if is_train else 1))
    if shape.kind == "decode" and B < dpx:
        # distributed softmax stats: (heads, 2) floats per layer per step
        n_attn = spec.num_attention_layers()
        coll.sp_softmax = n_attn * B * spec.num_heads * 2 * 4 * (dpx - 1) / dpx

    return Analysis(
        spec=spec, shape=shape, params=P_logical, params_active=P_active,
        flops_per_token=fpt, flops_dispatch_per_token=fpt_d,
        step_flops=step_flops, model_flops=model_flops,
        memory=mem, collectives=coll, hbm_traffic=hbm_traffic)

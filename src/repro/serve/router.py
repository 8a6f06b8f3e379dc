"""Prefix-aware data-parallel router: N engines, one front door.

Tensor parallelism (``ShardedPagedBackend``) shrinks per-device weight
and KV traffic; DATA parallelism multiplies aggregate slots by running
N fully independent scheduler+backend replicas.  The piece that makes
dp work for templated serving is the ROUTER: each replica owns a
private page pool and prefix cache, so two requests sharing a template
prefix only reuse pages if they land on the SAME replica.  Spraying
requests round-robin would cold-prefill every template on every
replica; hashing the template prefix pins each template's traffic to
one replica, so its prefix pages stay hot there.

Routing is rendezvous (highest-random-weight) hashing over the live
replica ids: every (key, replica) pair gets an independent hash score
and the key goes to the max.  Unlike modular hashing, removing a
replica only remaps the keys that replica owned — every other key's
max is untouched — which is exactly the drain/failure behaviour a
serve fleet wants (tests/test_serve_router.py pins this).

The key is the PAGE-ALIGNED template prefix (first ``route_pages``
pages of the prompt, floored to a page boundary): page granularity is
what the prefix cache can actually share, and flooring keeps a
template's requests — which differ only past the template — on one
key even when their suffixes differ in length.

Two liveness escape hatches temper the affinity:

* overflow SPILL at submit: if the hashed replica is backed up by
  ``spill_slack`` more pending requests than the least-loaded replica,
  the request goes to the latter (losing affinity beats queuing).
  Load is ``engine.pending_cost``, which counts DEVICE work only:
  idle session slots and host-parked (swapped-out) KV are not device
  occupancy, and a returning turn is charged its suffix, not its
  whole context — so session affinity survives the spill heuristic.
* REBALANCE on drain: an idle replica steals queued (not yet admitted)
  requests from the back of the deepest queue — up to its free-slot
  count per step, skipping donors whose queue head is a recompute
  resume — so the fleet never sits half-idle while one replica has a
  backlog.  A stolen request's resume record follows it.

The router is also the fleet's HEALTH CHECKER.  ``step()`` wraps each
replica's iteration: a replica that throws ``fail_after`` consecutive
times — or whose last successful step is older than ``heartbeat_s``
while it has work — is EVICTED via ``fail()``, which migrates BOTH its
queued requests and its admitted slots (``engine.export_active`` turns
partial outputs into resume records; rendezvous remaps only the dead
replica's keys) to survivors.  Zero requests are lost even on a crash
mid-decode: the failover contract tests/test_serve_faults.py pins.
``add()`` rejoins a recovered replica (rendezvous shifts back exactly
the keys it wins).

With an optional ``ServeSLO`` policy, ``submit`` applies BACKPRESSURE
from the analytical model instead of queue cost alone: the policy
turns a replica's pending token cost into a predicted TTFT via
``predict_serve_throughput``'s TTFT/ITL decomposition; if only the
hashed replica would violate, the request SPILLS to the best
survivor, and if every live replica would violate (or steady-state
ITL can't meet its SLO at all) the request is SHED with a typed
completion — an overloaded edge fleet degrades by refusing work it
cannot serve in time, never by silently serving it late.

Replicas are plain ``ContinuousBatchingEngine`` instances — the router
never reaches past ``submit``/``step``/``queue``/``num_active`` plus
the load/drain/failover surface (``pending_cost`` for cost-aware
spill, ``take_queued``/``export_resume``/``adopt_resume``/
``export_active``/``head_is_resume``), so any mix of single-device and
tensor-parallel backends works; tp x dp clusters give each replica its
own disjoint device slice (``make_replicas``).  Outputs are
per-request identical-in-band to a single dp=1 engine: which replica
decodes a request changes batch composition, never the per-slot decode
math — and a failover recompute resumes the greedy stream exactly.
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.serve.scheduler import Completion


def route_key(prompt, *, page_size: int = 16, route_pages: int = 2) -> bytes:
    """Page-aligned template-prefix key for a prompt.

    Takes the first ``route_pages * page_size`` tokens floored to a
    page boundary (whole short prompts key on themselves): requests
    sharing a template agree on these pages even though their suffixes
    differ, so they hash to the same replica."""
    toks = np.asarray(prompt, dtype=np.int64).ravel()
    n = min(len(toks), route_pages * page_size)
    aligned = (n // page_size) * page_size
    return toks[: aligned if aligned else n].tobytes()


def _score(key: bytes, replica_id: str) -> int:
    h = hashlib.blake2b(key + b"|" + replica_id.encode(), digest_size=8)
    return int.from_bytes(h.digest(), "big")


def pick_replica(key: bytes, replica_ids: Sequence[str]) -> str:
    """Rendezvous hashing: the live replica with the max (key, id) hash
    score.  Deterministic in (key, id set); removing an id never
    changes the winner of a key it did not win."""
    if not replica_ids:
        raise ValueError("no live replicas")
    return max(replica_ids, key=lambda r: _score(key, r))


@dataclass
class ServeSLO:
    """Admission backpressure from the analytical TTFT/ITL decomposition.

    The policy is three numbers distilled from
    ``core.latency.predict_serve_throughput`` (``from_model`` builds
    them): a replica whose pending token cost is C retires ~
    ``tokens_per_iteration`` of it per iteration at ``predicted_itl_s``
    each, so a newly routed request waits about
    ``C / tokens_per_iteration * predicted_itl_s`` before its own
    admission burst (``predicted_ttft_s``) even starts.  ``submit``
    compares that predicted TTFT against ``ttft_slo_s`` per live
    replica: hashed-target-only violation spills, fleet-wide violation
    sheds.  ``predicted_itl_worst_s`` vs ``itl_slo_s`` is the capacity
    check — a fleet whose admission-burst iteration already exceeds
    the ITL budget cannot serve ANY placement in SLO, so everything
    sheds until load drains."""
    ttft_slo_s: float
    itl_slo_s: float = float("inf")
    predicted_itl_s: float = 0.0
    predicted_itl_worst_s: float = 0.0
    predicted_ttft_s: float = 0.0
    tokens_per_iteration: float = 1.0

    def predict_ttft(self, pending_cost: float) -> float:
        """Queueing delay for ``pending_cost`` tokens of backlog plus
        the request's own admission time."""
        drain = (pending_cost / max(1e-9, self.tokens_per_iteration)
                 * self.predicted_itl_s)
        return drain + self.predicted_ttft_s

    def violates(self, pending_cost: float) -> bool:
        return (self.predict_ttft(pending_cost) > self.ttft_slo_s
                or self.predicted_itl_worst_s > self.itl_slo_s)

    @classmethod
    def from_model(cls, spec, hw, precision, plan, *, slots: int,
                   avg_prompt: float, avg_new: float, ttft_slo_s: float,
                   itl_slo_s: float = float("inf"),
                   chunk_tokens: Optional[int] = None,
                   **predict_kw) -> "ServeSLO":
        """Distil the analytical decomposition into a policy: one
        iteration retires ~``slots`` decode tokens plus one admission
        burst's worth of prefill cost (``chunk_tokens`` when chunked,
        else the mean uncached prompt)."""
        from repro.core.latency import predict_serve_throughput
        pred = predict_serve_throughput(
            spec, hw, precision, plan, slots=slots, avg_prompt=avg_prompt,
            avg_new=avg_new, chunk_tokens=chunk_tokens, **predict_kw)
        per_iter = slots + (chunk_tokens if chunk_tokens else avg_prompt)
        return cls(ttft_slo_s=ttft_slo_s, itl_slo_s=itl_slo_s,
                   predicted_itl_s=pred["predicted_itl_s"],
                   predicted_itl_worst_s=pred["predicted_itl_worst_s"],
                   predicted_ttft_s=pred["predicted_ttft_s"],
                   tokens_per_iteration=float(per_iter))


class PrefixRouter:
    """Front door over N scheduler replicas (see module docstring).

    ``engines`` maps replica id -> ``ContinuousBatchingEngine`` (or a
    list, ids becoming "r0".."rN-1").  Pass ``engines=None`` ids-only
    for pure routing-policy use (the determinism tests).  ``mode`` is
    "prefix" (rendezvous on the template prefix) or "random" (seeded
    uniform — the affinity-free baseline the tests compare against)."""

    def __init__(self, engines=None, *, replica_ids: Optional[Sequence[str]] = None,
                 page_size: int = 16, route_pages: int = 2,
                 spill_slack: int = 4, mode: str = "prefix", seed: int = 0,
                 fail_after: int = 2, heartbeat_s: Optional[float] = None,
                 slo: Optional[ServeSLO] = None):
        if engines is None:
            if replica_ids is None:
                raise ValueError("need engines or replica_ids")
            self.engines: Dict[str, Any] = {r: None for r in replica_ids}
        elif isinstance(engines, dict):
            self.engines = dict(engines)
        else:
            self.engines = {f"r{i}": e for i, e in enumerate(engines)}
        if mode not in ("prefix", "random"):
            raise ValueError(f"unknown route mode {mode!r}")
        if fail_after < 1:
            raise ValueError("fail_after must be >= 1")
        self.page_size = page_size
        self.route_pages = route_pages
        self.spill_slack = spill_slack
        self.mode = mode
        self.fail_after = fail_after
        self.heartbeat_s = heartbeat_s
        self.slo = slo
        self._rng = np.random.default_rng(seed)
        self.busy_s: Dict[str, float] = {r: 0.0 for r in self.engines}
        self.stats: Dict[str, float] = {
            "routed": 0, "spilled": 0, "rebalanced": 0,
            # failover bookkeeping: requests re-submitted by drain /
            # failover (NOT new front-door traffic — kept out of
            # "routed"/"assigned" so those stay per-request counters),
            # replica evictions, step exceptions seen, and SLO
            # backpressure outcomes
            "re_routed": 0, "failed_replicas": 0, "step_faults": 0,
            "slo_shed": 0, "slo_spilled": 0}
        self.assigned: Dict[str, int] = {r: 0 for r in self.engines}
        # health-check state: consecutive step failures and the wall
        # time of the last successful (or idle) step per replica
        self._streak: Dict[str, int] = {r: 0 for r in self.engines}
        self._last_ok: Dict[str, float] = {r: time.monotonic()
                                           for r in self.engines}
        self._shed: List[Completion] = []    # SLO-shed typed completions

    # -- routing policy (pure, engine-free) ---------------------------------
    @property
    def replica_ids(self) -> List[str]:
        return list(self.engines)

    def route(self, prompt) -> str:
        """The replica this prompt's template prefix hashes to — the
        policy only, no load awareness (``submit`` adds spill)."""
        if self.mode == "random":
            ids = self.replica_ids
            return ids[int(self._rng.integers(len(ids)))]
        key = route_key(prompt, page_size=self.page_size,
                        route_pages=self.route_pages)
        return pick_replica(key, self.replica_ids)

    def remove(self, replica_id: str) -> None:
        """Drop a replica from the live set (cooperative drain).  Keys
        it owned remap by rendezvous; every other key keeps its
        replica.  Requests still QUEUED on the removed engine are
        drained and re-submitted through the router — rendezvous
        re-routes exactly the removed replica's keys to survivors, and
        a queued recompute request's resume record (prior output of a
        preempted incarnation) follows it so its completion still
        splices.  Requests already ADMITTED (live slots) are not
        migrated: drain a replica to ``num_active == 0`` first, or use
        ``fail()`` (the failover path) which migrates them too.
        Idempotent: removing an unknown or already-removed id is a
        no-op — a crashed replica may be evicted by the health check
        and again by an operator."""
        eng = self.engines.pop(replica_id, None)
        self._drop_health(replica_id)
        if eng is None:
            return
        for req in eng.take_queued():
            record = eng.export_resume(req.uid)
            target = self.submit(req, _re_route=True)
            if record is not None and self.engines.get(target) is not None:
                self.engines[target].adopt_resume(req.uid, record)

    def fail(self, replica_id: str) -> List[Completion]:
        """FAILOVER eviction: drop a dead replica and migrate ALL its
        work to survivors — queued requests re-route exactly like
        ``remove()``, and admitted slots export as (request,
        resume-record) pairs (``engine.export_active``): committed
        tokens become the record's prior output and the adopting
        replica's greedy recompute resumes the stream exactly, so a
        crash mid-decode loses zero requests.  Slots that had already
        hit their budget complete here (returned).  Migration bypasses
        SLO backpressure: half-done work always lands.  Idempotent
        like ``remove``."""
        eng = self.engines.pop(replica_id, None)
        self._drop_health(replica_id)
        if eng is None:
            return []
        self.stats["failed_replicas"] += 1
        out: List[Completion] = []
        moved = list(eng.take_queued())
        records, done = eng.export_active()
        out.extend(done)
        for req in moved:
            record = eng.export_resume(req.uid)
            target = self.submit(req, _re_route=True)
            if record is not None and self.engines.get(target) is not None:
                self.engines[target].adopt_resume(req.uid, record)
        for req, record in records:
            target = self.submit(req, _re_route=True)
            if self.engines.get(target) is not None:
                self.engines[target].adopt_resume(req.uid, record)
        return out

    def add(self, replica_id: str, engine=None) -> None:
        """Rejoin a (recovered or new) replica.  Rendezvous shifts back
        exactly the keys the new id wins — every other key keeps its
        replica, so rejoining is as non-disruptive as removal.  Queued
        work stays where it is (affinity returns with new traffic);
        health-check state starts fresh."""
        if replica_id in self.engines:
            raise ValueError(f"replica {replica_id!r} is already live")
        self.engines[replica_id] = engine
        self.busy_s.setdefault(replica_id, 0.0)
        self.assigned.setdefault(replica_id, 0)
        self._streak[replica_id] = 0
        self._last_ok[replica_id] = time.monotonic()

    def _drop_health(self, replica_id: str) -> None:
        self._streak.pop(replica_id, None)
        self._last_ok.pop(replica_id, None)

    # -- load-aware dispatch ------------------------------------------------
    @property
    def _live(self) -> List[str]:
        """Replica ids with a real engine attached — ids-only / mixed
        routers carry ``None`` placeholders that load probes and the
        rebalance donor scan must skip (calling ``.queue`` on them was
        the crash)."""
        return [r for r, e in self.engines.items() if e is not None]

    def _load(self, rid: str) -> float:
        """Pending work on a live replica in bucket-padded TOKEN cost
        (``engine.pending_cost``): a queue of sixteen chat turns and a
        queue of one 2k-token prompt are not the same backlog, so spill
        compares cost, not request count.  PARKED state is free here by
        the scheduler's contract: idle session slots and host-parked
        (swapped-out) KV contribute zero — they hold pages or host
        bytes, not iterations — and a queued turn whose context is
        parked on the replica costs only its SUFFIX prefill, so spill
        never punishes the replica that holds a session's KV for
        holding it."""
        eng = self.engines[rid]
        if eng is None:
            return 0.0
        return float(eng.pending_cost)

    def submit(self, req, *, _re_route: bool = False) -> Optional[str]:
        """Route + enqueue one request; returns the replica id chosen
        (or None when SLO backpressure sheds it — the typed completion
        surfaces from the next ``step()``).  Spills off the hashed
        replica only when it leads the least-loaded one by more than
        ``spill_slack`` requests' worth of mean pending cost (the slack
        knob keeps its request-count units; the comparison converts
        through the fleet's current mean cost per pending request, so
        uniform workloads behave exactly as before).

        With a ``ServeSLO`` policy, predicted-TTFT violation overrides
        queue-cost spill: hashed-target-only violation spills to the
        least-loaded live replica, fleet-wide violation SHEDS.
        ``_re_route`` marks drain/failover re-submissions: they count
        under ``re_routed`` (not ``routed``/``assigned``, which stay
        one-per-request front-door counters) and bypass SLO shedding —
        half-done migrated work always lands."""
        target = self.route(req.prompt)
        live = self._live
        if self.slo is not None and not _re_route and live:
            ok_ids = [r for r in live if not self.slo.violates(self._load(r))]
            if not ok_ids:
                self._shed.append(Completion(
                    req.uid, len(req.prompt),
                    np.zeros((0,), np.int32), status="shed"))
                self.stats["slo_shed"] += 1
                return None
            if target not in ok_ids:
                target = min(ok_ids, key=self._load)
                self.stats["slo_spilled"] += 1
        elif self.engines.get(target) is not None and len(live) > 1:
            least = min(live, key=self._load)
            pending = sum(len(self.engines[r].queue)
                          + self.engines[r].num_active for r in live)
            unit = (sum(self._load(r) for r in live) / pending
                    if pending else 1.0)
            if self._load(target) - self._load(least) > self.spill_slack * unit:
                target = least
                self.stats["spilled"] += 1
        if _re_route:
            self.stats["re_routed"] += 1
        else:
            self.stats["routed"] += 1
            self.assigned[target] = self.assigned.get(target, 0) + 1
        if self.engines[target] is not None:
            self.engines[target].submit(req)
        return target

    def rebalance(self) -> int:
        """Let idle replicas steal queued (never admitted) work from
        the back of the deepest queue; returns requests moved.  An idle
        replica steals up to its FREE-SLOT count per step (one steal
        per step left it idling at dp-wide batch widths), re-picking
        the deepest donor after every move.  Donors whose queue HEAD is
        a recompute resume are skipped — head-of-line recompute
        priority is the preemption contract and its re-prefill re-hits
        its own replica's pages — and a stolen TAIL request's resume
        record (if any) migrates with it."""
        moved = 0
        live = self._live
        idle = [r for r in live
                if self.engines[r].num_active == 0
                and not self.engines[r].queue]
        for rid in idle:
            eng = self.engines[rid]
            free = getattr(eng.cfg, "max_slots", 1)
            while free > 0:
                donors = [r for r in live
                          if r != rid and len(self.engines[r].queue) >= 2
                          and not self.engines[r].head_is_resume]
                if not donors:
                    break
                donor = max(donors, key=lambda r: len(self.engines[r].queue))
                req = self.engines[donor].queue.pop()  # tail: head keeps FCFS
                record = self.engines[donor].export_resume(req.uid)
                eng.submit(req)
                if record is not None:
                    eng.adopt_resume(req.uid, record)
                moved += 1
                free -= 1
        self.stats["rebalanced"] += moved
        return moved

    # -- serve loop ---------------------------------------------------------
    def progress(self) -> Dict[int, int]:
        """Tokens emitted so far per live request uid, fleet-wide —
        the open-loop driver's latency-stamping probe.  Uids are unique
        across replicas, and a migrated request's count stays monotone
        (its resume record's prior tokens fold into the adopter's
        ``engine.progress``)."""
        out: Dict[int, int] = {}
        for eng in self.engines.values():
            if eng is not None:
                out.update(eng.progress())
        return out

    def step(self) -> List:
        """One scheduler iteration on every replica that has work,
        tracking per-replica busy seconds (each replica's decode rate
        is its tokens over ITS OWN busy time: replicas are independent
        engines that a test host merely time-slices, so the fleet's
        aggregate rate is the sum of per-replica rates).

        Doubling as the HEALTH CHECK: a replica whose ``step`` raises
        ``fail_after`` consecutive times, or whose last successful
        step is older than ``heartbeat_s`` while it holds work, is
        evicted through ``fail()`` — its queued AND admitted requests
        migrate to survivors before this call returns."""
        out: List = []
        for rid in list(self.engines):
            eng = self.engines.get(rid)
            if eng is None:
                continue
            if eng.num_active == 0 and not eng.queue:
                self._last_ok[rid] = time.monotonic()  # idle is healthy
                continue
            if (self.heartbeat_s is not None
                    and time.monotonic() - self._last_ok.get(
                        rid, time.monotonic()) > self.heartbeat_s):
                out.extend(self.fail(rid))
                continue
            t0 = time.perf_counter()
            try:
                out.extend(eng.step())
            except Exception:
                self.stats["step_faults"] += 1
                self._streak[rid] = self._streak.get(rid, 0) + 1
                if self._streak[rid] >= self.fail_after:
                    out.extend(self.fail(rid))
                continue
            self.busy_s[rid] += time.perf_counter() - t0
            self._streak[rid] = 0
            self._last_ok[rid] = time.monotonic()
        self.rebalance()
        if self._shed:
            out.extend(self._shed)
            self._shed = []
        return out

    def run(self, requests: Sequence) -> List:
        """Route and drain a whole workload; completions sorted by uid.
        Every submitted uid comes back exactly once — ``ok``, ``shed``
        or ``failed`` — whatever happens to its replica."""
        done: List = []
        for req in requests:
            self.submit(req)
        done.extend(self._shed)
        self._shed = []
        while any(e is not None and (e.num_active or e.queue)
                  for e in self.engines.values()):
            done.extend(self.step())
        return sorted(done, key=lambda c: c.uid)

    def aggregate_stats(self) -> Dict[str, float]:
        """Fleet totals: summed engine counters, per-replica busy time
        and the aggregate decode rate (sum of per-replica rates)."""
        agg: Dict[str, float] = dict(self.stats)
        rate = 0.0
        for rid, eng in self.engines.items():
            if eng is None:
                continue
            for k, v in eng.stats.items():
                agg[k] = agg.get(k, 0) + v
            if self.busy_s[rid] > 0:
                rate += eng.stats["decode_tokens"] / self.busy_s[rid]
        agg["aggregate_decode_tokens_per_s"] = rate
        agg["busy_s"] = dict(self.busy_s)
        agg["assigned"] = dict(self.assigned)
        return agg


def make_replicas(params, spec, cfg, *, dp: int, tp: int = 1) -> List:
    """dp independent engines over disjoint device slices: replica r
    runs on ``jax.devices()[r*tp:(r+1)*tp]`` — a tp=1 replica has its
    params and pool committed to its own device."""
    import jax

    from repro.serve.backend import make_backend
    from repro.serve.scheduler import ContinuousBatchingEngine

    devices = jax.devices()
    if dp * tp > len(devices):
        raise RuntimeError(
            f"dp={dp} x tp={tp} needs {dp * tp} devices, "
            f"have {len(devices)}")
    engines = []
    for r in range(dp):
        backend = make_backend(params, spec, cfg, devices=tp,
                               device_list=devices[r * tp:(r + 1) * tp])
        engines.append(ContinuousBatchingEngine(params, spec, cfg,
                                                backend=backend))
    return engines

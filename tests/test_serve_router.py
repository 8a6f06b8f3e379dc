"""Router policy tests: pure host logic, no devices, no engines.

The dp serve fleet's correctness-critical property is DETERMINISTIC
AFFINITY: a template's requests must keep landing on one replica (or
its prefix pages never hit), and replica removal must not reshuffle
the rest of the fleet (or a drain cold-starts every template).  Both
are properties of the rendezvous hash alone, so they test without
building a single engine.
"""
import numpy as np
import pytest

from forced_devices import run_on_devices
from repro.serve.router import PrefixRouter, pick_replica, route_key


def _templated_prompts(n_templates=4, per_template=6, template_len=40,
                       seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    groups = []
    for _ in range(n_templates):
        t = rng.integers(0, vocab, size=template_len).astype(np.int32)
        prompts = [np.concatenate(
            [t, rng.integers(0, vocab,
                             size=int(rng.integers(4, 12))).astype(np.int32)])
            for _ in range(per_template)]
        groups.append(prompts)
    return groups


def test_same_template_same_replica():
    """Every request sharing a template prefix routes to one replica,
    across router instances (determinism, not an instance cache)."""
    for dp in (2, 3, 4):
        r1 = PrefixRouter(replica_ids=[f"r{i}" for i in range(dp)])
        r2 = PrefixRouter(replica_ids=[f"r{i}" for i in range(dp)])
        for prompts in _templated_prompts():
            picks = {r1.route(p) for p in prompts}
            assert len(picks) == 1, f"template split across {picks}"
            assert {r2.route(p) for p in prompts} == picks


def test_distinct_templates_spread_at_dp2():
    """4 distinct templates must use >= 2 replicas at dp=2 — a hash
    that collapsed everything onto one replica would make dp useless
    for the templated workload."""
    router = PrefixRouter(replica_ids=["r0", "r1"])
    picks = {router.route(prompts[0])
             for prompts in _templated_prompts(n_templates=4)}
    assert len(picks) >= 2, picks


def test_removal_only_remaps_own_keys():
    """Rendezvous property: dropping one replica remaps ONLY the keys
    it owned; every other key keeps its replica."""
    ids = [f"r{i}" for i in range(4)]
    router = PrefixRouter(replica_ids=list(ids))
    groups = _templated_prompts(n_templates=12, per_template=1)
    before = {i: router.route(g[0]) for i, g in enumerate(groups)}
    victim = before[0]                    # some replica that owns keys
    router.remove(victim)
    after = {i: router.route(g[0]) for i, g in enumerate(groups)}
    for i, owner in before.items():
        if owner == victim:
            assert after[i] != victim     # remapped somewhere live
        else:
            assert after[i] == owner, (i, owner, after[i])


def test_route_key_page_alignment():
    """Suffixes of different length past the page-aligned template
    prefix must not change the key; a different template must."""
    rng = np.random.default_rng(3)
    t = rng.integers(0, 256, size=20).astype(np.int32)   # 1+ page @ 16
    a = np.concatenate([t, rng.integers(0, 256, size=5).astype(np.int32)])
    b = np.concatenate([t, rng.integers(0, 256, size=11).astype(np.int32)])
    assert route_key(a, page_size=16) == route_key(b, page_size=16)
    t2 = rng.integers(0, 256, size=20).astype(np.int32)
    c = np.concatenate([t2, a[20:]])
    assert route_key(a, page_size=16) != route_key(c, page_size=16)
    # sub-page prompts key on themselves (still deterministic)
    short = t[:7]
    assert route_key(short, page_size=16) == route_key(short.copy(),
                                                       page_size=16)


def test_pick_replica_rejects_empty():
    with pytest.raises(ValueError):
        pick_replica(b"key", [])


def test_random_mode_ignores_prefix():
    """The routing baseline: random mode spreads one template's
    requests across replicas (seeded, so the comparison reproduces)."""
    router = PrefixRouter(replica_ids=["r0", "r1"], mode="random", seed=0)
    prompts = _templated_prompts(n_templates=1, per_template=32)[0]
    picks = {router.route(p) for p in prompts}
    assert picks == {"r0", "r1"}


def test_prefix_routing_beats_random_on_prefix_hits():
    """dp=2 replicas of tp=2 (``make_replicas`` over 8 forced host
    devices, int4 pages) on a 4-template workload: prefix routing's
    aggregate prefix-cache hit tokens beat seeded-random routing's, and
    every request's output stays within the tolerance band of one dp=1
    replica's.  jax fixes the device count at start-up, so the fleets
    run in a subprocess."""
    out = run_on_devices("""
        import jax, numpy as np
        from tolerance import assert_close_tokens
        from repro.configs import ASSIGNED
        from repro.models import lm
        from repro.serve.router import PrefixRouter, make_replicas
        from repro.serve.scheduler import Request, SchedulerConfig

        spec = ASSIGNED["granite-3-8b"].scaled_down(layers=2, width=64,
                                                    vocab=256)
        params = lm.init(jax.random.PRNGKey(0), spec)
        rng = np.random.default_rng(0)
        templates = [rng.integers(0, 256, size=64).astype(np.int32)
                     for _ in range(4)]
        reqs = [Request(i, np.concatenate([
                    templates[i % 4],
                    rng.integers(0, 256, size=int(rng.integers(8, 17)))
                    .astype(np.int32)]), int(rng.integers(16, 25)))
                for i in range(16)]
        cfg = SchedulerConfig(max_slots=4, page_size=16, max_seq=160,
                              kv_budget_bytes=64e6, cache_dtype="int4")

        def fleet(dp, mode):
            engines = make_replicas(params, spec, cfg, dp=dp, tp=2)
            router = PrefixRouter(engines, mode=mode, seed=0,
                                  page_size=cfg.page_size)
            done = router.run([Request(r.uid, r.prompt.copy(),
                                       r.max_new_tokens) for r in reqs])
            for eng in engines:
                eng.alloc.check()
            assert len(done) == len(reqs)
            return (sorted(done, key=lambda c: c.uid),
                    router.aggregate_stats()["prefix_hit_tokens"])

        base, _ = fleet(1, "prefix")
        routed, hits_prefix = fleet(2, "prefix")
        _, hits_random = fleet(2, "random")
        assert hits_prefix > hits_random, (hits_prefix, hits_random)
        for a, b in zip(base, routed):
            assert_close_tokens(a.tokens, b.tokens, context=f"uid {a.uid}")
        print("OK")
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Engine-backed drain / guard tests (real engines, single device)
# ---------------------------------------------------------------------------

def _engines(n, cfg=None):
    import jax
    from repro.configs import ASSIGNED
    from repro.models import lm
    from repro.serve.scheduler import ContinuousBatchingEngine, SchedulerConfig
    spec = ASSIGNED["granite-3-8b"].scaled_down(layers=2, width=64,
                                                vocab=128)
    params = lm.init(jax.random.PRNGKey(0), spec)
    cfg = cfg or SchedulerConfig(max_slots=2, page_size=8, max_seq=48,
                                 num_pages=24)
    return spec, params, cfg, \
        [ContinuousBatchingEngine(params, spec, cfg) for _ in range(n)]


def _reqs(n, seed=0, vocab=128, plen=(10, 20), new=(4, 7)):
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab, size=int(
        rng.integers(plen[0], plen[1] + 1))).astype(np.int32),
        int(rng.integers(new[0], new[1] + 1))) for i in range(n)]


def test_remove_drains_queue_zero_lost():
    """Removing a replica with QUEUED requests loses none of them: the
    drained requests re-route to survivors, the survivor's own queue is
    untouched, and the fleet's outputs stay per-uid identical to a
    single dp=1 engine."""
    from repro.serve.scheduler import ContinuousBatchingEngine
    spec, params, cfg, engines = _engines(2)
    router = PrefixRouter(engines, page_size=cfg.page_size)
    reqs = _reqs(10, seed=2)
    for r in reqs:
        router.submit(r)
    # pre-step: everything is still queued on its hashed replica
    queued = {rid: [q.uid for q in router.engines[rid].queue]
              for rid in router.replica_ids}
    victim = max(queued, key=lambda r: len(queued[r]))
    survivor = next(r for r in router.replica_ids if r != victim)
    victim_uids, survivor_uids = queued[victim], queued[survivor]
    assert victim_uids, "workload must queue on the victim"
    router.remove(victim)
    after = [q.uid for q in router.engines[survivor].queue]
    assert after[:len(survivor_uids)] == survivor_uids  # FCFS kept
    assert sorted(after) == sorted(survivor_uids + victim_uids)
    done = []
    while any(e.num_active or e.queue for e in router.engines.values()):
        done.extend(router.step())
    done = sorted(done, key=lambda c: c.uid)
    assert [c.uid for c in done] == [r.uid for r in reqs]
    ref_eng = ContinuousBatchingEngine(params, spec, cfg)
    ref = ref_eng.run([type(r)(r.uid, r.prompt.copy(), r.max_new_tokens)
                       for r in reqs])
    for a, b in zip(sorted(ref, key=lambda c: c.uid), done):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_remove_hands_off_resume_record():
    """A queued RECOMPUTE request drained off a removed replica keeps
    its prior output: the resume record follows it to the adopting
    engine and the completion still splices prior + new tokens."""
    from repro.serve.scheduler import Request, _Resume
    spec, params, cfg, engines = _engines(2)
    router = PrefixRouter(engines, page_size=cfg.page_size)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 128, size=12).astype(np.int32)
    victim = router.route(prompt)
    # fabricate the scheduler's own preemption-requeue shape: prompt
    # grown by the prior output, budget reduced, resume record parked
    prior = [3, 5]
    resumed = Request(0, np.concatenate(
        [prompt, np.asarray(prior, np.int32)]), 4)
    router.engines[victim].submit(resumed)
    router.engines[victim]._resume[0] = _Resume(len(prompt), list(prior))
    router.remove(victim)
    done = []
    while any(e.num_active or e.queue for e in router.engines.values()):
        done.extend(router.step())
    assert len(done) == 1
    assert list(done[0].tokens[:2]) == prior
    assert len(done[0].tokens) == len(prior) + resumed.max_new_tokens


def test_mixed_mode_none_engine_guards():
    """ids-only / mixed routers carry ``None`` engines: load probes,
    spill, rebalance and removal must skip them instead of raising
    AttributeError."""
    spec, params, cfg, engines = _engines(1)
    router = PrefixRouter(engines={"r0": engines[0], "r1": None},
                          page_size=cfg.page_size)
    assert router._load("r1") == 0.0
    assert router.rebalance() == 0
    for r in _reqs(6, seed=6):
        target = router.submit(r)           # no crash whichever way it hashes
        assert target in ("r0", "r1")
    router.remove("r1")                     # None replica: quiet no-op
    assert "r1" not in router.engines
    # ids-only mode: the pure-policy surface stays engine-free
    ids_only = PrefixRouter(replica_ids=["a", "b"])
    assert ids_only.rebalance() == 0
    assert ids_only.submit(_reqs(1, seed=7)[0]) in ("a", "b")
    ids_only.remove("a")
    assert ids_only.replica_ids == ["b"]


def test_spill_uses_pending_cost_not_request_count():
    """Load is bucket-padded token COST: one long-prompt request must
    outweigh several short ones, steering spill at equal request
    counts."""
    spec, params, cfg, engines = _engines(2)
    from repro.serve.scheduler import Request
    long_req = Request(0, np.zeros(40, np.int32), 4)
    shorts = [Request(1 + i, np.zeros(8, np.int32), 4) for i in range(2)]
    engines[0].submit(long_req)
    for s in shorts:
        engines[1].submit(s)
    router = PrefixRouter(engines, page_size=cfg.page_size)
    assert router._load("r0") > router._load("r1")
    assert router._load("r0") == engines[0].pending_cost


def test_remove_is_idempotent():
    """Removing an unknown or already-removed replica is a quiet no-op
    — a crashed replica may be evicted by the health check and again by
    an operator — and health state is dropped with the engine."""
    spec, params, cfg, engines = _engines(2)
    router = PrefixRouter(engines, page_size=cfg.page_size)
    router.remove("not-a-replica")           # never existed: no KeyError
    assert sorted(router.replica_ids) == ["r0", "r1"]
    router.remove("r0")
    assert router.replica_ids == ["r1"]
    assert "r0" not in router._streak and "r0" not in router._last_ok
    router.remove("r0")                      # already removed: no-op
    router.fail("r0")                        # failover path too
    assert router.stats["failed_replicas"] == 0   # no-op evicted nothing
    assert router.replica_ids == ["r1"]


def test_drain_resubmissions_count_as_re_routed():
    """``remove()``'s drain must not inflate the front-door counters:
    ``routed``/``assigned`` stay one-per-request, the re-submissions
    land under ``re_routed``."""
    spec, params, cfg, engines = _engines(2)
    router = PrefixRouter(engines, page_size=cfg.page_size)
    reqs = _reqs(10, seed=8)
    for r in reqs:
        router.submit(r)
    assert router.stats["routed"] == 10
    assert sum(router.assigned.values()) == 10
    victim = max(router.replica_ids,
                 key=lambda rid: len(router.engines[rid].queue))
    drained = len(router.engines[victim].queue)
    assert drained >= 1
    router.remove(victim)
    assert router.stats["routed"] == 10      # unchanged by the drain
    assert sum(router.assigned.values()) == 10
    assert router.stats["re_routed"] == drained


def test_rebalance_idle_steals_up_to_free_slots():
    """An idle replica steals up to its free-slot count per step (one
    steal per step left it idling at dp-wide batch widths), always from
    the back of the deepest queue."""
    spec, params, cfg, engines = _engines(2)   # max_slots=2
    router = PrefixRouter(engines, page_size=cfg.page_size)
    for r in _reqs(5, seed=10):
        engines[0].submit(r)                  # donor: 5 deep, r1 idle
    moved = router.rebalance()
    assert moved == cfg.max_slots == 2
    assert router.stats["rebalanced"] == 2
    # tail steals keep the donor's FCFS head intact
    assert [q.uid for q in engines[0].queue] == [0, 1, 2]
    assert sorted(q.uid for q in engines[1].queue) == [3, 4]


def test_rebalance_skips_resume_head_donor():
    """Donors whose queue HEAD is a recompute resume are skipped:
    head-of-line recompute priority is the preemption contract, and the
    resume's re-prefill re-hits its own replica's pages."""
    from repro.serve.scheduler import _Resume
    spec, params, cfg, engines = _engines(2)
    router = PrefixRouter(engines, page_size=cfg.page_size)
    reqs = _reqs(4, seed=12)
    for r in reqs:
        engines[0].submit(r)
    engines[0]._resume[reqs[0].uid] = _Resume(5, [1, 2])   # head is a resume
    assert engines[0].head_is_resume
    assert router.rebalance() == 0
    assert len(engines[0].queue) == 4
    del engines[0]._resume[reqs[0].uid]      # head back to a fresh request
    assert router.rebalance() == 2


def test_rebalance_migrates_stolen_tail_resume_record():
    """A stolen TAIL request that happens to be a (non-head) recompute
    carries its resume record to the thief, so its completion still
    splices prior output."""
    from repro.serve.scheduler import _Resume
    spec, params, cfg, engines = _engines(2)
    router = PrefixRouter(engines, page_size=cfg.page_size)
    reqs = _reqs(3, seed=14)
    for r in reqs:
        engines[0].submit(r)
    tail_uid = reqs[-1].uid
    engines[0]._resume[tail_uid] = _Resume(7, [9])
    assert not engines[0].head_is_resume     # resume sits at the tail
    assert router.rebalance() >= 1
    assert tail_uid in engines[1]._resume    # record followed the steal
    assert engines[1]._resume[tail_uid].prior == [9]
    assert tail_uid not in engines[0]._resume


# ---------------------------------------------------------------------------
# ServeSLO policy arithmetic (pure, engine-free)
# ---------------------------------------------------------------------------

def test_serve_slo_predict_and_violate():
    from repro.serve.router import ServeSLO
    slo = ServeSLO(ttft_slo_s=2.0, predicted_itl_s=0.1,
                   predicted_ttft_s=0.5, tokens_per_iteration=10.0)
    # drain model: C tokens retire at tokens_per_iteration per itl
    assert slo.predict_ttft(0.0) == pytest.approx(0.5)
    assert slo.predict_ttft(100.0) == pytest.approx(100 / 10 * 0.1 + 0.5)
    assert not slo.violates(100.0)           # 1.5s < 2s budget
    assert slo.violates(200.0)               # 2.5s > 2s budget
    # capacity check: worst-iteration ITL over budget sheds at ANY load
    tight = ServeSLO(ttft_slo_s=2.0, itl_slo_s=0.05,
                     predicted_itl_worst_s=0.08)
    assert tight.violates(0.0)


def test_serve_slo_from_model_distils_prediction():
    from repro.configs import ASSIGNED
    from repro.core import analytical, hardware, precision as prec_mod
    from repro.core.latency import predict_serve_throughput
    from repro.serve.router import ServeSLO
    spec = ASSIGNED["granite-3-8b"].scaled_down()
    plan = analytical.PagedCachePlan(page_size=16, num_pages=129,
                                     page_bytes=4096.0,
                                     bytes_per_token=256.0)
    hw, prec = hardware.get("rpi5"), prec_mod.get("fp32")
    kw = dict(slots=8, avg_prompt=128.0, avg_new=32.0)
    pred = predict_serve_throughput(spec, hw, prec, plan, **kw)
    slo = ServeSLO.from_model(spec, hw, prec, plan, ttft_slo_s=1.0, **kw)
    assert slo.predicted_itl_s == pred["predicted_itl_s"]
    assert slo.predicted_itl_worst_s == pred["predicted_itl_worst_s"]
    assert slo.predicted_ttft_s == pred["predicted_ttft_s"]
    assert slo.tokens_per_iteration == 8 + 128.0   # slots + mean prompt
    chunked = ServeSLO.from_model(spec, hw, prec, plan, ttft_slo_s=1.0,
                                  chunk_tokens=64, **kw)
    assert chunked.tokens_per_iteration == 8 + 64.0
    # an over-capacity fleet (worst ITL over budget) sheds everything
    assert ServeSLO.from_model(
        spec, hw, prec, plan, ttft_slo_s=1.0,
        itl_slo_s=pred["predicted_itl_worst_s"] / 2, **kw).violates(0.0)

"""Spans of the serve stack (``serve/spans.py``): the recorder's self-time
arithmetic, and the counters a small engine run leaves in ``stats`` —
call counts equal to what happened, self times that sum to the
``engine.step`` spans' duration, and a span stack that unwinds whatever
a step raised."""
import time

import numpy as np
import pytest

from repro.serve.faults import ChaosBackend, ChaosSchedule, ReplicaFault
from repro.serve.spans import NAMES, NO_SPANS, SpanRecorder, host_split_ms


def _ticks(*values):
    it = iter(values)
    return lambda: next(it)


def test_self_time_excludes_children():
    stats = {}
    rec = SpanRecorder(stats)
    # outer 0..100 ns holds grow 10..40 and finish 50..60, which holds
    # page_table 52..57
    rec.clock = _ticks(0, 10, 40, 50, 52, 57, 60, 100)
    with rec.span("engine.step"):
        with rec.span("engine.grow"):
            pass
        with rec.span("engine.finish"):
            assert rec.depth == 2
            with rec.span("backend.page_table"):
                pass
    assert rec.depth == 0
    got = {k: round(v * 1e9) for k, v in stats.items()
           if k.startswith("self_s:") and v}
    assert got == {"self_s:engine.step": 60, "self_s:engine.grow": 30,
                   "self_s:engine.finish": 5, "self_s:backend.page_table": 5}
    assert sum(got.values()) == 100
    assert stats["calls:engine.step"] == stats["calls:engine.grow"] == 1
    assert stats["calls:engine.decode"] == 0


def test_stack_unwinds_on_exception():
    stats = {}
    rec = SpanRecorder(stats)
    with pytest.raises(KeyError):
        with rec.span("engine.step"):
            with rec.span("engine.admit", uid=3):
                raise KeyError("backend died")
    assert rec.depth == 0
    assert stats["calls:engine.admit"] == stats["calls:engine.step"] == 1
    assert stats["self_s:engine.admit"] >= 0
    with NO_SPANS.span("backend.prefill"):
        pass


def _model():
    import jax
    from repro.configs import ASSIGNED
    from repro.models import lm
    spec = ASSIGNED["granite-3-8b"].scaled_down(layers=2, width=64,
                                                vocab=128)
    return spec, lm.init(jax.random.PRNGKey(0), spec)


class _Counting:
    """Forwards to a backend and counts its device calls."""

    COUNTED = ("decode", "write_block_entries", "release_slot", "copy_page",
               "admit_full", "admit_prefix", "prefill_chunk")

    def __init__(self, inner):
        self._inner = inner
        self.n = dict.fromkeys(self.COUNTED, 0)

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name not in self.COUNTED:
            return attr

        def call(*a, **kw):
            self.n[name] += 1
            return attr(*a, **kw)
        return call


def _engine(spec, params, backend=None, **cfg):
    from repro.serve.scheduler import ContinuousBatchingEngine, SchedulerConfig
    kw = dict(max_slots=2, page_size=8, max_seq=64, num_pages=14)
    kw.update(cfg)
    sched = SchedulerConfig(**kw)
    if backend is not None:
        from repro.serve.backend import SingleDeviceBackend
        backend = backend(SingleDeviceBackend(params, spec, sched))
    return ContinuousBatchingEngine(params, spec, sched, backend=backend)


def _requests(n, seed=0):
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(seed)
    # the first prompt, 12 tokens, ends mid-page: the later prompts
    # extend it and copy its partial page (copy-on-write)
    shared = rng.integers(0, 128, size=12).astype(np.int32)
    return [Request(i, np.concatenate(
        [shared, rng.integers(0, 128, size=3 * i).astype(np.int32)]),
        int(rng.integers(4, 14))) for i in range(n)]


def test_engine_counters_match_what_happened():
    spec, params = _model()
    eng = _engine(spec, params, backend=_Counting)
    counted = eng.backend
    reads = []                     # (ns, open spans) of every clock read

    def clock():
        t = time.perf_counter_ns()
        reads.append((t, eng.spans.depth))
        return t
    eng.spans.clock = clock
    for r in _requests(6):
        eng.submit(r)
    steps, wall = 0, 0.0
    while eng.queue or eng.num_active:
        t0 = time.perf_counter()
        eng.step()
        wall += time.perf_counter() - t0
        steps += 1
        assert eng.spans.depth == 0
    st, n = eng.stats, counted.n
    assert st["calls:engine.step"] == steps
    assert st["calls:backend.decode_dispatch"] == n["decode"]
    assert st["calls:backend.decode_wait"] == n["decode"]
    assert st["calls:engine.decode"] >= n["decode"] > 0
    assert st["calls:backend.page_table"] == (
        n["write_block_entries"] + n["release_slot"] + n["copy_page"])
    assert n["copy_page"] > 0 and n["write_block_entries"] > 0
    assert st["calls:backend.prefill"] == (
        n["admit_full"] + n["admit_prefix"] + n["prefill_chunk"])
    assert st["calls:engine.admit"] == st["admitted"] == 6
    assert st["calls:backend.swap"] == 0
    own = {k: v for k, v in st.items() if k.startswith("self_s:")}
    assert set(own) == {"self_s:" + name for name in NAMES}
    assert all(v >= 0 for v in own.values())
    assert sum(own.values()) <= wall
    # the engine.step spans are the outermost: their clock reads are
    # the ones taken with one span open, in (enter, exit) pairs
    outer = [t for t, depth in reads if depth == 1]
    assert len(outer) == 2 * steps
    total_ns = sum(b - a for a, b in zip(outer[::2], outer[1::2]))
    assert sum(own.values()) == pytest.approx(total_ns * 1e-9, rel=1e-12)
    split = host_split_ms(st)
    assert split["engine_self_ms"] == pytest.approx(
        sum(v for k, v in own.items() if k.startswith("self_s:engine."))
        / steps * 1e3)
    assert all(v >= 0 for v in split.values())


def test_spans_unwind_after_a_crash_mid_decode_and_mid_admission():
    spec, params = _model()
    eng = _engine(spec, params)
    chaos = ChaosBackend(eng.backend, ChaosSchedule(crash_at=frozenset({0})))
    eng.backend = chaos
    reqs = _requests(3, seed=1)
    eng.submit(reqs[0])
    with pytest.raises(ReplicaFault):
        eng.step()                     # admits, then the decode crashes
    assert eng.spans.depth == 0
    assert eng.stats["calls:engine.decode"] == 1
    assert eng.stats["calls:backend.decode_dispatch"] == 0

    eng = _engine(spec, params)
    eng.backend = chaos                # dead: the admission's prefill raises
    eng.submit(reqs[1])
    with pytest.raises(ReplicaFault):
        eng.step()
    assert eng.spans.depth == 0
    assert eng.stats["calls:engine.admit"] == 1
    assert eng.stats["calls:engine.step"] == 1
    assert [r.uid for r in eng.queue] == [reqs[1].uid]   # zero lost


def test_spans_land_in_a_profiler_trace(tmp_path):
    import glob

    import jax
    spec, params = _model()
    eng = _engine(spec, params)
    for r in _requests(3, seed=2):
        eng.submit(r)
    eng.step()                         # admits two; the third waits
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.queue or eng.num_active:
            eng.step()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [ev for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    names = {ev.name for ev in events}
    assert {"engine.step", "engine.grow", "engine.decode", "engine.finish",
            "backend.decode_dispatch", "backend.decode_wait",
            "backend.page_table"} <= names
    steps = [ev for ev in events if ev.name == "engine.step"]
    assert len(steps) == eng.stats["calls:engine.step"] - 1
    admit, = [ev for ev in events if ev.name == "engine.admit"]
    assert dict(admit.stats)["uid"] == 2

"""The readers of the program's span counters on a CPU window of a small
cell, the names the program's spans take, and the split of idle gaps
over the innermost spans that cover them."""
import lzma
import math
import shutil
import time
from types import SimpleNamespace

import jax
import pytest

from chipbench import drive, program_spans, run, trace_reduce
from chipbench import traffic as traffic_mod
from chipbench.adapters.dense_gqa import program_params, program_spec
from chipbench.cell import reader
from chipbench.tests.faults import HERE, tiny_cell
from chipbench.weights import base_key

SEED = 2**33 + 5
READERS = ("engine_self_ms", "page_table_ms", "decode_dispatch_ms")


@pytest.fixture(scope="module")
def served():
    """A 3 s window of a small open-loop cell, with every read of the
    spans' clock logged beside the number of spans open then."""
    from repro.serve.backend import make_backend
    from repro.serve.scheduler import ContinuousBatchingEngine
    cell = tiny_cell("tiny-gqa", "tiny-open")
    spec = program_spec(cell.config)
    params = program_params(cell.config, spec, base_key(SEED))
    sched = run.scheduler_config(cell, params, jax.devices()[0])
    inner = make_backend(params, spec, sched, devices=1)
    backend = drive.TimedBackend(inner, sched.max_slots)
    engine = ContinuousBatchingEngine(params, spec, sched, backend=backend)
    reads = []

    def clock():
        t = time.perf_counter_ns()
        reads.append((t, engine.spans.depth))
        return t
    engine.spans.clock = clock
    w = drive.run_window(engine, backend, traffic_mod.Traffic(
        cell.mix, spec.vocab_size, SEED, 3.0), 3.0, drive.CompileLog())
    return SimpleNamespace(window=w, stats=dict(engine.stats), reads=reads)


def test_program_span_names_are_not_the_benchmarks():
    from repro.serve.spans import NAMES
    ours = set(trace_reduce.SPAN_NAMES) | set(drive.TimedBackend.TIMED)
    assert "step" in ours
    assert not set(NAMES) & ours
    assert set(program_spans.GAP_SPANS) == set(NAMES) | ours


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_window(served, name):
    v = reader(name)(SimpleNamespace(window=served.window))
    assert v is not None and math.isfinite(v) and v >= 0


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_span_counters(name):
    w = SimpleNamespace(stats0={"iterations": 3}, stats1={"iterations": 9})
    assert reader(name)(SimpleNamespace(window=w)) is None


def test_self_times_sum_to_the_engine_steps(served):
    own = sum(v for k, v in served.stats.items() if k.startswith("self_s:"))
    # engine.step spans are the outermost: their clock reads are the
    # ones taken with one span open, in (enter, exit) pairs
    outer = [t for t, depth in served.reads if depth == 1]
    steps = served.stats["calls:engine.step"]
    assert len(outer) == 2 * steps == 2 * len(served.window.steps)
    total = sum(b - a for a, b in zip(outer[::2], outer[1::2])) * 1e-9
    assert own == pytest.approx(total, rel=1e-12)
    assert total <= sum(b - a for a, b in served.window.steps)


def test_a_gap_is_split_over_the_innermost_spans():
    spans = [(0, 100, "step"), (5, 95, "engine.step"),
             (10, 60, "engine.decode"), (12, 20, "decode"),
             (12, 15, "backend.decode_dispatch"),
             (20, 40, "backend.decode_wait"), (60, 80, "engine.finish"),
             (62, 64, "backend.page_table"), (200, 210, "unrelated")]
    gaps = [(30, 70), (85, 110), (120, 130)]
    got = {k: round(v * 1e9, 6) for k, v in
           program_spans.split_gaps(gaps, spans).items()}
    assert got == {"backend.decode_wait": 10, "engine.decode": 20,
                   "engine.finish": 8, "backend.page_table": 2,
                   "engine.step": 10, "scheduler": 5, "between steps": 20}
    assert sum(got.values()) == sum(b - a for a, b in gaps)


def test_recorded_trace_keeps_its_idle_total(tmp_path):
    """On the recorded chip trace, which has the harness's spans only,
    the split keeps the idle total and the harness's labels."""
    path = tmp_path / "t.xplane.pb"
    with lzma.open(HERE / "data" / "reason-decode.xplane.pb.xz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    labels = program_spans.idle_by_span(str(path))
    summary = trace_reduce.reduce(str(path))
    assert sum(labels.values()) == pytest.approx(
        sum(s for _, s in summary.gaps), rel=1e-9)
    assert set(labels) <= set(dict(summary.gaps)) | {"scheduler", "decode"}
    assert labels["scheduler"] > labels["decode"] > 0

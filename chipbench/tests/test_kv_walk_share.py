"""``kv_walk_share`` on a CPU window of a small cell: the share of the
block table the paged kernel streams, read from the engine's counters,
and nothing on a program that keeps no such counters."""
from types import SimpleNamespace

import jax

from chipbench import drive, run
from chipbench import traffic as traffic_mod
from chipbench.adapters.dense_gqa import program_params, program_spec
from chipbench.cell import reader
from chipbench.tests.faults import tiny_cell
from chipbench.weights import base_key

SEED = 2**33 + 7


def test_reads_the_share_of_the_table_streamed():
    from repro.serve.backend import make_backend
    from repro.serve.scheduler import ContinuousBatchingEngine
    cell = tiny_cell("tiny-gqa", "tiny-open")
    spec = program_spec(cell.config)
    params = program_params(cell.config, spec, base_key(SEED))
    sched = run.scheduler_config(cell, params, jax.devices()[0])
    inner = make_backend(params, spec, sched, devices=1)
    backend = drive.TimedBackend(inner, sched.max_slots)
    engine = ContinuousBatchingEngine(params, spec, sched, backend=backend)
    w = drive.run_window(engine, backend, traffic_mod.Traffic(
        cell.mix, spec.vocab_size, SEED, 2.0), 2.0, drive.CompileLog())
    v = reader("kv_walk_share")(SimpleNamespace(window=w))
    assert v is not None and 0 < v < 100
    streamed = w.stats1["kv_pages_streamed"] - w.stats0["kv_pages_streamed"]
    table = w.stats1["kv_pages_table"] - w.stats0["kv_pages_table"]
    assert v == 100.0 * streamed / table


def test_is_silent_without_the_counters():
    w = SimpleNamespace(stats0={"iterations": 3}, stats1={"iterations": 9})
    assert reader("kv_walk_share")(SimpleNamespace(window=w)) is None

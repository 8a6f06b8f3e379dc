#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

In order: load the cell's configuration and traffic by name; fail
(non-zero exit, no result) unless JAX finds enough TPU chips; turn on
the compile cache at ``<checkout>/.jax_cache``; make the weights on the
device from the seed; warm up exactly the shapes the mix can produce;
serve the mix for ``--seconds`` through ``ContinuousBatchingEngine``
over ``make_backend(..., devices=1)`` (the path of
``launch/serve.py --engine paged``); read the cell's metrics
(``--trace 0``: its end-to-end metrics; ``--trace 1``: its per-layer
metrics, from a profiler trace of part of the window); check the served
tokens against the plain reference; print the result as the last line
of standard output, and each number compared beside its limit as the
last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import check, drive, traffic as traffic_mod  # noqa: E402
from chipbench.cell import Cell, family, load_cell, reader  # noqa: E402
from chipbench.peaks import peaks  # noqa: E402

TRACE_DIR = ROOT / ".chipbench_trace"
#: Weight dtypes as the analytical model's precision names.
PRECISION = {"bfloat16": "bf16", "float32": "fp32"}
#: The traced part of a ``--trace 1`` window: from this share of it,
#: for at most TRACE_MAX_S seconds or TRACE_SHARE of the window.
TRACE_FROM, TRACE_SHARE, TRACE_MAX_S = 0.3, 0.4, 6.0


class NoChip(RuntimeError):
    pass


def log(*parts):
    print(*parts, flush=True)


def chips(need: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < need:
        raise NoChip(f"needs {need} TPU chip(s); JAX found {len(devices)} "
                     f"{devices[0].platform} device(s)")
    return devices


def enable_cache():
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    # every program, however quick to compile, so a warm run compiles
    # nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def scheduler_config(cell: Cell, params, device):
    from repro.serve import paged_cache as pc
    from repro.serve.scheduler import SchedulerConfig
    prog = cell.config["program"]
    budget = pc.device_kv_budget(params, device)
    num_pages = None
    if budget is None:               # a backend that reports no limit
        num_pages = prog["slots"] * pc.pages_needed(
            prog["max_seq"], prog["page_size"]) + 1
    return SchedulerConfig(max_slots=prog["slots"],
                           page_size=prog["page_size"],
                           max_seq=prog["max_seq"], num_pages=num_pages,
                           kv_budget_bytes=budget,
                           cache_dtype=prog["cache_dtype"])


def warm_up(backend, sched, mix: dict) -> int:
    """Run every program the window can call once, at every shape the
    mix can produce: cold prefill per prompt bucket, prefix-hit prefill
    per suffix bucket, the decode step, block-table writes of 1 to
    ``max_slots`` entries, a page copy and a slot release.  Returns the
    number of calls."""
    from repro.serve import paged_cache as pc
    from repro.serve.scheduler import _bucket, _pow2_pages
    b = traffic_mod.request_bounds(mix)
    page, max_seq, B = sched.page_size, sched.max_seq, sched.max_slots
    row_len = backend.layout.slots_pages(max_seq)
    row = np.arange(1, row_len + 1, dtype=np.int32)
    calls = 0
    cold = sorted({_bucket(n, page, max_seq)
                   for n in range(b["prompt_min"], b["admit_max"] + 1)})
    for width in cold:
        backend.admit_full(np.zeros((1, width), np.int32), 0,
                           min(width, b["admit_max"]), row)
        calls += 1
    if b["prefix"]:
        npp = _pow2_pages(pc.pages_needed(b["prefix"], page), row_len)
        for width in sorted({_bucket(n, page, max_seq) for n in
                             range(b["suffix_min"], b["suffix_max"] + 1)}):
            backend.admit_prefix(np.zeros((1, width), np.int32), 0,
                                 b["prefix"], min(width, b["suffix_max"]),
                                 row, n_prefix_pages=npp)
            calls += 1
    backend.decode(np.zeros((B, 1), np.int32), np.ones((B,), np.int32))
    for n in range(1, B + 1):
        backend.write_block_entries([(i, 0, pc.NULL_PAGE)
                                     for i in range(n)])
    backend.copy_page(1, 2)
    for i in range(B):
        backend.release_slot(i)
    return calls + 2 + B + B


def build(cell: Cell, seed: int, device) -> SimpleNamespace:
    """One run's set-up: the weights made on the device from the seed,
    the KV pool, the timed backend over it, a warm-up of every shape the
    mix can produce, and the engine.  The set-up's objects are then
    frozen out of the garbage collector's scans, as a long-running
    server's are, so a collection in the window scans only what the
    window made.  ``marks`` holds the ``perf_counter()`` at the end of
    each stage."""
    import jax
    from chipbench.weights import base_key
    from repro.serve.backend import make_backend
    from repro.serve.scheduler import ContinuousBatchingEngine
    adapter = family("adapters", cell.family)
    spec = adapter.program_spec(cell.config)
    key = base_key(seed)
    params = adapter.program_params(cell.config, spec, key)
    jax.block_until_ready(params)
    marks = {"weights": time.perf_counter()}
    sched = scheduler_config(cell, params, device)
    inner = make_backend(params, spec, sched, devices=1)
    jax.block_until_ready(inner.cache)
    marks["pool"] = time.perf_counter()
    backend = drive.TimedBackend(inner, sched.max_slots)
    warm_calls = warm_up(inner, sched, cell.mix)
    marks["warm_up"] = time.perf_counter()
    engine = ContinuousBatchingEngine(params, spec, sched, backend=backend)
    gc.collect()
    gc.freeze()
    return SimpleNamespace(key=key, spec=spec, params=params, sched=sched,
                           inner=inner, backend=backend, engine=engine,
                           warm_calls=warm_calls, marks=marks)


def analytical(spec, plan, cell: Cell) -> dict:
    """The paper's analytical model at the cell's slots, mean prompt and
    mean output (``core.latency.predict_serve_throughput``)."""
    from repro.core import precision
    from repro.core.hardware import TPU_V5E
    from repro.core.latency import predict_serve_throughput
    mix = cell.mix
    pre = mix.get("shared_prefix")
    prompt = sum(traffic_mod.quantiles(s, 1000).mean()
                 for s in mix["prompt"]) + (pre["tokens"] if pre else 0)
    new = traffic_mod.quantiles(mix["output"], 1000).mean()
    slots = cell.config["program"]["slots"]
    pred = predict_serve_throughput(
        spec, TPU_V5E,
        precision.get(PRECISION[cell.config["program"]["weight_dtype"]]),
        plan, slots=slots, avg_prompt=float(prompt), avg_new=float(new),
        prefix_hit_rate=(pre["tokens"] / prompt if pre else 0.0))
    return {k: pred[k] for k in ("continuous_tokens_per_s",
                                 "predicted_itl_s", "predicted_ttft_s")}


class Tracer:
    """Starts the profiler at ``TRACE_FROM`` of the window and stops it
    after its span; records both times on the window's clock."""

    def __init__(self, backend, seconds: float):
        self.backend = backend
        self.t_on = TRACE_FROM * seconds
        self.t_off = self.t_on + min(TRACE_MAX_S, TRACE_SHARE * seconds)
        self.t0 = self.t1 = None
        self._origin = 0.0           # perf_counter() at the window's start

    def __call__(self, now: float):
        import jax
        if self.t0 is None and now >= self.t_on:
            called = time.perf_counter()
            self._origin = called - now
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self.backend.trace = True
            self.t0 = time.perf_counter() - self._origin
        elif self.t0 is not None and self.t1 is None and now >= self.t_off:
            self.stop()

    def stop(self):
        import jax
        self.backend.trace = False
        self.t1 = time.perf_counter() - self._origin
        jax.profiler.stop_trace()


def step_extremes(window) -> dict:
    """The longest engine step inside the window, and the longest wait
    between two steps there: where a stall of the host shows."""
    inside = [(a, b) for a, b in window.steps
              if 0 <= a and b <= window.seconds]
    return {"longest_step_s": round(max((b - a for a, b in inside),
                                        default=0.0), 4),
            "longest_between_s": round(max(
                (c - b for (_, b), (c, _) in zip(inside, inside[1:])),
                default=0.0), 4)}


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def main(argv=None, *, cell: Cell = None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else under /tmp
    cell = cell or load_cell(args.workload)
    b = traffic_mod.request_bounds(cell.mix)
    if b["prompt_max"] + b["output_max"] > cell.config["program"]["max_seq"]:
        raise ValueError(f"{cell.name}: the mix's longest request does not "
                         "fit max_seq")

    import jax
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        if require_tpu:
            print(f"chipbench: {e}", file=sys.stderr)
            return 2
        devices = jax.devices()
    device = devices[0]
    marks = {"chip": time.perf_counter() - T_START}
    enable_cache()
    compiles = drive.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    built = build(cell, args.seed, device)
    marks.update({k: v - T_START for k, v in built.marks.items()})
    key, spec, params, sched = (built.key, built.spec, built.params,
                                built.sched)
    inner, backend, engine = built.inner, built.backend, built.engine
    warm_calls = built.warm_calls
    del built
    log("setup", json.dumps({
        "cell": cell.name, "device_kind": device.device_kind,
        "pages": inner.layout.num_pages,
        "pool_bytes": int(sum(x.nbytes for x in jax.tree_util.tree_leaves(
            inner.cache["groups"]))),
        "param_bytes": int(sum(x.nbytes for x in
                               jax.tree_util.tree_leaves(params))),
        "kv_budget_bytes": sched.kv_budget_bytes,
        "warm_calls": warm_calls,
        "compiles": compiles.count, "compile_s": round(compiles.seconds, 3),
        "marks_s": {k: round(v, 3) for k, v in marks.items()}}))
    log("analytical", json.dumps(analytical(spec, inner.plan, cell)))
    setup_s = time.perf_counter() - T_START

    mix = traffic_mod.Traffic(cell.mix, spec.vocab_size, args.seed,
                              args.seconds)
    tracer = Tracer(backend, args.seconds) if args.trace else None
    gcs = drive.GcLog()
    gc.callbacks.append(gcs)
    window = drive.run_window(engine, backend, mix, args.seconds, compiles,
                              on_time=tracer)
    gc.callbacks.remove(gcs)
    if tracer is not None and tracer.t0 is not None and tracer.t1 is None:
        tracer.stop()
    stats = device.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log("window", json.dumps({
        "compiles": window.compiles, "late_s": round(window.late_s, 4),
        "drained_s": round(window.drained_s, 3),
        "requests": len(window.requests),
        "completed": len(window.completions),
        "steps": len(window.steps), **step_extremes(window),
        "gc_runs": gcs.runs, "gc_s": round(gcs.seconds, 4),
        "gc_longest_s": round(gcs.longest, 4)}))

    trace_summary = None
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    breakdown = None
    if tracer is not None:
        from chipbench import trace_reduce
        trace_summary = trace_reduce.reduce(
            trace_reduce.find_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        dev["busy_s"] = trace_summary.busy_s
        dev["window_s"] = tracer.t1 - tracer.t0
        ops = sorted(trace_summary.op_s.items(), key=lambda kv: -kv[1])
        breakdown = {"device_ops": [[n, s] for n, s in ops[:10]],
                     "idle_gaps": [[n, s] for n, s in
                                   trace_summary.gaps[:10]]}
    # what a metric reader gets: the cell, its published shapes, the
    # window, set-up seconds, the chip's peaks, and in a traced run the
    # trace summary and the traced span of the window
    rec = SimpleNamespace(
        cell=cell, dims=family("reference", cell.family).dims(cell.config),
        window=window, setup_s=setup_s,
        peaks=peaks(device.device_kind) if args.trace else None,
        trace=trace_summary,
        trace_t0=tracer.t0 if tracer else 0.0,
        trace_t1=tracer.t1 if tracer else 0.0)
    metrics = {}
    for m in cell.metrics(bool(args.trace)):
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    from chipbench.window_stats import failed
    in_win = window.in_window()
    n_failed = sum(failed(window, u) for u in in_win)

    # free the program's state before the reference runs
    del engine, backend, inner, params
    if tracer is not None:
        tracer.backend = None
    gc.unfreeze()
    gc.collect()
    t_check = time.perf_counter()
    uids = check.sample(window, args.seed)
    found = check.readings(cell, key, window, uids)
    correct, checks = check.judge(cell.limits, found["program"], len(uids))
    now = time.perf_counter()
    log("checked", json.dumps({"requests": uids, "tokens": found["tokens"],
                               **found["program"],
                               "window_compiles": window.compiles,
                               "check_s": round(now - t_check, 3),
                               "run_s": round(now - T_START, 3)}))
    for name, c in checks.items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(result_line(correct, len(in_win), n_failed, metrics, dev, checks,
                      breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

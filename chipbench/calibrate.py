#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--control-seeds 1,2,3] [--rates 2,3,4 --sweep-seed 1]

For each seed: weights from the seed, the cell's mix served for
``--seconds`` through the timed path, the sample that a run checks, and
its numbers against the reference (``check.gaps``); on the control
seeds also the control's numbers (the reference at float8 in the
program's place).  With ``--rates``, an open-loop mix is served once per
rate instead (the knee sweep), and the backlog is read: how many of the
window's requests got a first token before its close.  One JSON line per
seed or rate, with ``correct`` as a run would decide it and, on a
control seed, ``control_correct``: the control judged by the cell's own
limits, which has to read false.  Set-up is ``run.build``, the objects
a benchmark run drives.  The benchmark's own runs never run this.
"""
import time

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import check, drive, run, traffic as traffic_mod  # noqa: E402
from chipbench.cell import load_cell  # noqa: E402
from chipbench.window_stats import (itl_gaps, percentile,  # noqa: E402
                                    tokens_in_window, ttfts, window_spans)


def _ints(s):
    return [int(x) for x in s.split(",")] if s else []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--sweep-seed", type=int, default=None)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    import jax
    device = run.chips(cell.chips)[0]
    run.enable_cache()
    compiles = drive.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    seeds = _ints(args.seeds)
    ctrl = set(_ints(args.control_seeds))
    rates = [float(r) for r in args.rates.split(",")] if args.rates else []
    sweep_seed = args.sweep_seed if args.sweep_seed is not None else (
        seeds[0] if seeds else 1)
    jobs = [(s, None) for s in seeds] + [(sweep_seed, r) for r in rates]
    for seed, rate in jobs:
        t0 = time.perf_counter()
        built = run.build(cell, seed, device)
        key, engine = built.key, built.engine
        setup_s = time.perf_counter() - t0
        mix = dict(cell.mix, rate=rate) if rate else cell.mix
        w = drive.run_window(engine, built.backend,
                             traffic_mod.Traffic(mix, built.spec.vocab_size,
                                                 seed, args.seconds),
                             args.seconds, compiles)
        out = {"seed": seed, "setup_s": round(setup_s, 2),
               "served_s": round(time.perf_counter() - t0 - setup_s, 2),
               "window_compiles": w.compiles}
        if rate:
            # a growing backlog shows as later arrivals waiting longer
            inw = w.in_window()
            t = ttfts(w)
            half = [x for u, x in zip(inw, t)
                    if w.submitted[u] < w.seconds / 2]
            rest = [x for u, x in zip(inw, t)
                    if w.submitted[u] >= w.seconds / 2]
            out.update(rate=rate, arrivals=len(inw), drained_s=w.drained_s,
                       queue_at_close=len(engine.queue),
                       ttft_p50_ms=percentile(t, 50) * 1e3,
                       ttft_p90_ms=percentile(t, 90) * 1e3,
                       ttft_p50_first_half_ms=percentile(half, 50) * 1e3,
                       ttft_p50_second_half_ms=percentile(rest, 50) * 1e3,
                       itl_p95_ms=percentile(itl_gaps(w), 95) * 1e3,
                       decode_slots_mean=float(np.mean(
                           [s.tokens for s in window_spans(w, ("decode",))]
                           or [0])),
                       tokens_per_s=tokens_in_window(w) / w.seconds)
        del built, engine
        gc.unfreeze()
        gc.collect()
        if not rate:
            uids = check.sample(w, seed)
            found = check.readings(cell, key, w, uids,
                                   control=seed in ctrl)
            out.update(found["program"], tokens=found["tokens"])
            out["correct"] = check.judge(cell.limits, found["program"],
                                         len(uids))[0]
            if found["control"]:
                out.update({f"control_{k}": v
                            for k, v in found["control"].items()})
                out["control_correct"] = check.judge(
                    cell.limits, found["control"], len(uids))[0]
            out["requests"] = len(uids)
            out["checked_s"] = round(time.perf_counter() - t0 - setup_s
                                     - out["served_s"], 2)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

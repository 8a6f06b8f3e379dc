#!/usr/bin/env python3
"""Bring-up smoke: the continuous-batching paged serve engine on a TPU
at the full published width of TinyLlama-1.1B.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # tp=4 vs one chip, dp=4 replicas

One chip: seeded random weights are served through
``ContinuousBatchingEngine`` + ``make_backend`` (the path of
``launch/serve.py --engine paged``), once with fp32 pages and once with
int4 pages.  Sixteen requests with 129-1024-token prompts, half of them
behind one shared 256-token prefix, so both cold (``admit_full``) and
prefix-hit (``admit_prefix``) admission run; the KV pool is sized from
the device memory the weights leave free.  The run fails (non-zero
exit, no ``ok`` line) unless

* JAX finds a TPU,
* the backend's lowered decode step holds the Pallas paged-attention
  kernel (``tpu_custom_call``), and that op matches its jnp reference
  on random pools within ``KERNEL_ATOL``,
* every completion is ``ok`` and the prefix cache was hit,
* the paged prefill + decode logits of one prompt stay within
  ``PROBE_RTOL`` of a plain float32 contiguous reference
  (``lm.prefill`` + ``lm.decode_step`` at "highest" matmul precision),
* every engine token is a near-argmax of that reference given the same
  context (``repro.serve.parity.greedy_gap`` within ``GAP_RTOL``).

``--four-chips`` runs only the multi-chip path and what it is compared
with: a one-chip engine, a tp=4 engine (KV heads and weights sharded;
at the default matmul precision each stream is held to the float32
reference by ``greedy_gap``, and at "highest" precision to the one-chip
stream within the 0.9 matching-prefix band), and four
one-chip replicas behind ``PrefixRouter``, each on its own device, whose
streams must keep to the one-chip ones within that band.  Any router
step fault fails the run.

Lines before the last are bring-up readings (sizes, compile counts,
wall-clock seconds of one cold run), not benchmark numbers.  The last
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.quant.quantize import (lane_major_scales, pack_int4,  # noqa: E402
                                  quantize_kv_int4)
from repro.serve import paged_cache as pc  # noqa: E402
from repro.serve.backend import make_backend  # noqa: E402
from repro.serve.parity import greedy_gap, token_match_fraction  # noqa: E402
from repro.serve.router import PrefixRouter, make_replicas  # noqa: E402
from repro.serve.scheduler import (ContinuousBatchingEngine,  # noqa: E402
                                   Request, SchedulerConfig)

ARCH = "tinyllama-1.1b"
SEED = 0
PAGE = 16
SLOTS = 8
MAX_SEQ = 2048                 # TinyLlama's published context length
PROBE_STEPS = 4
# Paged logits vs the float32 contiguous reference, both at "highest"
# matmul precision: max |paged - ref| / max |ref| over the prefill and
# PROBE_STEPS decode positions of one prompt.  int4 pages cost ~0.2-0.3
# at full width with random weights (0.18-0.20 on the CPU at 2-6
# layers, 0.30 on a v5e at 22), while a wrong context moves the logits
# by ~1.3-1.5; the int4 bound sits between.
PROBE_RTOL = {"fp32": 1e-4, "int4": 0.5}
# Engine tokens (default matmul precision) vs that reference under
# teacher forcing: parity.greedy_gap of every completion.
GAP_RTOL = {"fp32": 0.05, "int4": 0.4}
# Paged-attention op vs its jnp reference at "highest" precision on
# random pools: max |kernel - reference|.
KERNEL_ATOL = 1e-4
BAND = 0.9                     # tolerance.py's matching-prefix band


class CompileLog:
    """Counts XLA backend compiles and their seconds (a listener on
    JAX's monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def reading(tag: str, **kv) -> None:
    print(f"bring-up reading [{tag}] "
          + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def tree_bytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def dir_bytes(path) -> int:
    return sum(f.stat().st_size for f in Path(path).rglob("*")
               if f.is_file())


def peak_bytes(device) -> str:
    stats = device.memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def init_params(spec, seed: int = SEED):
    return jax.jit(lm.init, static_argnums=(1, 2))(
        jax.random.PRNGKey(seed), spec, jnp.float32)


def workload(vocab: int, *, n: int = 16, seed: int = SEED,
             prefix: int = 256, short=(129, 256), long=(513, 1024),
             new=(32, 64)):
    """Seeded requests: even uids are one shared ``prefix``-token system
    prompt plus a ``short``-length question, odd uids alternate cold
    ``short`` and ``long`` prompts.  With the defaults, cold prompts
    fill three prefill buckets (256, 512 and 1024 tokens) and prefix
    hits one suffix bucket."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, vocab, size=prefix)
    reqs = []
    for i in range(n):
        lo, hi = long if i % 4 == 3 else short
        body = rng.integers(0, vocab, size=int(rng.integers(lo, hi + 1)))
        if i % 2 == 0:
            body = np.concatenate([sys_prompt, body])
        reqs.append(Request(i, body.astype(np.int32),
                            int(rng.integers(new[0], new[1] + 1))))
    return reqs


def scheduler_config(cache_dtype: str, kv_budget_bytes: float, *,
                     slots: int = SLOTS,
                     max_seq: int = MAX_SEQ) -> SchedulerConfig:
    return SchedulerConfig(max_slots=slots, page_size=PAGE, max_seq=max_seq,
                           kv_budget_bytes=kv_budget_bytes,
                           cache_dtype=cache_dtype)


def decode_has_kernel(backend) -> bool:
    """Whether the backend's lowered one-token decode step calls a
    Mosaic (Pallas TPU) kernel — the paged-attention kernel is the only
    one on that path."""
    step = backend._decode
    B = backend.cfg.max_slots
    lowered = step.func.lower(backend.params, backend.cache,
                              jnp.zeros((B, 1), jnp.int32),
                              jnp.zeros((B,), jnp.int32), **step.keywords)
    return "tpu_custom_call" in lowered.as_text()


def kernel_vs_reference(spec, cache_dtype: str, *, slots: int = SLOTS,
                        pages_per_slot: int = 8) -> float:
    """Largest |paged attention op - its jnp reference| on random pools
    at the spec's decode widths, ragged contexts, for one-token and
    4-token verify windows — the compiled kernel on a TPU."""
    rng = np.random.default_rng(SEED)
    H, KV, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    n_pages = slots * pages_per_slot + 1
    k, v = (jnp.asarray(rng.normal(size=(n_pages, PAGE, KV, D)), jnp.float32)
            for _ in range(2))
    scales = {}
    if cache_dtype != "fp32":
        (k, ks), (v, vs) = quantize_kv_int4(k), quantize_kv_int4(v)
        k, v = pack_int4(k, axis=1), pack_int4(v, axis=1)
        scales = {"k_scale": lane_major_scales(ks),
                  "v_scale": lane_major_scales(vs)}
    tables = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(
        slots, pages_per_slot), jnp.int32)
    lengths = jnp.asarray(rng.integers(4, pages_per_slot * PAGE + 1,
                                       size=slots), jnp.int32)
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for q_shape in ((slots, H, D), (slots, 4, H, D)):
            q = jnp.asarray(rng.normal(size=q_shape), jnp.float32)
            got = ops.paged_attention(q, k, v, tables, lengths, **scales)
            want = ops.paged_attention(q, k, v, tables, lengths,
                                       impl="ref", **scales)
            worst = max(worst, float(jnp.max(jnp.abs(got - want))))
    return worst


def run_engine(backend, params, spec, cfg, reqs, compiles: CompileLog):
    eng = ContinuousBatchingEngine(params, spec, cfg, backend=backend)
    c0, s0, t0 = compiles.count, compiles.seconds, time.perf_counter()
    done = eng.run(reqs)
    return done, eng, {"compiles": compiles.count - c0,
                       "compile_s": compiles.seconds - s0,
                       "drain_s": time.perf_counter() - t0,
                       "generated_tokens": sum(len(c.tokens) for c in done)}


def completion_failures(tag, reqs, done) -> list:
    out = []
    if sorted(c.uid for c in done) != sorted(r.uid for r in reqs):
        out.append(f"{tag}: completions do not match the submitted uids")
    for r, c in zip(sorted(reqs, key=lambda r: r.uid), done):
        if c.status != "ok" or len(c.tokens) != r.max_new_tokens:
            out.append(f"{tag}: uid {c.uid} status {c.status}, "
                       f"{len(c.tokens)}/{r.max_new_tokens} tokens")
    return out


# -- the float32 contiguous reference and the paged probe ---------------

@functools.partial(jax.jit, static_argnames=("spec", "max_seq"))
def _ref_prefill(params, tokens, *, spec, max_seq):
    return lm.prefill(params, spec, {"tokens": tokens}, max_seq=max_seq,
                      impl="naive")


@functools.partial(jax.jit, static_argnames=("spec",))
def _decode_step(params, cache, tokens, *, spec):
    return lm.decode_step(params, spec, cache, tokens)


@functools.partial(jax.jit, static_argnames=("spec", "n_prefix_pages"))
def _paged_prefill(params, cache, prefix, suffix, suffix_len, row, *, spec,
                   n_prefix_pages):
    """The engine's two admission programs back to back: a cold prefill
    of the page-aligned prefix scattered into pages, then the suffix
    prefilled against those pages (``lm.prefill_paged``)."""
    _, pre = lm.prefill(params, spec, {"tokens": prefix}, impl="naive")
    groups = pc.scatter_prompt_pages(cache["groups"], pre["groups"],
                                     row[:n_prefix_pages],
                                     lm.paged_page_size(cache))
    cache = {"pos": cache["pos"], "block_tables": cache["block_tables"],
             "groups": groups}
    return lm.prefill_paged(params, spec, suffix, cache, 0, row,
                            prefix.shape[1], suffix_len,
                            n_prefix_pages=n_prefix_pages)


@functools.partial(jax.jit, static_argnames=("spec",))
def _ref_rows(params, ids, rows, *, spec):
    """Teacher-forced reference logits of ``ids`` at positions ``rows``."""
    logits, _ = lm.forward(params, spec, {"tokens": ids}, impl="naive",
                           remat=False)
    return logits[0, rows]


def probe_logits(params, spec, cache_dtype: str, prompt, *, prefix: int,
                 steps: int = PROBE_STEPS, max_seq: int = MAX_SEQ) -> float:
    """Largest relative logit error of the paged path (prefix pages,
    suffix prefill, ``steps`` paged decode steps through the paged
    attention op) against the contiguous float32 reference on one
    prompt; decode is teacher-forced with the reference's greedy
    tokens."""
    n = len(prompt)
    suffix = prompt[prefix:]
    spad = np.zeros((1, pc.pages_needed(len(suffix), PAGE) * PAGE), np.int32)
    spad[0, :len(suffix)] = suffix
    pps = pc.pages_needed(max_seq, PAGE)
    layout = lm.PagedLayout(num_pages=pps + 1, page_size=PAGE,
                            pages_per_slot=pps)
    with jax.default_matmul_precision("highest"):
        logits, cache = _ref_prefill(params, jnp.asarray(prompt[None]),
                                     spec=spec, max_seq=n + steps)
        ref, toks = [logits[0, 0]], []
        for _ in range(steps):
            toks.append(jnp.argmax(ref[-1]).astype(jnp.int32)[None, None])
            logits, cache = _decode_step(params, cache, toks[-1], spec=spec)
            ref.append(logits[0, 0])
        pcache = lm.init_cache(spec, 1, max_seq, cache_dtype, paged=layout)
        logits, pcache = _paged_prefill(
            params, pcache, jnp.asarray(prompt[None, :prefix]),
            jnp.asarray(spad), jnp.int32(len(suffix)),
            jnp.arange(1, pps + 1, dtype=jnp.int32), spec=spec,
            n_prefix_pages=prefix // PAGE)
        got = [logits[0, 0]]
        for tok in toks:
            logits, pcache = _decode_step(params, pcache, tok, spec=spec)
            got.append(logits[0, 0])
    return max(float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
               for g, r in zip(got, ref))


def engine_gap(params, spec, reqs, done) -> float:
    """Largest ``greedy_gap`` of the engine's completions against the
    teacher-forced float32 reference (one fixed-shape forward per
    request)."""
    by_uid = {r.uid: r for r in reqs}
    width = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    n_rows = max(r.max_new_tokens for r in reqs)
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for c in done:
            prompt = by_uid[c.uid].prompt
            seq = np.concatenate([prompt, c.tokens])
            ids = np.zeros((1, width), np.int32)
            ids[0, :len(seq) - 1] = seq[:-1]
            rows = np.minimum(len(prompt) - 1 + np.arange(n_rows),
                              width - 1).astype(np.int32)
            ref = _ref_rows(params, jnp.asarray(ids), jnp.asarray(rows),
                            spec=spec)
            worst = max(worst, greedy_gap(c.tokens,
                                          np.asarray(ref)[:len(c.tokens)]))
    return worst


# -- phases ---------------------------------------------------------------

def one_chip_phase(params, spec, cache_dtype: str, compiles: CompileLog, *,
                   kv_budget_bytes: float, reqs, slots: int = SLOTS,
                   max_seq: int = MAX_SEQ, prefix: int = 256) -> list:
    """Serve ``reqs`` on one device with ``cache_dtype`` pages and check
    kernel, completions and numerics; returns failure messages."""
    tag = f"1chip {cache_dtype}"
    cfg = scheduler_config(cache_dtype, kv_budget_bytes, slots=slots,
                           max_seq=max_seq)
    backend = make_backend(params, spec, cfg, devices=1)
    failures = []
    if not decode_has_kernel(backend):
        failures.append(f"{tag}: no Pallas kernel in the decode step")
    done, eng, r = run_engine(backend, params, spec, cfg, reqs, compiles)
    reading(tag, kv_budget_bytes=int(kv_budget_bytes),
            pool_bytes=tree_bytes(backend.cache["groups"]),
            pages=eng.layout.num_pages, slots=slots,
            prefix_hit_tokens=int(eng.stats["prefix_hit_tokens"]),
            compiles=r["compiles"], compile_s=f"{r['compile_s']:.1f}",
            drain_s=f"{r['drain_s']:.1f}",
            generated_tokens=r["generated_tokens"],
            peak_bytes_in_use=peak_bytes(jax.devices()[0]))
    failures += completion_failures(tag, reqs, done)
    if eng.stats["prefix_hit_tokens"] == 0:
        failures.append(f"{tag}: the prefix cache was never hit")
    del eng, backend
    # uid 0 carries the shared prefix (see workload)
    err = probe_logits(params, spec, cache_dtype, reqs[0].prompt,
                       prefix=prefix, max_seq=max_seq)
    gap = engine_gap(params, spec, reqs, done)
    kerr = kernel_vs_reference(spec, cache_dtype, slots=slots)
    reading(tag, kernel_abs_err=f"{kerr:.3g}", kernel_atol=KERNEL_ATOL,
            probe_rel_err=f"{err:.3g}", probe_rtol=PROBE_RTOL[cache_dtype],
            engine_greedy_gap=f"{gap:.3g}", gap_rtol=GAP_RTOL[cache_dtype])
    if not kerr <= KERNEL_ATOL:
        failures.append(f"{tag}: paged attention op differs from its "
                        f"reference by {kerr:.3g} > {KERNEL_ATOL}")
    if not err <= PROBE_RTOL[cache_dtype]:
        failures.append(f"{tag}: paged logits rel err {err:.3g} > "
                        f"{PROBE_RTOL[cache_dtype]}")
    if not gap <= GAP_RTOL[cache_dtype]:
        failures.append(f"{tag}: engine greedy gap {gap:.3g} > "
                        f"{GAP_RTOL[cache_dtype]}")
    return failures


def four_chip_phase(params, spec, compiles: CompileLog, *,
                    kv_budget_bytes: float, reqs_fn, slots: int = SLOTS,
                    max_seq: int = 512) -> list:
    """tp=4 engine vs the one-chip engine, at the default and at
    "highest" matmul precision, then dp=4 one-chip replicas behind the
    router, each on its own device; returns failures."""
    cfg = scheduler_config("fp32", kv_budget_bytes, slots=slots,
                           max_seq=max_seq)
    failures = []
    one = make_backend(params, spec, cfg, devices=1)
    one_bytes = one.param_bytes_per_device()
    base, _, r = run_engine(one, params, spec, cfg, reqs_fn(), compiles)
    reading("1chip fp32", **r)
    failures += completion_failures("1chip", reqs_fn(), base)
    with jax.default_matmul_precision("highest"):
        base_hi, _, r = run_engine(make_backend(params, spec, cfg, devices=1),
                                   params, spec, cfg, reqs_fn(), compiles)
    reading("1chip fp32 highest", **r)
    del one

    tp = make_backend(params, spec, cfg, devices=4)
    ratio = tp.param_bytes_per_device() / one_bytes
    if not tp.weights_sharded:
        failures.append("tp=4: weights not sharded")
    if not ratio <= 0.35:
        failures.append(f"tp=4: per-device param bytes {ratio:.3f} of one "
                        "chip's (want about 1/4)")
    if not decode_has_kernel(tp):
        failures.append("tp=4: no Pallas kernel in the decode step")
    done, _, r = run_engine(tp, params, spec, cfg, reqs_fn(), compiles)
    # sharded matmuls reduce in another order, and at the default
    # (one bf16 pass) precision that forks greedy streams at near-ties
    # within a few tokens, so there each stream is held to the float32
    # reference token by token; at "highest" precision the two programs
    # differ by float32 rounding only, and their streams must keep to
    # each other within the prefix band
    gaps = [engine_gap(params, spec, reqs_fn(), d) for d in (base, done)]
    fracs = [token_match_fraction(a.tokens, b.tokens)
             for a, b in zip(base, done)]
    reading("tp=4 fp32", param_bytes_ratio=f"{ratio:.4f}",
            min_match_frac=f"{min(fracs):.3f}",
            one_chip_greedy_gap=f"{gaps[0]:.3g}",
            tp4_greedy_gap=f"{gaps[1]:.3g}", gap_rtol=GAP_RTOL["fp32"],
            **r)
    failures += completion_failures("tp=4", reqs_fn(), done)
    if not gaps[1] <= GAP_RTOL["fp32"]:
        failures.append(f"tp=4: engine greedy gap {gaps[1]:.3g} > "
                        f"{GAP_RTOL['fp32']}")
    del tp
    with jax.default_matmul_precision("highest"):
        done, _, r = run_engine(make_backend(params, spec, cfg, devices=4),
                                params, spec, cfg, reqs_fn(), compiles)
    fracs = [token_match_fraction(a.tokens, b.tokens)
             for a, b in zip(base_hi, done)]
    reading("tp=4 fp32 highest", min_match_frac=f"{min(fracs):.3f}", **r)
    failures += completion_failures("tp=4 highest", reqs_fn(), done)
    failures += [f"tp=4 highest: uid {c.uid} matches one chip on "
                 f"{f:.3f} < {BAND}" for c, f in zip(done, fracs) if f < BAND]

    engines = make_replicas(params, spec, cfg, dp=4)
    placed = [(e.backend.device, {d for x in jax.tree_util.tree_leaves(
        (e.backend.params, e.backend.cache)) for d in x.devices()})
        for e in engines]
    if (len({d for d, _ in placed}) != 4
            or any(held != {d} for d, held in placed)):
        failures.append(f"dp=4: replicas not one per device: {placed}")
    # each replica runs the one-chip program itself, so its streams
    # keep to the one-chip ones within tolerance.py's prefix band
    router = PrefixRouter(engines, page_size=PAGE)
    c0, t0 = compiles.count, time.perf_counter()
    done = router.run(reqs_fn())
    fracs = [token_match_fraction(a.tokens, b.tokens)
             for a, b in zip(base, done)]
    reading("dp=4 fp32", devices=",".join(str(d.id) for d, _ in placed),
            per_replica_tokens=",".join(
                str(int(e.stats["decode_tokens"])) for e in engines),
            step_faults=int(router.stats["step_faults"]),
            compiles=compiles.count - c0,
            drain_s=f"{time.perf_counter() - t0:.1f}",
            min_match_frac=f"{min(fracs):.3f}")
    if router.stats["step_faults"]:
        failures.append(f"dp=4: {router.stats['step_faults']} step faults")
    if any(e.stats["decode_tokens"] == 0 for e in engines):
        failures.append("dp=4: a replica served nothing")
    failures += completion_failures("dp=4", reqs_fn(), done)
    failures += [f"dp=4: uid {c.uid} matches one chip on {f:.3f} < {BAND}"
                 for c, f in zip(done, fracs) if f < BAND]
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 and dp=4 phase (needs 4 chips)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else under /tmp
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    cache_bytes = dir_bytes(enable_compile_cache())
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    spec = ARCHS[ARCH]
    print("bring-up readings below are single cold runs, not benchmark "
          "numbers", flush=True)
    params = init_params(spec)
    budget = pc.device_kv_budget(params, devices[0])
    reading("setup", device_kind=devices[0].device_kind,
            device_count=len(devices), arch=ARCH,
            param_bytes=tree_bytes(params),
            compile_cache_bytes_at_start=cache_bytes)
    if args.four_chips:
        failures = four_chip_phase(
            params, spec, compiles, kv_budget_bytes=budget,
            reqs_fn=lambda: workload(spec.vocab_size, n=8, prefix=0,
                                     long=(129, 256)))
    else:
        failures = []
        for cache_dtype in ("fp32", "int4"):
            failures += one_chip_phase(
                params, spec, cache_dtype, compiles, kv_budget_bytes=budget,
                reqs=workload(spec.vocab_size))
    reading("total", compiles=compiles.count,
            compile_s=f"{compiles.seconds:.1f}")
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

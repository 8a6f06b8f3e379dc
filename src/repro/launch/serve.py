"""Serving launcher: static batched generation or the continuous-
batching paged engine, with optional weight quantization.

Local mode runs a reduced config end-to-end — the paper's deployment
scenario (INT8/INT4 weight-only) on real arrays.  ``--engine paged``
drives the full scheduler stack (paged KV cache, prefix store, lazy
allocation/preemption) instead of the static ``engine.generate`` path;
``--cache-dtype {fp32,int8,int4}`` picks the page precision,
``--devices N`` serves the pool tensor-parallel over N devices
(KV-head-sharded pools + column/row-parallel weights via
``ShardedPagedBackend`` — on CPU run under
``XLA_FLAGS=--xla_force_host_platform_device_count=N``),
``--dp R`` runs R independent engine replicas behind the prefix-aware
rendezvous router (``serve.router.PrefixRouter``; with ``--devices``
each replica gets its own tp-device slice, so R x N host devices),
``--spec-k K`` turns on self-speculative decoding (n-gram
prompt-lookup drafts verified K tokens per step; outputs stay
token-for-token greedy), and ``--prefill-chunk T`` caps per-iteration
prefill admission at T tokens (chunked prefill: long prompts stream in
across iterations co-scheduled with decode, flattening the inter-token
latency spike their one-shot admission would cause; outputs stay
token-for-token identical).  ``--sliding-window W`` overrides the
spec's attention window; on a uniformly ``attn_local`` stack (gemma3
reduced to its local layers) the paged engine auto-switches to ring
block tables — per-slot KV bounded at O(window) pages for unbounded
streams.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve.engine import ServeConfig, generate, load_quantized
from repro.serve.spans import host_split_ms


def _run_static(args, spec, params):
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        spec.vocab_size)}
    if spec.vision_tokens:
        batch["patch_embeds"] = jnp.zeros(
            (args.batch, spec.vision_tokens, spec.vision_embed_dim), jnp.float32)
    if spec.encoder_layers:
        batch["frames"] = jnp.zeros(
            (args.batch, spec.encoder_seq, spec.d_model), jnp.float32)

    cfg = ServeConfig(max_seq=args.prompt_len + args.steps + 1,
                      temperature=args.temperature,
                      weight_precision=args.precision,
                      attention_impl="naive")
    t0 = time.time()
    out = generate(params, spec, batch, args.steps, cfg)
    out["tokens"].block_until_ready()
    dt = time.time() - t0
    print(f"[serve] generated {args.batch}x{args.steps} tokens in {dt:.2f}s "
          f"({args.batch * args.steps / dt:.1f} tok/s)")
    print(out["tokens"][:, :16])


def _run_paged(args, spec, params):
    """Continuous batching end-to-end: submit ``--batch`` requests with
    the prompt spread, drain the scheduler, report stats."""
    from repro.serve.backend import make_backend
    from repro.serve.paged_cache import device_kv_budget
    from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                      SchedulerConfig)
    rng = np.random.default_rng(1)
    reqs = []
    for i in range(args.batch):
        plen = int(rng.integers(max(4, args.prompt_len // 2),
                                args.prompt_len + 1))
        prompt = rng.integers(0, spec.vocab_size, size=plen).astype(np.int32)
        reqs.append(Request(i, prompt, args.steps))
    # the pool takes what the device holds next to the weights; a
    # device that reports no memory limit (the CPU) gets 64 MB
    budget = device_kv_budget(params, jax.devices()[0]) or 64e6
    cfg = SchedulerConfig(
        max_slots=min(8, args.batch), page_size=16,
        max_seq=args.prompt_len + args.steps + 16,
        kv_budget_bytes=budget, cache_dtype=args.cache_dtype,
        spec_k=args.spec_k,
        prefill_chunk_tokens=args.prefill_chunk)
    if args.dp > 1:
        _run_routed(args, spec, params, cfg, reqs)
        return
    backend = make_backend(params, spec, cfg, devices=args.devices)
    eng = ContinuousBatchingEngine(params, spec, cfg, backend=backend)
    t0 = time.time()
    done = eng.run(reqs)
    dt = time.time() - t0
    tok = sum(len(c.tokens) for c in done)
    usable = eng.layout.num_pages - 1
    occ = eng.stats["occupancy_sum"] / max(1, eng.stats["iterations"])
    print(f"[serve] paged engine ({args.cache_dtype} pages, "
          f"tp={backend.tp}, spec_k={cfg.spec_k}): {len(done)} requests, "
          f"{tok} tokens in {dt:.2f}s ({tok / dt:.1f} tok/s)")
    print(f"[serve] pool: {eng.layout.num_pages} pages x "
          f"{eng.layout.page_size} tok, mean occupancy {occ:.2f}, "
          f"preemptions {int(eng.stats['preemptions'])}, "
          f"prefix hits {int(eng.stats['prefix_hit_tokens'])} tok "
          f"({usable} usable pages)")
    if cfg.prefill_chunk_tokens:
        print(f"[serve] chunked prefill: {cfg.prefill_chunk_tokens}-token "
              f"budget, {int(eng.stats['prefill_chunks'])} partial chunks")
    if eng.ring:
        print(f"[serve] sliding window {eng.window}: ring tables "
              f"{eng.layout.slots_pages(cfg.max_seq)} pages/slot, "
              f"{int(eng.stats['ring_recycled_pages'])} pages recycled "
              f"in place, {int(eng.stats['ring_shared_released'])} "
              "shared entries released")
    if cfg.spec_k > 1:
        st = eng.stats
        acc = st["spec_accepted"] / max(1, st["spec_drafted"])
        print(f"[serve] spec decode: {int(st['spec_steps'])} windows, "
              f"{int(st['spec_accepted'])}/{int(st['spec_drafted'])} drafts "
              f"accepted ({acc:.2f}), "
              f"{st['decode_tokens'] / max(1, st['iterations']):.2f} "
              "tokens/iteration")
    _print_host_split(eng.stats)
    print(np.stack([c.tokens[:8] for c in done[:4]]))


def _print_host_split(stats):
    """The host time of a step as the engine's spans split it."""
    ms = host_split_ms(stats)
    print(f"[serve] host per step: engine {ms['engine_self_ms']:.3f} ms, "
          f"page table {ms['page_table_ms']:.3f} ms; decode dispatch "
          f"{ms['decode_dispatch_ms']:.3f} ms per call")


def _run_routed(args, spec, params, cfg, reqs):
    """``--dp R``: R independent scheduler+backend replicas behind the
    prefix-aware rendezvous router; reports fleet aggregate stats."""
    from repro.serve.router import PrefixRouter, make_replicas
    engines = make_replicas(params, spec, cfg, dp=args.dp,
                            tp=args.devices)
    router = PrefixRouter(engines, page_size=cfg.page_size)
    t0 = time.time()
    done = router.run(reqs)
    dt = time.time() - t0
    tok = sum(len(c.tokens) for c in done)
    agg = router.aggregate_stats()
    print(f"[serve] routed fleet (dp={args.dp} x tp={args.devices}, "
          f"{args.cache_dtype} pages): {len(done)} requests, {tok} tokens "
          f"in {dt:.2f}s ({tok / dt:.1f} tok/s wall, "
          f"{agg['aggregate_decode_tokens_per_s']:.1f} decode tok/s "
          "aggregate)")
    print(f"[serve] router: assigned {agg['assigned']}, "
          f"spilled {int(agg['spilled'])}, "
          f"rebalanced {int(agg['rebalanced'])}, "
          f"prefix hits {int(agg['prefix_hit_tokens'])} tok")
    _print_host_split(agg)
    print(np.stack([c.tokens[:8] for c in done[:4]]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "fp16", "int8", "int4"])
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--engine", default="static",
                    choices=["static", "paged"],
                    help="static generate() vs the continuous-batching "
                         "paged scheduler")
    ap.add_argument("--cache-dtype", default="fp32",
                    choices=["fp32", "int8", "int4"],
                    help="paged KV page precision (--engine paged)")
    ap.add_argument("--devices", type=int, default=1,
                    help="tensor-parallel degree for the paged engine "
                         "(KV-head-sharded page pool + column/row-"
                         "parallel weights)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replicas for the paged engine: "
                         "independent engines behind the prefix-aware "
                         "router (--devices becomes per-replica tp)")
    ap.add_argument("--spec-k", type=int, default=1,
                    help="self-speculative decode window for the paged "
                         "engine: verify up to K tokens per step from "
                         "n-gram prompt-lookup drafts (1 = off)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: per-iteration prefill token "
                         "budget for the paged engine (multiple of the "
                         "page size; 0 = admit whole prompts, the "
                         "latency-spiky default)")
    ap.add_argument("--sliding-window", type=int, default=0,
                    help="override the spec's attention sliding window "
                         "(tokens).  On a uniformly attn_local stack "
                         "(e.g. gemma3 scaled to its local layers) the "
                         "paged engine auto-switches to RING block "
                         "tables: per-slot KV bounded at O(window) "
                         "pages, out-of-window pages recycled in place")
    args = ap.parse_args()
    enable_compile_cache()

    spec = ARCHS[args.arch]
    if args.local:
        spec = spec.scaled_down(layers=args.layers, width=args.width,
                                vocab=args.vocab)
    if args.sliding_window:
        spec = spec.with_(sliding_window=args.sliding_window)
    rng = jax.random.PRNGKey(0)
    params = lm.init(rng, spec, dtype=jnp.float32)
    if args.precision in ("int8", "int4"):
        params = load_quantized(params, args.precision)
        print(f"[serve] weights quantized to {args.precision}")

    if args.engine == "paged":
        _run_paged(args, spec, params)
    else:
        _run_static(args, spec, params)


if __name__ == "__main__":
    main()

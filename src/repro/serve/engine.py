"""Serving engine: prefill + batched greedy/sampled decode.

Weight-only INT8/INT4 serving is first-class (the paper's deployment
recipe): ``load_quantized`` converts a float param tree once, and the
same decode_step runs with QuantizedTensor weights (qdot dispatches to
the Pallas dequant-matmul on TPU).  The KV cache can itself be held in
int8 (``cache_precision="int8"``) — a beyond-paper memory-roofline
optimization measured in §Perf.

Two serving modes live in this package:

* **Static batching** (this module): one ``generate()`` call prefills a
  fixed batch padded to the longest prompt and scan-decodes a fixed
  number of steps.  Simple, fully jitted, and the right tool for
  offline eval — but every padded prompt token and every decode step
  past a request's completion is wasted work on the memory-bound edge
  decode roofline the analytical model identifies.

* **Continuous batching** (``scheduler.ContinuousBatchingEngine``):
  iteration-level scheduling over a block-table paged KV cache
  (``paged_cache.py``) with REFCOUNTED pages.  Prompts are matched
  against a hash-indexed prefix store first — cached system-prompt /
  template pages are shared read-only across requests (copy-on-write
  when a shared prefix ends mid-page) and only the uncached suffix
  prefills (``lm.prefill_paged``); admission allocates prompt pages
  only (lazy), decode slots grab pages on demand, and under pressure
  the scheduler evicts unshared store pages then preempts the newest
  slot (greedy recompute, prefix pages retained by refcount).  Each
  iteration decodes one token for all live slots through the
  gather-based paged attention op (``kernels/paged_attention.py``).
  The scheduler drives the device through a
  ``serve.backend.PagedKVBackend``: ``--devices N`` serves the same
  host logic tensor-parallel (page pools sharded over the KV-head dim,
  block tables replicated, paged attention per shard) with
  token-for-token identical output.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.model_config import ModelSpec
from repro.models import lm
from repro.quant.qlinear import quantize_params


@dataclass
class ServeConfig:
    max_seq: int = 2048
    temperature: float = 0.0          # 0 = greedy
    weight_precision: str = "fp32"    # fp32 | fp16 | int8 | int4
    cache_dtype: Any = None
    attention_impl: str = "auto"


def load_quantized(params: Any, precision: str) -> Any:
    return quantize_params(params, precision)


def _sample(logits: jnp.ndarray, temperature: float, key) -> jnp.ndarray:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def generate(params: Any, spec: ModelSpec, batch: Dict[str, jnp.ndarray],
             num_steps: int, cfg: ServeConfig,
             rng: Optional[jax.Array] = None) -> Dict[str, jnp.ndarray]:
    """Prefill the prompt then decode ``num_steps`` tokens for the batch."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    logits, cache = lm.prefill(params, spec, batch, max_seq=cfg.max_seq,
                               impl=cfg.attention_impl,
                               cache_dtype=cfg.cache_dtype)
    tok0 = _sample(logits[:, 0], cfg.temperature, rng)

    def step(carry, key):
        cache, tok = carry
        logits, cache = lm.decode_step(params, spec, cache, tok[:, None])
        nxt = _sample(logits[:, 0], cfg.temperature, key)
        return (cache, nxt), nxt

    keys = jax.random.split(rng, num_steps)
    (cache, _), toks = jax.lax.scan(step, (cache, tok0), keys)
    out = jnp.concatenate([tok0[:, None], toks.T], axis=1)[:, :num_steps + 1]
    return {"tokens": out, "cache_pos": cache["pos"]}


def make_serve_step(spec: ModelSpec):
    """The jit-able unit the dry-run lowers: one batched decode step."""
    def serve_step(params, cache, tokens):
        return lm.decode_step(params, spec, cache, tokens)
    return serve_step


def make_prefill_step(spec: ModelSpec, max_seq: int, impl: str = "auto"):
    def prefill_step(params, batch):
        return lm.prefill(params, spec, batch, max_seq=max_seq, impl=impl)
    return prefill_step

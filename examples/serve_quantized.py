"""Serve a small model with batched requests and INT4/INT8 weight-only
quantization — the paper's edge-deployment recipe, end to end.

    PYTHONPATH=src python examples/serve_quantized.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.models import lm
from repro.serve.engine import ServeConfig, generate, load_quantized

spec = ARCHS["tinyllama-1.1b"].scaled_down(layers=4, width=256, vocab=1024)
params = lm.init(jax.random.PRNGKey(0), spec, dtype=jnp.float32)

BATCH, PROMPT, STEPS = 4, 16, 24
prompts = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                        (BATCH, PROMPT), 0, spec.vocab_size)}
cfg = ServeConfig(max_seq=PROMPT + STEPS + 1, attention_impl="naive")

for precision in ("fp32", "int8", "int4"):
    p = params if precision == "fp32" else load_quantized(params, precision)
    nbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree_util.tree_leaves(p))
    t0 = time.time()
    out = generate(p, spec, prompts, STEPS, cfg)
    out["tokens"].block_until_ready()
    dt = time.time() - t0
    print(f"{precision:5s} weights={nbytes / 1e6:7.2f}MB "
          f"batch={BATCH} steps={STEPS} wall={dt:5.2f}s "
          f"first tokens: {out['tokens'][0, :8].tolist()}")

print("\nINT8 halves and INT4 quarters the weight bytes — on the "
      "memory-bandwidth-bound decode path this is the paper's 2-3x speedup "
      "(python -m repro.launch.profile --sweep).")

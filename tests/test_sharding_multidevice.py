"""Multi-device sharding tests.

jax locks the device count at first init, so these run in subprocesses
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the same
mechanism the 512-device dry-run uses).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


PRELUDE = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import ASSIGNED
from repro.models import lm
from repro.parallel.sharding import ShardingRules
from repro.train.optimizer import AdamWState, adamw_init
from repro.train.train_step import TrainConfig, make_train_step
from jax.sharding import AxisType
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
def sds(tree, sh):
    return jax.tree_util.tree_map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        tree, sh)
"""


def test_sharded_train_step_runs_real_arrays():
    """Materialized sharded training step on a 4x2 mesh: loss finite and
    equal to the single-device value (SPMD correctness)."""
    out = _run(PRELUDE + """
import numpy as np
from repro.data.synthetic import DataConfig, batch_at
spec = ASSIGNED['granite-3-8b'].scaled_down(layers=2, width=64, vocab=64)
rules = ShardingRules(mesh, spec)
params = lm.init(jax.random.PRNGKey(0), spec)
psh = rules.param_shardings(params)
params = jax.device_put(params, psh)
opt = adamw_init(params)
dc = DataConfig(vocab_size=64, seq_len=32, global_batch=8)
batch = {k: jnp.asarray(v) for k, v in batch_at(dc, 0).items()}
batch = jax.device_put(batch, rules.batch_shardings(batch))
step = jax.jit(make_train_step(spec, TrainConfig(attention_impl='naive')))
p2, o2, m = step(params, opt, batch)
print('LOSS', float(m['loss']))
# single-device reference
params_s = jax.device_put(params, jax.sharding.SingleDeviceSharding(jax.devices()[0]))
batch_s = jax.device_put(batch, jax.sharding.SingleDeviceSharding(jax.devices()[0]))
opt_s = adamw_init(params_s)
p1, o1, m1 = step(params_s, opt_s, batch_s)
print('REF', float(m1['loss']))
assert abs(float(m['loss']) - float(m1['loss'])) < 1e-4
print('OK')
""")
    assert "OK" in out


def test_sharded_decode_and_long_context():
    """Decode with head-sharded KV cache and batch=1 seq-sharded cache
    (the long_500k layout) both run under SPMD."""
    out = _run(PRELUDE + """
from repro.core.model_config import ShapeSpec
spec = ASSIGNED['qwen2-moe-a2.7b'].scaled_down(layers=2, width=64, vocab=64)
rules = ShardingRules(mesh, spec)
params = lm.init(jax.random.PRNGKey(0), spec)
params = jax.device_put(params, rules.param_shardings(params))
# decode: batch 8 over data, kv heads over model
cache = lm.init_cache(spec, 8, 64)
csh = rules.cache_shardings(cache)
cache = jax.device_put(cache, csh)
toks = jnp.zeros((8, 1), jnp.int32)
logits, cache = jax.jit(lambda p, c, t: lm.decode_step(p, spec, c, t))(params, cache, toks)
assert logits.shape[0] == 8
# long-context: batch 1, seq sharded over data axis
cache1 = lm.init_cache(spec, 1, 128)
c1sh = rules.cache_shardings(cache1)
kspec = c1sh['groups'][0][0]['k'].spec
assert kspec[1] is not None, f'seq dim not sharded: {kspec}'
cache1 = jax.device_put(cache1, c1sh)
logits1, _ = jax.jit(lambda p, c, t: lm.decode_step(p, spec, c, t))(params, cache1, jnp.zeros((1, 1), jnp.int32))
import numpy as np
assert np.isfinite(np.asarray(logits1, np.float32)).all()
print('OK')
""")
    assert "OK" in out


def test_dryrun_machinery_on_debug_mesh():
    """The exact dry-run pipeline (abstract params -> lower -> compile ->
    cost extraction) on an 8-device mesh for a reduced arch."""
    out = _run(PRELUDE + """
from repro.core import hlo_analysis
spec = ASSIGNED['gemma3-4b'].scaled_down(layers=6, width=64, vocab=128)
spec = spec.with_(sliding_window=16, local_global_ratio=5)
rules = ShardingRules(mesh, spec)
params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), spec, dtype=jnp.bfloat16))
params_sds = sds(params, rules.param_shardings(params))
opt = jax.eval_shape(adamw_init, params_sds)
osh = rules.opt_shardings(params)
opt_sds = sds(opt, AdamWState(step=NamedSharding(mesh, P()), m=osh, v=osh))
batch = {'tokens': jax.ShapeDtypeStruct((8, 32), jnp.int32),
         'labels': jax.ShapeDtypeStruct((8, 32), jnp.int32)}
batch_sds = sds(batch, rules.batch_shardings(batch))
step = make_train_step(spec, TrainConfig(attention_impl='naive'))
compiled = jax.jit(step).lower(params_sds, opt_sds, batch_sds).compile()
cost = hlo_analysis.extract_cost(compiled)
assert cost['flops'] > 0
coll = hlo_analysis.parse_collective_bytes(compiled.as_text())
assert coll.total_bytes > 0, 'expected collectives on a 4x2 mesh'
print('OK flops=%.3g coll=%.3g' % (cost['flops'], coll.total_bytes))
""")
    assert "OK" in out


def test_pod_axis_composes_with_data():
    """(pod, data, model) mesh: gradient sync spans pod x data (the
    multi-pod proof at debug scale)."""
    out = _run("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
x = jax.ShapeDtypeStruct((8, 16), jnp.float32,
                         sharding=NamedSharding(mesh, P(("pod", "data"), None)))
w = jax.ShapeDtypeStruct((16, 16), jnp.float32,
                         sharding=NamedSharding(mesh, P(None, "model")))
def f(x, w):
    return jnp.sum(x @ w)
compiled = jax.jit(f).lower(x, w).compile()
txt = compiled.as_text()
assert "all-reduce" in txt
print("OK")
""")
    assert "OK" in out

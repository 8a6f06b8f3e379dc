"""Training loop, QAT, checkpointing, fault tolerance, elastic resharding,
compressed gradients, data-pipeline determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs import ASSIGNED
from repro.data.synthetic import DataConfig, batch_at
from repro.models import lm
from repro.parallel.compress import compressed_allreduce, init_residual
from repro.train.loop import LoopConfig, SimulatedPreemption, train
from repro.train.optimizer import (AdamWConfig, adamw_init, adamw_update,
                                   warmup_cosine)
from repro.train.train_step import TrainConfig, cross_entropy, make_train_step
from repro.quant.qtypes import W8_SYM_CHANNEL


def _cfgs(steps=25, fail_at=None, ckpt_dir=None, qat=None, micro=1):
    spec = ASSIGNED["granite-3-8b"].scaled_down(layers=2, width=64, vocab=64)
    tc = TrainConfig(optimizer=AdamWConfig(lr=5e-3), microbatches=micro,
                     attention_impl="naive", qat=qat)
    dc = DataConfig(vocab_size=64, seq_len=32, global_batch=8)
    loop = LoopConfig(total_steps=steps, ckpt_every=10, ckpt_dir=ckpt_dir,
                      log_every=100, fail_at_step=fail_at)
    return spec, tc, dc, loop


def test_loss_decreases():
    spec, tc, dc, loop = _cfgs(steps=40)
    res = train(spec, tc, dc, loop, log_fn=lambda s: None)
    h = res["history"]
    assert h[-1]["loss"] < h[0]["loss"]


def test_qat_trains():
    spec, tc, dc, loop = _cfgs(steps=15, qat=W8_SYM_CHANNEL)
    res = train(spec, tc, dc, loop, log_fn=lambda s: None)
    assert np.isfinite(res["history"][-1]["loss"])


def test_microbatch_equivalence():
    """grad accumulation over 4 microbatches == single big batch (loss
    metrics averaged; params equal within fp tolerance)."""
    spec, tc, dc, loop = _cfgs()
    params = lm.init(jax.random.PRNGKey(0), spec)
    opt = adamw_init(params)
    batch = {k: jnp.asarray(v) for k, v in batch_at(dc, 0).items()}
    s1 = make_train_step(spec, TrainConfig(optimizer=AdamWConfig(lr=1e-3),
                                           microbatches=1,
                                           attention_impl="naive"))
    s4 = make_train_step(spec, TrainConfig(optimizer=AdamWConfig(lr=1e-3),
                                           microbatches=4,
                                           attention_impl="naive"))
    p1, _, m1 = jax.jit(s1)(params, opt, batch)
    p4, _, m4 = jax.jit(s4)(params, opt, batch)
    # losses computed per-microbatch then averaged vs full batch: equal here
    # because every microbatch has identical token counts
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-5)
    d = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p4)))
    assert d < 5e-5


def test_checkpoint_resume_bit_exact(tmp_path):
    """Kill at step 15, resume from step-10 checkpoint, end state must equal
    the uninterrupted run (fault-tolerance invariant)."""
    d1 = tmp_path / "a"
    spec, tc, dc, loop = _cfgs(steps=20, ckpt_dir=str(d1))
    res_full = train(spec, tc, dc, loop, log_fn=lambda s: None)

    d2 = tmp_path / "b"
    spec, tc, dc, loop = _cfgs(steps=20, ckpt_dir=str(d2), fail_at=15)
    with pytest.raises(SimulatedPreemption):
        train(spec, tc, dc, loop, log_fn=lambda s: None)
    # restart: auto-resume from step 10
    spec, tc, dc, loop = _cfgs(steps=20, ckpt_dir=str(d2))
    res_resumed = train(spec, tc, dc, loop, log_fn=lambda s: None)

    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        res_full["params"], res_resumed["params"])
    assert max(jax.tree_util.tree_leaves(diffs)) < 1e-6


def test_checkpoint_atomicity(tmp_path):
    """A half-written checkpoint directory must never be selected."""
    spec, tc, dc, loop = _cfgs()
    params = {"w": jnp.arange(4.0)}
    ckpt.save(tmp_path, 10, params)
    # simulate a crashed writer at step 20
    (tmp_path / "step_00000020.tmp").mkdir()
    (tmp_path / "step_00000020.tmp" / "arrays.npz").write_bytes(b"garbage")
    assert ckpt.latest_step(tmp_path) == 10
    restored = ckpt.restore(tmp_path, params)
    assert jnp.allclose(restored["w"], params["w"])


def test_checkpoint_corrupt_latest_pointer(tmp_path):
    params = {"w": jnp.arange(4.0)}
    ckpt.save(tmp_path, 5, params)
    ckpt.save(tmp_path, 7, params)
    (tmp_path / "LATEST").write_text("step_99999999")   # dangling pointer
    assert ckpt.latest_step(tmp_path) == 7               # falls back to scan


def test_elastic_reshard_restore(tmp_path):
    """Checkpoint written on one 'mesh' restores onto different shardings
    (elastic shrink/grow) — single-process device_put path."""
    params = {"w": jnp.arange(64.0).reshape(8, 8)}
    ckpt.save(tmp_path, 1, params)
    shd = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    out = ckpt.restore(tmp_path, params, shardings={"w": shd})
    assert jnp.allclose(out["w"], params["w"])
    assert out["w"].sharding == shd


def test_data_pipeline_deterministic_and_shardable():
    dc = DataConfig(vocab_size=64, seq_len=32, global_batch=8)
    a = batch_at(dc, 7)
    b = batch_at(dc, 7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # sharded reads partition the same global batch
    s0 = batch_at(dc, 7, shard=0, num_shards=2)
    assert s0["tokens"].shape == (4, 32)
    c = batch_at(dc, 8)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_cross_entropy_masks_padded_vocab():
    logits = jnp.zeros((1, 4, 10))
    labels = jnp.array([[1, 2, 3, -1]])
    loss = cross_entropy(logits, labels, vocab_size=8)
    # uniform over 8 real classes -> ln(8); padded ids excluded
    assert float(loss) == pytest.approx(np.log(8), rel=1e-3)


def test_warmup_cosine_schedule():
    s = warmup_cosine(1.0, warmup=10, total=100)
    assert float(s(jnp.array(0))) < 0.2
    assert float(s(jnp.array(10))) == pytest.approx(1.0, rel=0.1)
    assert float(s(jnp.array(99))) < 0.2


def test_compressed_allreduce_error_feedback():
    """int8 error-feedback compression: mean of per-rank grads recovered
    within quantization error per step; residual carries the bias."""
    from jax.sharding import AxisType, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(16,)),
                          jnp.float32)}
    r = init_residual(g)

    def f(gv, rv):
        return compressed_allreduce({"w": gv}, {"w": rv}, "data")

    fn = jax.shard_map(lambda a, b: f(a, b), mesh=mesh,
                       in_specs=(P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    (synced, res) = fn(g["w"], r["w"])
    # single rank: synced == dequantized(g); residual == g - synced
    np.testing.assert_allclose(np.asarray(synced["w"] + res["w"]),
                               np.asarray(g["w"]), rtol=1e-6, atol=1e-6)
    # error feedback: applying twice with residual recovers exactly on avg
    (synced2, _) = fn(g["w"], res["w"])
    total = np.asarray(synced["w"]) + np.asarray(synced2["w"])
    np.testing.assert_allclose(total, 2 * np.asarray(g["w"]),
                               atol=2 * float(jnp.abs(g["w"]).max()) / 127)

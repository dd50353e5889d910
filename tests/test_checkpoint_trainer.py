"""Fault-tolerance substrate: checkpoint round-trip, elastic resharding,
NaN rollback, preemption, straggler accounting, data-stream resumption."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.data.synthetic import DataConfig, SyntheticTokenStream
from repro.models.model import Model
from repro.optim import AdamWConfig
from repro.train import checkpoint as ckpt
from repro.train.trainer import Trainer, TrainerConfig
from repro.launch.steps import init_train_state, make_train_step


def _tiny_setup(tmp_path, total_steps=12, ckpt_every=4):
    cfg = get_arch("internlm2-1.8b").reduced().replace(
        num_layers=2, d_model=32, d_ff=64, vocab_size=64, num_heads=2,
        num_kv_heads=2, dtype="float32")
    model = Model(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3)))
    data = SyntheticTokenStream(DataConfig(vocab_size=64, seq_len=16,
                                           global_batch=4))
    tc = TrainerConfig(total_steps=total_steps, ckpt_every=ckpt_every,
                       ckpt_dir=str(tmp_path / "ck"), log_every=100)
    return model, state, step_fn, data, tc


def test_checkpoint_round_trip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    ckpt.save(tmp_path, 3, tree, extra={"train_step": 3, "data_step": 7})
    restored, extra = ckpt.restore(tmp_path, tree)
    assert extra["train_step"] == 3
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(restored["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_checkpoint_atomicity_and_gc(tmp_path):
    tree = {"x": jnp.zeros((3,))}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, tree, keep=2)
    steps = sorted(p.name for p in tmp_path.glob("step_*"))
    assert steps == ["step_0000004", "step_0000005"]
    assert not list(tmp_path.glob("tmp_*"))
    assert ckpt.latest_step(tmp_path) == 5


def test_checkpoint_elastic_resharding(tmp_path):
    """Restore under a different sharding (elastic restart path)."""
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    ckpt.save(tmp_path, 1, tree)
    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", None))
    restored, _ = ckpt.restore(tmp_path, tree, shardings={"w": sh})
    assert restored["w"].sharding == sh
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


def test_trainer_end_to_end_and_resume(tmp_path):
    model, state, step_fn, data, tc = _tiny_setup(tmp_path)
    tr = Trainer(step_fn, data, tc)
    _, step = tr.fit(state, resume=False)
    assert step == tc.total_steps
    losses = [m["loss"] for m in tr.metrics_history]
    assert all(np.isfinite(l) for l in losses)

    # resume from checkpoint: a fresh trainer continues, not restarts
    tc2 = TrainerConfig(**{**tc.__dict__, "total_steps": 16})
    data2 = SyntheticTokenStream(data.cfg)
    tr2 = Trainer(jax.jit(step_fn), data2, tc2)
    model2 = Model  # noqa
    state2, step2 = tr2.fit(state, resume=True)
    assert step2 == 16
    assert tr2.metrics_history[0]["step"] == 13   # continued, not restarted


def test_trainer_nan_rollback(tmp_path):
    model, state, step_fn, data, tc = _tiny_setup(tmp_path, total_steps=10,
                                                  ckpt_every=3)
    calls = {"n": 0}

    def poisoned_step(state, batch):
        calls["n"] += 1
        new_state, metrics = step_fn(state, batch)
        if calls["n"] == 5:       # poison exactly one step
            metrics = dict(metrics)
            metrics["loss"] = jnp.float32(jnp.nan)
        return new_state, metrics

    tr = Trainer(poisoned_step, data, tc)
    _, step = tr.fit(state, resume=False)
    assert step == 10
    assert tr.rollbacks == 1
    assert all(np.isfinite(m["loss"]) for m in tr.metrics_history)


def test_trainer_preemption_checkpoints(tmp_path):
    model, state, step_fn, data, tc = _tiny_setup(tmp_path, total_steps=50,
                                                  ckpt_every=100)

    tr = Trainer(step_fn, data, tc)
    orig = tr.step_fn

    def slow_then_preempt(state, batch):
        out = orig(state, batch)
        if len(tr.metrics_history) >= 4:
            tr.preempted = True       # simulate SIGTERM delivery
        return out

    tr.step_fn = slow_then_preempt
    _, step = tr.fit(state, resume=False)
    assert step < 50
    assert ckpt.latest_step(tc.ckpt_dir) == step  # checkpointed on exit


def test_trainer_straggler_detection(tmp_path):
    model, state, step_fn, data, tc = _tiny_setup(tmp_path, total_steps=20)
    tc.straggler_warmup = 3
    tc.straggler_factor = 2.0
    events = []

    def slow_step(state, batch):
        if len(events) == 0 and data.step == 15:
            time.sleep(0.5)
        return step_fn(state, batch)

    tr = Trainer(slow_step, data, tc,
                 straggler_cb=lambda s, t: events.append((s, t)))
    tr.fit(state, resume=False)
    assert tr.straggler_events >= 1


def test_data_stream_determinism_and_resume():
    cfg = DataConfig(vocab_size=97, seq_len=256, global_batch=8, seed=5)
    s1 = SyntheticTokenStream(cfg)
    batches = [s1.next_batch()["tokens"] for _ in range(4)]
    s2 = SyntheticTokenStream.from_state(cfg, {"step": 2, "seed": 5})
    np.testing.assert_array_equal(np.asarray(s2.next_batch()["tokens"]),
                                  np.asarray(batches[2]))
    # learnable structure: consecutive tokens obey the recurrence at the
    # (1-noise)^2 ~ 0.81 rate
    t = np.asarray(batches[0])
    hits = (t[:, 1:] == (t[:, :-1] * cfg.mult + cfg.add) % cfg.vocab_size)
    assert 0.7 < hits.mean() < 0.95

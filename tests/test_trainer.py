"""Elastic trainer: loss descends, checkpoint-restart resumes."""
import dataclasses

import jax
import pytest

from repro.data import DataConfig
from repro.models import build_model, get_model, reduced_config
from repro.optim import AdamWConfig
from repro.runtime import ElasticTrainer, TrainerConfig


def make(steps=60, **kw):
    _, full = get_model("smollm-135m")
    cfg = reduced_config(full)
    model = build_model(cfg)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
    opt = AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=steps)
    return ElasticTrainer(model, opt, data,
                          TrainerConfig(steps=steps, model_ways=1,
                                        max_slices=1, log_period=10, **kw))


@pytest.mark.slow
def test_loss_descends():
    tr = make(steps=120)
    tr.train()
    first = tr.metrics[0]["loss"]
    last = tr.metrics[-1]["loss"]
    assert last < first - 0.3, (first, last)


@pytest.mark.slow
def test_checkpoint_resume(tmp_path):
    tr = make(steps=40, ckpt_dir=str(tmp_path), ckpt_period=20)
    state = tr.train()
    assert tr.store.latest_step() == 40
    # resume into a new trainer from the checkpoint
    tr2 = make(steps=50, ckpt_dir=str(tmp_path), ckpt_period=20)
    template = tr2.init_state()
    restored = tr2.store.restore(40, template,
                                 tr2._state_shardings(tr2.mesh))
    assert int(restored["step"]) == 40
    out = tr2.train(state=restored)
    assert int(out["step"]) == 50


@pytest.mark.slow
def test_grad_accum_equivalence():
    """accum=2 must match accum=1 on the same global batch (fp32)."""
    import jax.numpy as jnp
    _, full = get_model("smollm-135m")
    cfg = dataclasses.replace(reduced_config(full), dtype="float32")
    model = build_model(cfg)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)

    def run(accum):
        tr = ElasticTrainer(model, opt, data,
                            TrainerConfig(steps=5, model_ways=1,
                                          max_slices=1, grad_accum=accum,
                                          log_period=1))
        tr.train()
        return [m["loss"] for m in tr.metrics]

    l1, l2 = run(1), run(2)
    assert max(abs(a - b) for a, b in zip(l1, l2)) < 5e-3


def fail_from(tr, first_bad_step):
    """Make every step from ``first_bad_step`` on fail as a device error
    that no retry cures would (an out-of-memory, a compile error)."""
    real = tr.step_fn
    failures = []

    def step_fn(mesh):
        fn = real(mesh)

        def step(state, batch):
            if int(state["step"]) >= first_bad_step:
                failures.append(int(state["step"]))
                raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: boom")
            return fn(state, batch)
        return step
    tr.step_fn = step_fn
    return failures


def test_repeated_step_failure_raises_without_store():
    tr = make(steps=4)
    failures = fail_from(tr, 2)
    with pytest.raises(jax.errors.JaxRuntimeError, match="boom"):
        tr.train()
    assert failures == [2]


def test_repeated_step_failure_raises_after_one_restore(tmp_path):
    tr = make(steps=6, ckpt_dir=str(tmp_path), ckpt_period=2)
    failures = fail_from(tr, 3)
    with pytest.raises(jax.errors.JaxRuntimeError, match="boom"):
        tr.train()
    # restored step 2 once, failed again at step 3, then gave up
    assert failures == [3, 3]
    assert tr.store.latest_step() == 2


def test_restore_onto_current_mesh_is_bit_equal(tmp_path):
    tr = make(steps=2, ckpt_dir=str(tmp_path), ckpt_period=2)
    state = tr.train()
    restored = tr.restore(2)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert a.sharding == b.sharding
        assert (jax.numpy.asarray(a) == jax.numpy.asarray(b)).all()

"""Checkpoint/resume (orbax): round-trip fidelity, latest-selection,
retention, sharded state (reference counterpart: write-only save at
train.py:123-125; resume/retention are our extensions)."""

import jax
import numpy as np
import pytest

from tpudist import checkpoint, engine
from tpudist.config import DataConfig, ParallelConfig, TrainConfig
from tpudist.parallel import build_mesh


@pytest.fixture()
def cfg():
    return TrainConfig(batch_size=32, data=DataConfig(n_samples=64))


def _state(cfg, mesh, seed=0):
    return engine.init_state(jax.random.PRNGKey(seed), cfg, mesh)


def test_roundtrip(tmp_path, cfg, devices8):
    mesh = build_mesh(cfg.parallel, devices=devices8)
    state = _state(cfg, mesh)
    checkpoint.save(str(tmp_path), state, epoch=0)
    restored, next_epoch = checkpoint.restore_latest(str(tmp_path), state)
    assert next_epoch == 1
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, restored)


def test_latest_wins(tmp_path, cfg, devices8):
    mesh = build_mesh(cfg.parallel, devices=devices8)
    s0 = _state(cfg, mesh, seed=0)
    s1 = _state(cfg, mesh, seed=1)
    checkpoint.save(str(tmp_path), s0, epoch=0)
    checkpoint.save(str(tmp_path), s1, epoch=1)
    restored, next_epoch = checkpoint.restore_latest(str(tmp_path), s0)
    assert next_epoch == 2
    np.testing.assert_array_equal(np.asarray(restored.params["fc1"]["w"]),
                                  np.asarray(s1.params["fc1"]["w"]))


def test_retention_keeps_last_k(tmp_path, cfg, devices8):
    mesh = build_mesh(cfg.parallel, devices=devices8)
    s = _state(cfg, mesh)
    for e in range(5):
        checkpoint.save(str(tmp_path), s, epoch=e, keep=2)
    kept = sorted(int(p.name) for p in tmp_path.iterdir() if p.name.isdigit())
    assert kept == [3, 4]


def test_restore_missing_dir_returns_none(tmp_path, cfg, devices8):
    mesh = build_mesh(cfg.parallel, devices=devices8)
    s = _state(cfg, mesh)
    assert checkpoint.restore_latest(str(tmp_path / "nope"), s) is None
    # empty dir also yields None
    (tmp_path / "empty").mkdir()
    assert checkpoint.restore_latest(str(tmp_path / "empty"), s) is None


def test_fsdp_sharded_roundtrip(tmp_path, devices8):
    """Sharded state saves/restores without gathering and lands back in the
    FSDP layout."""
    cfg = TrainConfig(batch_size=32, data=DataConfig(n_samples=64),
                      parallel=ParallelConfig(fsdp=4))
    mesh = build_mesh(cfg.parallel, devices=devices8)
    state = _state(cfg, mesh)
    checkpoint.save(str(tmp_path), state, epoch=0)
    restored, _ = checkpoint.restore_latest(str(tmp_path), state)
    from jax.sharding import PartitionSpec as P
    assert restored.params["fc1"]["w"].sharding.spec == P(None, "fsdp")
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state.params, restored.params)


@pytest.mark.parametrize("model_kw,par", [
    (dict(name="transformer", vocab_size=128, n_layers=4, d_model=32,
          n_heads=2, n_kv_heads=2, d_ff=64, max_seq_len=16),
     dict(data=2, pipe=2, fsdp=2)),
    (dict(name="moe", vocab_size=128, n_layers=2, d_model=32, n_heads=2,
          n_kv_heads=2, d_ff=48, max_seq_len=16, n_experts=4),
     dict(data=2, fsdp=2, expert=2)),
])
def test_pipe_and_expert_sharded_roundtrip(tmp_path, model_kw, par,
                                           devices8):
    """Stage-sharded layer stacks and expert-sharded FFN weights survive
    an orbax save/restore onto their mesh layouts, and training resumes
    from the restored state (loss continues, not restarts)."""
    from tpudist.config import ModelConfig

    cfg = TrainConfig(batch_size=8, lr=1e-2, seed=0, dtype="float32",
                      data=DataConfig(n_samples=8),
                      model=ModelConfig(**model_kw),
                      parallel=ParallelConfig(**par))
    mesh = build_mesh(cfg.parallel, devices=devices8)
    state = _state(cfg, mesh)
    step = engine.make_train_step(cfg, mesh)
    from tpudist import data as data_lib
    toks = data_lib.make_synthetic_tokens(8, 17, 128, seed=0)
    state, l0 = step(state, (toks,))
    checkpoint.save(str(tmp_path), state, epoch=0)

    fresh = _state(cfg, mesh, seed=7)     # different init
    restored, next_epoch = checkpoint.restore_latest(str(tmp_path), fresh)
    assert next_epoch == 1
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, restored)
    # restored state trains onward: same next loss as the original
    _, l1a = step(restored, (toks,))
    _, l1b = step(state, (toks,))
    np.testing.assert_allclose(float(l1a), float(l1b), rtol=1e-6)


def test_checkpointer_async_roundtrip(tmp_path, cfg, devices8):
    """Async saves land a readable step-keyed checkpoint with its resume
    position, and close() drains the outstanding write."""
    mesh = build_mesh(cfg.parallel, devices=devices8)
    state = _state(cfg, mesh)
    ck = checkpoint.Checkpointer(str(tmp_path), use_async=True)
    ck.save(state, epoch=2, step_in_epoch=5)
    ck.close()
    restored, epoch, sie = checkpoint.restore_latest_full(
        str(tmp_path), state)
    assert (epoch, sie) == (2, 5)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, restored)


def test_checkpointer_splits_enqueue_and_drain_timing(tmp_path, cfg,
                                                      devices8):
    """Async saves: ``save`` times only the enqueue (snapshot + handoff);
    the serialisation cost surfaces as blocked time at ``wait``/``close``
    and accumulates into ``drain_ms`` — the pair is the checkpoint path's
    honest cost where the old single save_ms under-reported it."""
    mesh = build_mesh(cfg.parallel, devices=devices8)
    state = _state(cfg, mesh)
    ck = checkpoint.Checkpointer(str(tmp_path), use_async=True)
    assert ck.saves == 0 and ck.drain_ms == 0.0
    ck.save(state, epoch=0, step_in_epoch=0)
    assert ck.saves == 1 and ck.last_enqueue_ms > 0
    assert ck.last_save_ms == ck.last_enqueue_ms   # back-compat alias
    ck.wait()
    after_wait = ck.drain_ms
    assert after_wait >= ck.last_drain_ms >= 0
    ck.save(state, epoch=1, step_in_epoch=0)
    ck.close()                                     # close drains too
    assert ck.saves == 2 and ck.drain_ms >= after_wait


def test_restore_full_reads_legacy_epoch_layout(tmp_path, cfg, devices8):
    """A save_dir written by the old epoch-keyed API must stay resumable:
    restore_latest_full falls back to the bare-StandardSave layout and
    reports (epoch+1, 0) as the resume position."""
    mesh = build_mesh(cfg.parallel, devices=devices8)
    state = _state(cfg, mesh)
    checkpoint.save(str(tmp_path), state, epoch=3)
    restored, epoch, sie = checkpoint.restore_latest_full(
        str(tmp_path), state)
    assert (epoch, sie) == (4, 0)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, restored)


def test_restore_latest_honors_step_keyed_resume_meta(tmp_path, cfg,
                                                      devices8):
    """The simple path on a Checkpointer-written (step-keyed) dir must
    honor the (epoch, step_in_epoch) resume metadata: the old code
    returned latest_step + 1 — a GLOBAL step masquerading as an epoch,
    silently restarting training far past the end of the run."""
    mesh = build_mesh(cfg.parallel, devices=devices8)
    state = _state(cfg, mesh)
    state = state._replace(step=state.step + 40)   # global step 40
    ck = checkpoint.Checkpointer(str(tmp_path), use_async=False)
    ck.save(state, epoch=5, step_in_epoch=0)       # resume: epoch 5, batch 0
    ck.close()
    restored, next_epoch = checkpoint.restore_latest(str(tmp_path), state)
    assert next_epoch == 5, \
        f"simple path must honor the resume metadata, got {next_epoch}"
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, restored)


def test_restore_latest_warns_on_midepoch_position(tmp_path, cfg,
                                                   devices8, capfd):
    """A mid-epoch save through the simple API: the returned epoch is
    the one to CONTINUE (conservative restart from batch 0) and a
    warning points at restore_latest_full for the exact position."""
    mesh = build_mesh(cfg.parallel, devices=devices8)
    state = _state(cfg, mesh)
    ck = checkpoint.Checkpointer(str(tmp_path), use_async=False)
    ck.save(state, epoch=2, step_in_epoch=6)
    ck.close()
    _, next_epoch = checkpoint.restore_latest(str(tmp_path), state)
    assert next_epoch == 2
    assert "restore_latest_full" in capfd.readouterr().err


def _final_params(save_dir, cfg, mesh):
    template = _state(cfg, mesh)
    restored, _, _ = checkpoint.restore_latest_full(str(save_dir), template)
    return restored


def test_midepoch_resume_reproduces_trajectory(tmp_path, devices8,
                                               monkeypatch):
    """The preemption drill: kill training mid-epoch (keep only a
    step-granular checkpoint), resume, and the final params must equal the
    uninterrupted run's bit-for-bit (the epoch batch order is stateless by
    (seed, epoch), so skipping the consumed prefix replays the exact
    trajectory)."""
    import shutil
    from tpudist import train as train_lib

    def mk(save_dir, **kw):
        return TrainConfig(batch_size=8, epochs=1, lr=1e-2, seed=3,
                           save_dir=str(save_dir), log_every=0,
                           data=DataConfig(n_samples=64),  # 8 steps/epoch
                           **kw)

    # A: uninterrupted
    train_lib.run(mk(tmp_path / "a"))
    # B: checkpoint every 3 steps (mid-epoch saves at batch 3 and 6),
    # then simulate the preemption by deleting everything after step 6
    train_lib.run(mk(tmp_path / "b", ckpt_every_steps=3))
    steps = sorted(int(p.name) for p in (tmp_path / "b").iterdir()
                   if p.name.isdigit())
    assert 6 in steps, f"expected a mid-epoch save at step 6, got {steps}"
    for s in steps:
        if s > 6:
            shutil.rmtree(tmp_path / "b" / str(s))
    # C: resume — must restart at epoch 0, batch 6 and finish the epoch
    train_lib.run(mk(tmp_path / "b", resume=True))

    cfg = mk(tmp_path / "a")
    mesh = build_mesh(cfg.parallel, devices=devices8)
    pa = _final_params(tmp_path / "a", cfg, mesh)
    pb = _final_params(tmp_path / "b", cfg, mesh)
    assert int(pa.step) == int(pb.step) == 8
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), pa.params, pb.params)

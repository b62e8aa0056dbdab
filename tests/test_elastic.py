"""Elastic preemption survival (tpudist.elastic): sharded manifest
checkpoints, mesh-reshaping resume, and the requeue policy.

The commit-race tests script the kill points a real preemption hits —
between shard write and commit, during the manifest rename, between a
committed step and the next — and pin the invariant the whole subsystem
exists for: a kill at ANY instant leaves either the previous or the
next fully-consistent checkpoint, never a torn one. The drills at the
bottom run the real CLI in subprocesses (a scripted ``os._exit``
preemption cannot run in the pytest process) and assert the acceptance
contract: bitwise-identical continuation on the same mesh, matching
trajectory on a 4→2 reshaped one.
"""

import json
import os
import socket
import subprocess
import sys

import jax
import numpy as np
import pytest

from tpudist import engine, verdict
from tpudist.config import DataConfig, ParallelConfig, TrainConfig
from tpudist.elastic import ckpt as eck
from tpudist.elastic import policy
from tpudist.elastic import resume as eres
from tpudist.parallel import build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(fsdp=1, data=1):
    return TrainConfig(batch_size=32, data=DataConfig(n_samples=64),
                      parallel=ParallelConfig(data=data, fsdp=fsdp))


def _state(cfg, mesh, seed=0):
    return engine.init_state(jax.random.PRNGKey(seed), cfg, mesh)


def _assert_tree_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


# ------------------------------------------------- manifest + reshard


def test_manifest_commit_and_bitwise_roundtrip(tmp_path, devices8):
    cfg = _cfg(fsdp=4)
    mesh = build_mesh(cfg.parallel, devices=devices8[:4])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False,
                                 run_meta={"seed": 42, "batch_size": 32})
    ck.save(state, epoch=2, step_in_epoch=5)
    ck.close()
    man = eck.latest_manifest(str(tmp_path))
    assert man["schema"] == eck.MANIFEST_SCHEMA_VERSION
    assert (man["epoch"], man["step_in_epoch"]) == (2, 5)
    assert man["run"] == {"seed": 42, "batch_size": 32}
    restored, epoch, sie = eres.restore(
        str(tmp_path), state, run_meta={"seed": 42, "batch_size": 32})
    assert (epoch, sie) == (2, 5)
    _assert_tree_equal(state, restored)


def test_async_save_commits_after_drain(tmp_path, devices8):
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=True)
    ck.save(state, epoch=1, step_in_epoch=0)
    assert ck.saves == 1 and ck.last_enqueue_ms > 0
    assert ck.last_save_ms == ck.last_enqueue_ms     # Checkpointer alias
    ck.wait()
    assert ck.drain_ms >= ck.last_drain_ms >= 0
    ck.close()
    assert ck.commits == 1 and ck.write_errors == 0
    restored, _, _ = eres.restore(str(tmp_path), state)
    _assert_tree_equal(state, restored)


@pytest.mark.parametrize("target", [2, 1, 8])
def test_reshard_restore_onto_different_device_count(tmp_path, devices8,
                                                     target):
    """The elastic primitive: a checkpoint sharded over 4 devices
    restores bitwise onto 2, 1, and 8 — per-leaf slice assembly maps
    saved spans onto whatever layout the template pins."""
    cfg = _cfg(fsdp=4)
    mesh = build_mesh(cfg.parallel, devices=devices8[:4])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.save(state, epoch=0, step_in_epoch=0)
    ck.close()
    tcfg = _cfg(fsdp=target)
    tmesh = build_mesh(tcfg.parallel, devices=devices8[:target])
    template = _state(tcfg, tmesh, seed=9)        # different init values
    restored, _, _ = eres.restore(str(tmp_path), template)
    _assert_tree_equal(state, restored)
    # and the restored arrays carry the TARGET layout, not the saved one
    assert (restored.params["fc1"]["w"].sharding.num_devices == target)


def test_replicated_leaves_written_once(tmp_path, devices8):
    """Pure-DP layout: every param is replicated over 4 devices — the
    shard files must store ONE copy per leaf, not four (the dedupe by
    lowest-ranked owner)."""
    cfg = _cfg(data=4)
    mesh = build_mesh(cfg.parallel, devices=devices8[:4])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.save(state, epoch=0, step_in_epoch=0)
    ck.close()
    d = eck.step_dir(eck.elastic_root(str(tmp_path)), int(state.step))
    with open(os.path.join(d, eck.index_name(0))) as f:
        idx = json.load(f)
    for name, rec in idx["leaves"].items():
        assert len(rec["shards"]) == 1, (name, rec)


def test_bfloat16_leaves_roundtrip_bitwise(tmp_path, devices8):
    """Mixed-precision states carry ml_dtypes bfloat16 mu leaves, which
    the npy format stores as raw void bytes — restore must reinterpret
    them bit-exactly, same-mesh and resharded."""
    cfg = TrainConfig(batch_size=32, dtype="bfloat16",
                      adam_nu_dtype="bfloat16",
                      data=DataConfig(n_samples=64),
                      parallel=ParallelConfig(data=1, fsdp=4))
    mesh = build_mesh(cfg.parallel, devices=devices8[:4])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.save(state, epoch=0, step_in_epoch=0)
    ck.close()
    restored, _, _ = eres.restore(str(tmp_path), state)
    _assert_tree_equal(state, restored)
    half = TrainConfig(batch_size=32, dtype="bfloat16",
                       adam_nu_dtype="bfloat16",
                       data=DataConfig(n_samples=64),
                       parallel=ParallelConfig(data=1, fsdp=2))
    hmesh = build_mesh(half.parallel, devices=devices8[:2])
    tmpl = _state(half, hmesh, seed=5)
    resharded, _, _ = eres.restore(str(tmp_path), tmpl)
    _assert_tree_equal(state, resharded)


# ------------------------------------------------------- commit races


def test_kill_between_shard_write_and_commit(tmp_path, devices8):
    """Shards of step N+1 land but the commit never runs (the scripted
    kill point): the previous manifest stays authoritative, restore
    reads the committed step, and the orphan dir is reaped on the next
    open."""
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.save(state, epoch=1, step_in_epoch=0)
    ck.close()

    class KilledBeforeCommit(eck.ShardedCheckpointer):
        def _commit(self, *a, **kw):
            raise SystemExit("scripted kill before commit")

    later = _state(cfg, mesh, seed=1)._replace(
        step=state.step + 7)
    torn = KilledBeforeCommit(str(tmp_path), use_async=False)
    with pytest.raises(SystemExit):
        torn.save(later, epoch=2, step_in_epoch=0)
    man = eck.latest_manifest(str(tmp_path))
    assert (int(man["step"]), man["epoch"]) == (int(state.step), 1)
    restored, epoch, _ = eres.restore(str(tmp_path), state)
    assert epoch == 1
    _assert_tree_equal(state, restored)
    orphan = eck.step_dir(eck.elastic_root(str(tmp_path)), int(later.step))
    assert os.path.isdir(orphan)
    fresh = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    fresh.close()
    assert not os.path.isdir(orphan), \
        "next open must reap the uncommitted step dir"


def test_kill_during_manifest_rename_ignores_tmp(tmp_path, devices8):
    """A kill mid-commit leaves ``manifest.json.tmp`` next to the valid
    manifest: the loader must read only the committed file, and the next
    open reaps the tmp."""
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.save(state, epoch=3, step_in_epoch=0)
    ck.close()
    torn = eck.manifest_path(str(tmp_path)) + ".tmp"
    with open(torn, "w") as f:
        f.write('{"step": 999999, "epoch":')      # torn mid-write
    man = eck.latest_manifest(str(tmp_path))
    assert man["epoch"] == 3, "tmp manifest must be invisible"
    removed = eck.cleanup_stale(str(tmp_path))
    assert torn in removed and not os.path.exists(torn)
    restored, epoch, _ = eres.restore(str(tmp_path), state)
    assert epoch == 3
    _assert_tree_equal(state, restored)


def test_commit_waits_for_every_workers_shards(tmp_path, devices8):
    """process_count=2: the coordinator must NOT commit while worker
    1's shard index is missing (bounded wait, previous manifest stays),
    and must commit once it lands — the filesystem rendezvous that
    replaces a collective barrier."""
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck0 = eck.ShardedCheckpointer(str(tmp_path), process_index=0,
                                  process_count=2, use_async=False,
                                  commit_timeout_s=0.2)
    ck0.save(state, epoch=0, step_in_epoch=0)
    assert ck0.commit_failures == 1 and ck0.commits == 0
    assert eck.latest_manifest(str(tmp_path)) is None
    # worker 1's writer lands its (possibly empty) shard set...
    ck1 = eck.ShardedCheckpointer(str(tmp_path), process_index=1,
                                  process_count=2, use_async=False)
    ck1.save(state, epoch=0, step_in_epoch=0)
    ck1.close()
    # ...and the coordinator's next save of the same step commits
    ck0.save(state, epoch=0, step_in_epoch=0)
    ck0.close()
    assert ck0.commits == 1
    man = eck.latest_manifest(str(tmp_path))
    assert man is not None and man["process_count"] == 2
    restored, _, _ = eres.restore(str(tmp_path), state)
    _assert_tree_equal(state, restored)


def _corrupt_npz(save_dir, step, *, truncate=False, worker=0):
    """Damage a committed step's shard file in place: mid-file byte
    flips (crc-detectable wrong data) or truncation (unreadable zip)."""
    path = os.path.join(eck.step_dir(eck.elastic_root(save_dir), step),
                        eck.shards_name(worker))
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        if truncate:
            f.truncate(size // 2)
            return path
        for pos in range(size // 2, size // 2 + 8):
            f.seek(pos)
            b = f.read(1)
            f.seek(pos)
            f.write(bytes([b[0] ^ 0xFF]))
    return path


def test_shard_index_records_crc32(tmp_path, devices8):
    """Every shard row in the index carries the crc32 of its raw bytes
    — the integrity record restore verifies before trusting the
    checkpoint (a corrupt shard must be DETECTED, never resumed)."""
    import zlib
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.save(state, epoch=0, step_in_epoch=0)
    ck.close()
    d = eck.step_dir(eck.elastic_root(str(tmp_path)), int(state.step))
    with open(os.path.join(d, eck.index_name(0))) as f:
        idx = json.load(f)
    with np.load(os.path.join(d, eck.shards_name(0))) as npz:
        for name, rec in idx["leaves"].items():
            for sh in rec["shards"]:
                assert isinstance(sh["crc32"], int), (name, sh)
                got = zlib.crc32(np.asarray(npz[sh["key"]]).tobytes()) \
                    & 0xFFFFFFFF
                assert got == sh["crc32"], name


def test_committed_manifests_newest_first(tmp_path, devices8):
    """Each commit leaves a per-step manifest copy; the listing returns
    them newest-first, capped at the top-level manifest (an uncommitted
    newer step dir must not appear)."""
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    for i in range(3):
        ck.save(state._replace(step=state.step + i), epoch=i,
                step_in_epoch=0)
    ck.close()
    mans = eck.committed_manifests(str(tmp_path))
    assert [int(m["step"]) for m in mans] == [2, 1, 0]
    assert mans[0] == eck.latest_manifest(str(tmp_path))


def test_restore_falls_back_to_previous_committed_on_corruption(
        tmp_path, devices8):
    """THE corrupt-shard contract (satellite): the newest committed
    manifest's shard is corrupted on disk — restore must crc-reject it
    and land on the OLDER committed step, flagging fallback_from and
    the corrupt shard in the details dict the train loop folds into
    kind=resume, instead of raising or fresh-starting."""
    cfg = _cfg(fsdp=4)
    mesh = build_mesh(cfg.parallel, devices=devices8[:4])
    s_old = _state(cfg, mesh, seed=1)
    s_new = _state(cfg, mesh, seed=2)._replace(step=s_old.step + 6)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.save(s_old, epoch=0, step_in_epoch=3)
    ck.save(s_new, epoch=0, step_in_epoch=6)
    ck.close()
    _corrupt_npz(str(tmp_path), int(s_new.step))
    details = {}
    restored, epoch, sie = eres.restore(str(tmp_path), s_old,
                                        details=details)
    assert (epoch, sie) == (0, 3), (epoch, sie)
    _assert_tree_equal(s_old, restored)
    assert details["fallback_from"] == int(s_new.step)
    # either detection layer may trip first (the npz zip's own member
    # crc, or our recorded shard crc32) — both read as corruption
    assert "corrupt" in details["corrupt_shard"]


def test_restore_falls_back_on_truncated_shard(tmp_path, devices8):
    """A TRUNCATED shard file (unreadable zip, the other damage shape)
    takes the same fallback path as a bit flip."""
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    s_old = _state(cfg, mesh, seed=1)
    s_new = _state(cfg, mesh, seed=2)._replace(step=s_old.step + 3)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.save(s_old, epoch=0, step_in_epoch=3)
    ck.save(s_new, epoch=0, step_in_epoch=6)
    ck.close()
    _corrupt_npz(str(tmp_path), int(s_new.step), truncate=True)
    details = {}
    restored, _, sie = eres.restore(str(tmp_path), s_old,
                                    details=details)
    assert sie == 3
    _assert_tree_equal(s_old, restored)
    assert details["fallback_from"] == int(s_new.step)


def test_recorded_crc_catches_mismatched_bytes(tmp_path, devices8):
    """The recorded-crc layer specifically (the npz zip's own member
    crc can't see this shape): the shard index claims a different
    crc32 than the bytes on disk — e.g. a stale index paired with a
    rewritten shard file — and restore must reject it."""
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.save(state, epoch=0, step_in_epoch=3)
    ck.close()
    d = eck.step_dir(eck.elastic_root(str(tmp_path)), int(state.step))
    ipath = os.path.join(d, eck.index_name(0))
    with open(ipath) as f:
        idx = json.load(f)
    first = next(iter(idx["leaves"].values()))["shards"][0]
    first["crc32"] = (first["crc32"] + 1) & 0xFFFFFFFF
    with open(ipath, "w") as f:
        json.dump(idx, f)
    with pytest.raises(eres.ShardCorruptionError, match="crc32"):
        eres.restore(str(tmp_path), state)


def test_restore_raises_when_every_manifest_corrupt(tmp_path, devices8):
    """No restorable history left: the newest manifest's corruption
    error propagates (ShardCorruptionError is a ResumeError, so
    --resume auto degrades it to a flagged fresh start)."""
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.save(state, epoch=0, step_in_epoch=3)
    ck.close()
    _corrupt_npz(str(tmp_path), int(state.step))
    with pytest.raises(eres.ShardCorruptionError):
        eres.restore(str(tmp_path), state)


def test_fs_error_retry_then_skip_never_raises(tmp_path, devices8):
    """Transient-fs-error hardening: EIO on the first attempts retries
    away (the save commits); exhaustion ABANDONS that step's commit —
    counted, logged, never raised into the caller and never a wedged
    writer — and a later save commits normally."""
    import errno
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
    ck.write_retry_backoff_s = 0.001
    fails = {"n": 2}

    def hook(point, **ctx):
        if point == "shard_write" and fails["n"] > 0:
            fails["n"] -= 1
            raise OSError(errno.EIO, "scripted transient EIO")
    eck.set_fault_hook(hook)
    try:
        ck.save(state, epoch=0, step_in_epoch=3)
        assert ck.write_retries == 2 and ck.write_errors == 0
        assert int(eck.latest_manifest(str(tmp_path))["step"]) \
            == int(state.step)
        # exhaustion: more failures than retries -> skip, don't raise
        fails["n"] = 99
        later = state._replace(step=state.step + 3)
        ck.save(later, epoch=0, step_in_epoch=6)
        assert ck.write_errors == 1 and ck.write_skips == 1
        assert int(eck.latest_manifest(str(tmp_path))["step"]) \
            == int(state.step), "skipped save must not move the manifest"
        # the writer is NOT wedged: the next save commits
        fails["n"] = 0
        final = state._replace(step=state.step + 5)
        ck.save(final, epoch=1, step_in_epoch=0)
        assert int(eck.latest_manifest(str(tmp_path))["step"]) \
            == int(final.step)
    finally:
        eck.set_fault_hook(None)
        ck.close()


def test_commit_rendezvous_ignores_stale_attempt_indexes(tmp_path,
                                                         devices8):
    """A corruption-FALLBACK resume re-reaches steps whose committed
    dir still holds the dead attempt's shard indexes (cleanup_stale
    only reaps dirs NEWER than the manifest) — the rendezvous must NOT
    let a peer's stale index satisfy this attempt's commit, or the
    manifest would flip onto the very bytes the fallback rejected. The
    index stamps its attempt; the commit waits for a fresh one."""
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    # attempt 0: both workers land, the commit flips to epoch 1 (both
    # constructed BEFORE any save: the coordinator's open-time
    # cleanup_stale reaps uncommitted step dirs, including a peer's
    # in-flight one — the same ordering a real pod gets)
    cks = [eck.ShardedCheckpointer(
        str(tmp_path), process_index=pi, process_count=2,
        use_async=False, commit_timeout_s=0.2,
        run_meta={"requeue_attempt": 0}) for pi in (0, 1)]
    for ck in reversed(cks):             # worker 1 lands first
        ck.save(state, epoch=1, step_in_epoch=0)
        ck.close()
    assert eck.latest_manifest(str(tmp_path))["epoch"] == 1
    # attempt 1 re-reaches the SAME step; only the coordinator has
    # rewritten — worker 1's index is the dead attempt's leftover
    ck0 = eck.ShardedCheckpointer(
        str(tmp_path), process_index=0, process_count=2,
        use_async=False, commit_timeout_s=0.2,
        run_meta={"requeue_attempt": 1})
    ck0.save(state, epoch=2, step_in_epoch=0)
    assert ck0.commit_failures == 1 and ck0.commits == 0
    assert eck.latest_manifest(str(tmp_path))["epoch"] == 1, \
        "stale peer index must not satisfy the new attempt's commit"
    # worker 1's fresh (attempt-1) write lands -> the commit proceeds
    ck1 = eck.ShardedCheckpointer(
        str(tmp_path), process_index=1, process_count=2,
        use_async=False, run_meta={"requeue_attempt": 1})
    ck1.save(state, epoch=2, step_in_epoch=0)
    ck1.close()
    ck0.save(state, epoch=2, step_in_epoch=0)
    ck0.close()
    assert ck0.commits == 1
    assert eck.latest_manifest(str(tmp_path))["epoch"] == 2


def test_grace_kill_rc137_with_stall_record_is_stall(tmp_path):
    """The `timeout -k` escalation: a wedged run ignores SIGTERM and
    eats SIGKILL (rc 137) AFTER the watchdog dumped its stall flight
    record — the policy must classify that as STALL (the requeue path
    with the stall diagnosis), not a bare preemption and never a
    crash."""
    d = tmp_path / "fr"
    d.mkdir()
    (d / "flightrec.worker0").write_text(json.dumps(
        {"reason": "stall", "stall_s": 312.4,
         "progress": {"phase": "train", "step": 41}}))
    assert policy.classify(137, flightrec_dir=str(d)) == policy.STALL
    dec = policy.decide(137, attempt=0, max_requeues=3,
                        flightrec_dir=str(d))
    assert dec.verdict == policy.STALL and dec.requeue
    # without the stall record the same rc stays a plain preemption
    assert policy.classify(137) == policy.PREEMPTION


def test_retention_keeps_last_k_committed(tmp_path, devices8):
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False, keep=2)
    for i in range(5):
        ck.save(state._replace(step=state.step + i), epoch=i,
                step_in_epoch=0)
    ck.close()
    sdir = os.path.join(eck.elastic_root(str(tmp_path)), "steps")
    kept = sorted(int(n) for n in os.listdir(sdir))
    assert kept == [3, 4], kept
    man = eck.latest_manifest(str(tmp_path))
    assert int(man["step"]) == 4


def test_data_cursor_validation_refuses_mismatch(tmp_path, devices8):
    """Resuming under a different seed/batch replays a DIFFERENT epoch
    permutation — the restore must refuse, not silently continue an
    unrelated trajectory."""
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    state = _state(cfg, mesh)
    ck = eck.ShardedCheckpointer(
        str(tmp_path), use_async=False,
        run_meta={"seed": 42, "batch_size": 32})
    ck.save(state, epoch=0, step_in_epoch=0)
    ck.close()
    with pytest.raises(eres.ResumeError, match="seed"):
        eres.restore(str(tmp_path), state,
                     run_meta={"seed": 43, "batch_size": 32})
    with pytest.raises(eres.ResumeError, match="batch_size"):
        eres.restore(str(tmp_path), state,
                     run_meta={"seed": 42, "batch_size": 64})
    # matching (or absent) cursor restores fine
    assert eres.restore(str(tmp_path), state) is not None


def test_restore_for_resume_newest_wins_with_orbax_fallback(tmp_path,
                                                            devices8):
    """Elastic manifest and orbax steps can coexist in one save dir:
    the resume pick is newest-wins by checkpoint key, and a manifest
    that cannot restore falls back to orbax instead of erroring or
    discarding real progress."""
    from tpudist import checkpoint as ckpt_lib
    cfg = _cfg(fsdp=2)
    mesh = build_mesh(cfg.parallel, devices=devices8[:2])
    s_orbax = _state(cfg, mesh, seed=1)
    s_manifest = _state(cfg, mesh, seed=2)._replace(
        step=_state(cfg, mesh).step + 10)
    # orbax only -> orbax source
    ckpt_lib.save(str(tmp_path), s_orbax, epoch=3)
    out = eres.restore_for_resume(str(tmp_path), s_orbax)
    assert out is not None and out[3] == "orbax" and out[1] == 4
    # a NEWER committed manifest (step 10 vs orbax key 3) wins
    ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False,
                                 run_meta={"seed": 42})
    ck.save(s_manifest, epoch=7, step_in_epoch=2)
    ck.close()
    state, epoch, sie, src = eres.restore_for_resume(str(tmp_path),
                                                     s_orbax)
    assert (src, epoch, sie) == ("manifest", 7, 2)
    _assert_tree_equal(s_manifest, state)
    # an OLDER manifest must not shadow newer orbax progress
    ck2 = eck.ShardedCheckpointer(str(tmp_path / "old"), use_async=False)
    ck2.save(_state(cfg, mesh, seed=4), epoch=0, step_in_epoch=0)  # step 0
    ck2.close()
    ckpt_lib.save(str(tmp_path / "old"), s_orbax, epoch=3)
    out = eres.restore_for_resume(str(tmp_path / "old"), s_orbax)
    assert out is not None and out[3] == "orbax" and out[1] == 4
    # a manifest that cannot restore (data-cursor mismatch) falls back
    # to orbax rather than raising past a perfectly good checkpoint
    state, epoch, sie, src = eres.restore_for_resume(
        str(tmp_path), s_orbax, run_meta={"seed": 999})
    assert src == "orbax" and epoch == 4, (src, epoch)
    # ...but with NO orbax fallback the manifest's error propagates
    ck3 = eck.ShardedCheckpointer(str(tmp_path / "manifest_only"),
                                  use_async=False, run_meta={"seed": 42})
    ck3.save(s_manifest, epoch=1, step_in_epoch=0)
    ck3.close()
    with pytest.raises(eres.ResumeError):
        eres.restore_for_resume(str(tmp_path / "manifest_only"),
                                s_orbax, run_meta={"seed": 999})
    # neither -> None (fresh start)
    assert eres.restore_for_resume(str(tmp_path / "void"), s_orbax) is None


# ------------------------------------------------------ requeue policy


def test_policy_classification_table(tmp_path):
    assert policy.classify(0) == policy.SUCCESS
    assert policy.classify(124) == policy.STALL
    for rc in (137, 143, 130):
        assert policy.classify(rc) == policy.PREEMPTION
    assert policy.classify(1) == policy.CRASH
    # a stall flight record upgrades any rc to STALL
    rec_dir = tmp_path / "fr"
    rec_dir.mkdir()
    (rec_dir / "flightrec.worker1").write_text(
        json.dumps({"reason": "stall", "progress": {}}))
    assert policy.classify(1, flightrec_dir=str(rec_dir)) == policy.STALL
    # a vanished worker (missing per-worker verdict) means preemption
    v = tmp_path / "job_status.txt"
    (tmp_path / "job_status.txt.worker0").write_text("success")
    assert policy.classify(1, verdict_path=str(v),
                           nprocs=2) == policy.PREEMPTION
    (tmp_path / "job_status.txt.worker1").write_text("fail")
    assert policy.classify(1, verdict_path=str(v), nprocs=2) == policy.CRASH
    # torn flight records are not evidence
    (rec_dir / "flightrec.worker2").write_text("{torn")
    assert policy.classify(137, flightrec_dir=str(rec_dir)) == policy.STALL
    # ssh/gcloud failing to reach a previously-reachable worker VM
    assert policy.classify(255) == policy.PREEMPTION


def test_policy_vanished_worker_inference_from_artifacts(tmp_path):
    """No --verdict/--nprocs wiring needed: a worker with a heartbeat
    beacon but no per-worker verdict file in the collected artifacts
    died un-orderly — the production launcher path for spotting a
    preempted worker behind a generic rc=1."""
    d = tmp_path / "artifacts"
    d.mkdir()
    for i in range(3):
        (d / f"heartbeat.worker{i}").write_text("{}")
    (d / "job_status.txt.worker0").write_text("success")
    (d / "job_status.txt.worker1").write_text("success")
    assert policy.vanished_workers(str(d)) == [2]
    assert policy.classify(1, flightrec_dir=str(d)) == policy.PREEMPTION
    # every worker exited orderly -> a real crash
    (d / "job_status.txt.worker2").write_text("fail")
    assert policy.vanished_workers(str(d)) == []
    assert policy.classify(1, flightrec_dir=str(d)) == policy.CRASH
    # no beacons at all -> nothing to infer from
    assert policy.vanished_workers(str(tmp_path)) == []


def test_report_fail_resume_says_started_fresh():
    """A failed restore degraded to a fresh start must not render as
    'continued from global step 0' in the report header."""
    from tpudist.obs import report as report_mod
    metrics = [{"kind": "resume", "status": "fail", "source": None,
                "epoch": 0, "step_in_epoch": 0, "resumed_from_step": 0,
                "steps_lost": None, "requeue_attempt": 2,
                "error": "ResumeError('torn')"}]
    rep = report_mod.build_report(metrics, {"traceEvents": []})
    assert rep["run"]["resume_status"] == "fail"
    md = report_mod.to_markdown(rep)
    line = [l for l in md.splitlines() if "resume:" in l][0]
    assert "started fresh" in line and "requeue attempt 2" in line
    assert "continued" not in line


def test_policy_backoff_and_budget():
    assert policy.backoff_s(0) == 10.0
    assert policy.backoff_s(3) == 80.0
    assert policy.backoff_s(10) == 300.0          # capped
    d = policy.decide(137, attempt=1, max_requeues=3)
    assert d.requeue and d.backoff_s == 20.0
    assert not policy.decide(137, attempt=3, max_requeues=3).requeue
    assert not policy.decide(1, attempt=0, max_requeues=3).requeue
    assert not policy.decide(0, attempt=0, max_requeues=3).requeue


def test_policy_cli_contract(capsys):
    rc = policy.main(["--rc", "137", "--attempt", "0",
                      "--max-requeues", "2", "--backoff-base-s", "5"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "VERDICT=preemption" in out and "REQUEUE=1" in out
    assert "BACKOFF_S=5" in out
    rc = policy.main(["--rc", "1", "--attempt", "0", "--max-requeues", "2"])
    out = capsys.readouterr().out
    assert rc == 1 and "REQUEUE=0" in out


def test_policy_is_importable_without_jax():
    """The launcher runs the policy on a CI host with no accelerator
    stack — the module (and the tpudist package roots above it) must
    import with jax AND numpy blocked."""
    code = ("import sys; sys.modules['jax'] = None; "
            "sys.modules['numpy'] = None; "
            "from tpudist.elastic import policy; "
            "d = policy.decide(137, attempt=0, max_requeues=1); "
            "assert d.requeue; print('ok')")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr


def test_resume_status_verdict():
    assert verdict.resume_status(False, False) == verdict.UNGATEABLE
    assert verdict.resume_status(True, False) == verdict.UNGATEABLE
    assert verdict.resume_status(True, True) == verdict.SUCCESS
    assert verdict.resume_status(True, False, error=True) == verdict.FAIL


def test_beacon_namespaced_by_requeue_attempt(tmp_path):
    """Attempt N's flight recorder must never let attempt N-1's beacon
    read as its own progress: a stale beacon in a shared obs dir is
    archived to heartbeat.worker<i>.attempt<K> (K from the STALE
    payload's own stamp) before the first write, and the fresh beacon
    carries the new attempt — the goodput ledger reads the archive for
    lost-step math, the launcher's per-attempt classification reads
    only current-attempt beacons."""
    from tpudist.obs.heartbeat import FlightRecorder

    # attempt 0 beats and dies (no close — a preemption)
    r0 = FlightRecorder(str(tmp_path), stall_timeout_s=0,
                        process_index=0, requeue_attempt=0)
    r0.note_progress(phase="train", epoch=0, step=5)
    r0.beacon_now()
    r0._stop.set()                     # thread down, beacon left behind
    with open(r0.beacon_path) as f:
        assert json.load(f)["requeue_attempt"] == 0

    # attempt 1 starts in the same dir: the stale beacon is archived,
    # its progress counters intact, and the live beacon is attempt 1's
    r1 = FlightRecorder(str(tmp_path), stall_timeout_s=0,
                        process_index=0, requeue_attempt=1)
    archived = os.path.join(str(tmp_path), "heartbeat.worker0.attempt0")
    assert os.path.exists(archived), os.listdir(str(tmp_path))
    with open(archived) as f:
        old = json.load(f)
    assert old["step"] == 5 and old["requeue_attempt"] == 0
    r1.note_progress(phase="train", epoch=0, step=3)
    r1.beacon_now()
    with open(r1.beacon_path) as f:
        fresh = json.load(f)
    assert fresh["requeue_attempt"] == 1 and fresh["step"] == 3
    r1.close()
    # same attempt restarting in place does NOT archive (overwrite wins)
    r1b = FlightRecorder(str(tmp_path), stall_timeout_s=0,
                         process_index=0, requeue_attempt=1)
    assert not os.path.exists(r1.beacon_path + ".attempt1")
    r1b.close()


def test_policy_vanished_workers_scoped_to_attempt(tmp_path):
    """A worker that never STARTED in attempt 1 leaves only its
    attempt-0 beacon behind; scoping the vanished-worker inference to
    the attempt under classification must ignore it — while beacons
    too old to carry the stamp keep the pre-namespacing behavior."""
    d = tmp_path / "artifacts"
    d.mkdir()
    (d / "heartbeat.worker0").write_text(
        json.dumps({"step": 4, "requeue_attempt": 1}))
    (d / "heartbeat.worker1").write_text(
        json.dumps({"step": 9, "requeue_attempt": 0}))   # stale
    # archived beacons are never evidence for ANY attempt
    (d / "heartbeat.worker1.attempt0").write_text(
        json.dumps({"step": 9, "requeue_attempt": 0}))
    assert policy.vanished_workers(str(d), attempt=1) == [0]
    # unscoped keeps the old behavior: both plain beacons count
    assert policy.vanished_workers(str(d)) == [0, 1]
    # an unstamped (old-format) beacon still counts under scoping
    (d / "heartbeat.worker2").write_text(json.dumps({"step": 1}))
    assert policy.vanished_workers(str(d), attempt=1) == [0, 2]
    # and decide() threads its attempt through to the classification
    dec = policy.decide(1, attempt=1, max_requeues=3,
                        flightrec_dir=str(d))
    assert dec.verdict == policy.PREEMPTION and dec.requeue


# --------------------------------------------------- preemption drills


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _launch(rank, port, nprocs, save_dir, extra, devices_per_proc=2,
            env_extra=None):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(f"--xla_force_host_platform_device_count="
                   f"{devices_per_proc}"),
    )
    env.update(env_extra or {})
    if nprocs > 1:
        env.update(
            TPUDIST_COORDINATOR=f"localhost:{port}",
            TPUDIST_NUM_PROCESSES=str(nprocs),
            TPUDIST_PROCESS_ID=str(rank),
        )
    return subprocess.Popen(
        [sys.executable, "-m", "tpudist.train",
         "--save-dir", save_dir, *extra],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)


def _run_world(save_dir, extra, nprocs=1, devices_per_proc=2,
               env_extra=None, timeout=300):
    port = _free_port()
    procs = [_launch(r, port, nprocs, save_dir, extra,
                     devices_per_proc=devices_per_proc,
                     env_extra=env_extra)
             for r in range(nprocs)]
    outs, rcs = [], []
    for p in procs:
        out, _ = p.communicate(timeout=timeout)
        outs.append(out)
        rcs.append(p.returncode)
    return rcs, outs


_DRILL = ["--epochs", "1", "--train-batch-size", "8", "--n-samples", "64",
          "--log-every", "0", "--lr", "1e-2", "--seed", "3",
          "--ckpt-mode", "sharded", "--ckpt-sync"]


def _final_state(save_dir, devices):
    """Restore a drill run's final committed state onto a 1-device mesh
    — the comparison layout; restore reshard-assembles from whatever
    topology wrote the manifest."""
    cfg = TrainConfig(batch_size=8, data=DataConfig(n_samples=64),
                      parallel=ParallelConfig(data=1))
    mesh = build_mesh(cfg.parallel, devices=devices[:1])
    template = _state(cfg, mesh)
    out = eres.restore(save_dir, template)
    assert out is not None, f"no committed manifest under {save_dir}"
    return out[0]


def test_preemption_drill_single_process_bitwise(tmp_path, devices8):
    """THE acceptance drill, single-host edition: a scripted preemption
    (os._exit — no finally blocks, no drain) kills training mid-epoch
    after a committed step-granular save; the requeued ``--resume auto``
    run must continue from the last committed manifest and produce
    final params BITWISE-identical to an uninterrupted run."""
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    rcs, outs = _run_world(a, _DRILL + ["--ckpt-every-steps", "3"])
    assert rcs == [0], outs
    # the preemption: every rank dies at epoch 0 once step >= 5 (the
    # k=3 superstep fires it at step 6, after the step-3 save committed)
    rcs, outs = _run_world(b, _DRILL + ["--ckpt-every-steps", "3"],
                           env_extra={"TPUDIST_TEST_KILL": "0:5"})
    assert rcs == [113], outs               # the scripted kill's code
    man = eck.latest_manifest(b)
    assert man is not None and man["step_in_epoch"] == 3, man
    rcs, outs = _run_world(b, _DRILL + ["--ckpt-every-steps", "3",
                                        "--resume", "auto"])
    assert rcs == [0], outs
    assert "Resumed at epoch 0, step 3" in outs[0], outs[0]
    assert "tpudist: resume success (manifest)" in outs[0], outs[0]
    pa = _final_state(a, devices8[:2])
    pb = _final_state(b, devices8[:2])
    assert int(pa.step) == int(pb.step) == 8
    _assert_tree_equal(pa.params, pb.params)


def test_reshard_resume_4_to_2_devices(tmp_path, devices8):
    """The elastic drill every backend can run: a 4-device run is
    preempted mid-epoch and comes back on TWO devices — same global
    batch, half the data-parallel shards. Continuation is LOSS-CORRECT,
    not bitwise: halving the shard count regroups the gradient psum, so
    final params agree to f32-ULP tolerance while the step count and
    trajectory match exactly. (The process-level 4→2 edition below
    needs a multiprocess-capable CPU backend and is marked slow, like
    tests/test_multiprocess.py.) Artifacts land in
    $TPUDIST_ELASTIC_DRILL_DIR when set — the CI elastic lane uploads
    the manifest/metrics it leaves behind."""
    base = os.environ.get("TPUDIST_ELASTIC_DRILL_DIR") or str(tmp_path)
    os.makedirs(base, exist_ok=True)
    a, b = os.path.join(base, "a"), os.path.join(base, "b")
    rcs, outs = _run_world(a, _DRILL + ["--ckpt-every-steps", "3"],
                           devices_per_proc=4)
    assert rcs == [0], outs
    rcs, outs = _run_world(b, _DRILL + ["--ckpt-every-steps", "3"],
                           devices_per_proc=4,
                           env_extra={"TPUDIST_TEST_KILL": "0:5"})
    assert rcs == [113], outs
    rcs, outs = _run_world(b, _DRILL + ["--ckpt-every-steps", "3",
                                        "--resume", "auto"],
                           devices_per_proc=2)
    assert rcs == [0], outs
    assert "tpudist: resume success (manifest)" in outs[0], outs[0]
    pa = _final_state(a, devices8)
    pb = _final_state(b, devices8)
    assert int(pa.step) == int(pb.step) == 8
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), atol=1e-6, rtol=1e-6),
        pa.params, pb.params)


@pytest.mark.slow
def test_preemption_drill_two_process_bitwise(tmp_path, devices8):
    """The pod edition: 2 processes × 2 devices, whole-slice preemption
    (a spot reaper kills every worker), auto-resume on the same
    topology → bitwise-identical final params vs uninterrupted."""
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    rcs, outs = _run_world(a, _DRILL + ["--ckpt-every-steps", "3"],
                           nprocs=2)
    assert rcs == [0, 0], outs
    rcs, outs = _run_world(b, _DRILL + ["--ckpt-every-steps", "3"],
                           nprocs=2,
                           env_extra={"TPUDIST_TEST_KILL": "0:5"})
    assert rcs == [113, 113], outs
    rcs, outs = _run_world(b, _DRILL + ["--ckpt-every-steps", "3",
                                        "--resume", "auto"], nprocs=2)
    assert rcs == [0, 0], outs
    assert "tpudist: resume success (manifest)" in outs[0], outs[0]
    pa = _final_state(a, devices8[:4])
    pb = _final_state(b, devices8[:4])
    assert int(pa.step) == int(pb.step) == 8
    _assert_tree_equal(pa.params, pb.params)


@pytest.mark.slow
def test_reshard_resume_4_to_2_processes(tmp_path, devices8):
    """The ELASTIC drill: a 4-process run is preempted mid-epoch; the
    job comes back on TWO processes (2 devices each — the same 4-chip
    math re-hosted, the post-preemption shape where half the hosts
    return) and must continue to the same final state."""
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    rcs, outs = _run_world(a, _DRILL + ["--ckpt-every-steps", "3"],
                           nprocs=4, devices_per_proc=1)
    assert rcs == [0, 0, 0, 0], outs
    rcs, outs = _run_world(b, _DRILL + ["--ckpt-every-steps", "3"],
                           nprocs=4, devices_per_proc=1,
                           env_extra={"TPUDIST_TEST_KILL": "0:5"})
    assert rcs == [113] * 4, outs
    man = eck.latest_manifest(b)
    assert man is not None and man["process_count"] == 4
    # resume on 2 processes x 2 devices: the manifest's 4-way shard
    # files reassemble onto the new topology
    rcs, outs = _run_world(b, _DRILL + ["--ckpt-every-steps", "3",
                                        "--resume", "auto"], nprocs=2,
                           devices_per_proc=2)
    assert rcs == [0, 0], outs
    assert "tpudist: resume success (manifest)" in outs[0], outs[0]
    pa = _final_state(a, devices8[:4])
    pb = _final_state(b, devices8[:4])
    assert int(pa.step) == int(pb.step) == 8
    _assert_tree_equal(pa.params, pb.params)

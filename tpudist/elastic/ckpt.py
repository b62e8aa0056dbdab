"""Sharded manifest checkpoints: each worker writes only its own shards.

Orbax (tpudist.checkpoint) coordinates a multi-host save internally;
this module is the preemption-first alternative the elastic resume path
builds on, with three properties orbax's opaque layout cannot give us:

  * **Per-worker shard files.** Worker ``i`` serialises only the
    param/opt-state shards it OWNS (dedup by sharding index: a shard
    replicated across processes is written once, by the lowest-ranked
    owner) into ``steps/<step>/worker<i>.npz`` plus a shard index
    (``worker<i>.json``: global shape, dtype, and the slice each shard
    covers, per leaf). Restore can therefore reassemble ANY slice of
    any leaf from a different process/device count — the N→M reshard
    primitive (tpudist.elastic.resume).
  * **Atomic two-phase commit.** The index json is written last
    (write-temp + ``os.replace``), so its presence marks "this worker's
    shards landed". The coordinator commits ``manifest.json`` (also
    temp + rename) only after EVERY worker's index landed — a
    filesystem rendezvous rather than a collective, so a worker dying
    mid-save can never wedge the survivors in a barrier; the commit
    just never happens and the previous manifest stays authoritative.
    A kill at ANY instant leaves either the previous or the next
    fully-consistent step, never a torn checkpoint.
  * **Transparent layout.** Everything is npz + json on a filesystem
    the whole pod shares (NFS, GCS-fuse, or a local dir in tests); the
    stale leftovers of a killed run are recognisable and reaped on the
    next open (:func:`cleanup_stale`). ``gs://`` URIs are NOT handled
    here — pods writing straight to GCS keep ``--ckpt-mode orbax``.

:class:`ShardedCheckpointer` mirrors ``checkpoint.Checkpointer``'s
interface (``save(state, epoch=, step_in_epoch=)`` / ``wait`` /
``close`` / ``last_enqueue_ms`` / ``drain_ms``) so the train loop
treats the modes interchangeably. ``save``
returns after the device→host snapshot (donation-safe: the next step
may reuse the donated buffers); the file writes and the commit run on
a background thread unless ``use_async=False``.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import sys
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

MANIFEST_SCHEMA_VERSION = 1
DEFAULT_KEEP = 3
# How long the coordinator's commit waits for every worker's shard index
# to land before giving up (the previous manifest then stays committed).
# Generous by default — a slow NFS worker must not lose a checkpoint —
# and shrunk by tests via the env override.
COMMIT_TIMEOUT_S = 300.0
# Transient-filesystem-error policy for the shard writer: a flaky NFS
# EIO / momentary ENOSPC must cost a retry, not a checkpoint — and
# exhaustion must cost THAT STEP'S commit, never a wedged writer thread
# or a dead training run (the previous manifest stays authoritative).
# Env overrides TPUDIST_CKPT_RETRIES / TPUDIST_CKPT_RETRY_BACKOFF_S.
WRITE_RETRIES = 3
WRITE_RETRY_BACKOFF_S = 0.05

# ---------------------------------------------------- chaos fault hook
# The chaos plane (tpudist.chaos) injects write-path faults through this
# module-level hook: called at named points of ShardedCheckpointer._write
# with the save's step context. A hook may raise OSError (a scripted
# transient fs error — the retry loop above absorbs it), damage the
# just-landed file (shard corruption — restore's crc check must catch
# it), or os._exit (the torn-manifest kill between index land and
# commit). None (the default) costs one attribute read per point.
_FAULT_HOOK: Optional[Callable[..., None]] = None


def set_fault_hook(hook: Optional[Callable[..., None]]) -> None:
    global _FAULT_HOOK
    _FAULT_HOOK = hook


def _fault(point: str, **ctx: Any) -> None:
    if _FAULT_HOOK is not None:
        _FAULT_HOOK(point, **ctx)


def elastic_root(save_dir: str) -> str:
    """The sharded-manifest tree lives under ``<save_dir>/elastic`` so it
    coexists with orbax step dirs in the same ``--save-dir``."""
    return os.path.join(save_dir, "elastic")


def _steps_dir(root: str) -> str:
    return os.path.join(root, "steps")


def step_dir(root: str, step: int) -> str:
    return os.path.join(_steps_dir(root), f"{step:08d}")


def manifest_path(save_dir: str) -> str:
    return os.path.join(elastic_root(save_dir), "manifest.json")


def index_name(process_index: int) -> str:
    return f"worker{process_index}.json"


def shards_name(process_index: int) -> str:
    return f"worker{process_index}.npz"


def _atomic_json(path: str, payload: Dict[str, Any]) -> None:
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)


def latest_manifest(save_dir: str) -> Optional[Dict[str, Any]]:
    """The committed manifest, or None when no sharded checkpoint has
    ever been committed in ``save_dir``. Only ``manifest.json`` itself
    is consulted — a ``manifest.json.tmp`` torn off by a kill
    mid-commit is ignored (and reaped by :func:`cleanup_stale`)."""
    path = manifest_path(save_dir)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def committed_manifests(save_dir: str) -> List[Dict[str, Any]]:
    """Every committed manifest still on disk, NEWEST FIRST: the
    top-level ``manifest.json`` plus the per-step copies each commit
    leaves inside its step directory. The per-step copies are what
    restore falls back onto when the newest checkpoint's shards fail
    their crc check — without them a corrupt byte would cost ALL the
    retained history, not one step. Steps newer than the top-level
    manifest are ignored (a per-step copy whose top-level flip a kill
    tore off is not committed; :func:`cleanup_stale` reaps its dir),
    and checkpoints predating the copies simply have no fallback."""
    latest = latest_manifest(save_dir)
    if latest is None:
        return []
    out = [latest]
    seen = {int(latest["step"])}
    sdir = _steps_dir(elastic_root(save_dir))
    if not os.path.isdir(sdir):
        return out
    for name in sorted(os.listdir(sdir), reverse=True):
        if not name.isdigit():
            continue
        step = int(name)
        if step in seen or step > int(latest["step"]):
            continue
        p = os.path.join(sdir, name, "manifest.json")
        if not os.path.exists(p):
            continue          # retained but never committed (or too old)
        try:
            with open(p) as f:
                man = json.load(f)
        except (OSError, ValueError):
            continue          # a torn copy is not a fallback
        if int(man.get("step", -1)) != step:
            continue
        out.append(man)
        seen.add(step)
    return out


def state_leaves(state: Any) -> List[Tuple[str, Any]]:
    """``(path_key, leaf)`` pairs in a stable order — the name contract
    both the writer and the restorer key on (jax keystr paths)."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def cleanup_stale(save_dir: str, *, process_index: int = 0) -> List[str]:
    """Reap the leftovers of a killed run: ``*.tmp`` files anywhere in
    the elastic tree, and (coordinator only) step directories NEWER than
    the committed manifest — those are mid-flight writes whose commit
    never happened; the resumed run will re-reach and rewrite those
    steps. Committed and retained older dirs are untouched. Returns the
    removed paths (tests pin the contract)."""
    root = elastic_root(save_dir)
    removed: List[str] = []
    if not os.path.isdir(root):
        return removed
    for dirpath, _, files in os.walk(root):
        for fn in files:
            if fn.endswith(".tmp"):
                p = os.path.join(dirpath, fn)
                try:
                    os.remove(p)
                    removed.append(p)
                except OSError:
                    pass
    if process_index != 0:
        return removed
    manifest = latest_manifest(save_dir)
    committed = -1 if manifest is None else int(manifest["step"])
    sdir = _steps_dir(root)
    if os.path.isdir(sdir):
        for name in sorted(os.listdir(sdir)):
            try:
                step = int(name)
            except ValueError:
                continue
            if step > committed:
                p = os.path.join(sdir, name)
                shutil.rmtree(p, ignore_errors=True)
                removed.append(p)
    return removed


class ShardedCheckpointer:
    """Per-worker sharded checkpoint writer with coordinator commit.

    Every process constructs one and calls ``save`` at the same train
    boundaries (the same all-ranks contract as the orbax
    ``Checkpointer``). ``run_meta`` is stored verbatim in the manifest
    — the train loop passes its data cursor (seed, global batch size)
    so resume can refuse a checkpoint whose batch order the current
    config would not reproduce.
    """

    def __init__(self, save_dir: str, *, process_index: int = 0,
                 process_count: int = 1, keep: Optional[int] = DEFAULT_KEEP,
                 use_async: bool = True,
                 run_meta: Optional[Dict[str, Any]] = None,
                 commit_timeout_s: Optional[float] = None):
        self.root = elastic_root(save_dir)
        self.save_dir = save_dir
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        self.keep = keep
        self.use_async = use_async
        self.run_meta = dict(run_meta or {})
        # the commit rendezvous' freshness key: a corruption-FALLBACK
        # resume re-reaches steps whose dir still holds the dead
        # attempt's indexes (the dir was committed, so cleanup_stale
        # leaves it), and a commit satisfied by a peer's STALE index
        # would flip the manifest onto the very bytes the fallback
        # rejected — indexes therefore stamp the attempt they were
        # written by, and the rendezvous only counts this attempt's
        # (None = unstamped callers/old indexes keep the old behavior)
        att = self.run_meta.get("requeue_attempt")
        self._attempt = int(att) if isinstance(att, (int, float)) else None
        if commit_timeout_s is None:
            try:
                commit_timeout_s = float(os.environ.get(
                    "TPUDIST_CKPT_COMMIT_TIMEOUT_S", COMMIT_TIMEOUT_S))
            except ValueError:
                commit_timeout_s = COMMIT_TIMEOUT_S
        self.commit_timeout_s = commit_timeout_s
        try:
            self.write_retries_max = int(os.environ.get(
                "TPUDIST_CKPT_RETRIES", WRITE_RETRIES))
        except ValueError:
            self.write_retries_max = WRITE_RETRIES
        try:
            self.write_retry_backoff_s = float(os.environ.get(
                "TPUDIST_CKPT_RETRY_BACKOFF_S", WRITE_RETRY_BACKOFF_S))
        except ValueError:
            self.write_retry_backoff_s = WRITE_RETRY_BACKOFF_S
        self.last_enqueue_ms: float = 0.0
        self.last_drain_ms: float = 0.0
        self.drain_ms: float = 0.0
        self.saves: int = 0
        self.commits: int = 0           # manifests this process committed
        self.commit_failures: int = 0   # commit waits that timed out
        self.write_errors: int = 0
        self.write_retries: int = 0     # transient fs errors retried away
        self.write_skips: int = 0       # saves abandoned after exhaustion
        # steps whose shard write was abandoned: the coordinator must
        # not sit out the full commit timeout waiting for shards that
        # will never land — that step's commit is skipped outright
        self._skip_commit_steps: set = set()
        # reap the dead run's tmp files / uncommitted step dirs BEFORE
        # the first save can collide with a half-written leftover
        cleanup_stale(save_dir, process_index=self.process_index)
        self._q: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        if use_async:
            self._thread = threading.Thread(
                target=self._worker, name="tpudist-elastic-ckpt",
                daemon=True)
            self._thread.start()

    @property
    def last_save_ms(self) -> float:
        """Alias matching ``checkpoint.Checkpointer`` (the enqueue time
        is what the old field measured under async saves)."""
        return self.last_enqueue_ms

    # ------------------------------------------------------------ save
    def save(self, state: Any, *, epoch: int, step_in_epoch: int = 0
             ) -> None:
        """Snapshot this worker's shards of ``state`` and hand the write
        (and, on the coordinator, the commit) to the background thread.
        Returns once the device→host copies are done — donation-safe."""
        t0 = time.perf_counter()
        from tpudist.obs import trace as trace_lib
        step = int(state.step)
        from tpudist.parallel import sharding as shd
        with trace_lib.span("ckpt_enqueue", cat="ckpt", step=step,
                            mode="sharded") as sp:
            cost = trace_lib.HostCost(sp)
            index: Dict[str, Any] = {}
            arrays: Dict[str, np.ndarray] = {}
            for li, (name, leaf) in enumerate(state_leaves(state)):
                shards = []
                for si, (span, data) in enumerate(
                        shd.owned_shard_spans(leaf, self.process_index)):
                    key = f"L{li}_S{si}"
                    arrays[key] = data
                    # crc32 of the shard's raw bytes, recorded BEFORE
                    # any file I/O: restore verifies it against what
                    # the filesystem hands back, so a corrupt or
                    # truncated shard is detected — and the manifest
                    # rejected in favor of the previous committed step
                    # — instead of resuming from garbage
                    shards.append({"key": key,
                                   "start": [s for s, _ in span],
                                   "shape": list(data.shape),
                                   "crc32": zlib.crc32(data.tobytes())
                                   & 0xFFFFFFFF})
                index[name] = {
                    "shape": list(getattr(leaf, "shape", ())),
                    "dtype": str(np.dtype(getattr(leaf, "dtype",
                                                  np.float32))),
                    "shards": shards}
            job = (step, int(epoch), int(step_in_epoch), index, arrays)
            # this process's shards are on the host now: the copy is done
            cost.note(bytes=sum(int(a.nbytes) for a in arrays.values()))
            if self.use_async:
                self._q.put(("write", job))
                if self.process_index == 0:
                    self._q.put(("commit", job[:3]))
            else:
                # sync mode shares the retry/skip discipline: a
                # transient fs error exhausting its retries skips this
                # step's commit instead of killing the training run
                if self._write_retrying(*job) and self.process_index == 0:
                    self._commit(step, int(epoch), int(step_in_epoch))
        self.last_enqueue_ms = (time.perf_counter() - t0) * 1000
        self.saves += 1

    # -------------------------------------------------- writer thread
    def _worker(self) -> None:
        while True:
            kind, payload = self._q.get()
            try:
                if kind == "stop":
                    return
                elif kind == "write":
                    self._write_retrying(*payload)
                elif kind == "commit":
                    self._commit(*payload)
            except Exception as e:
                # a failed background save must not kill training; the
                # previous manifest stays committed and the error is
                # visible in the run log + the write_errors counter
                self.write_errors += 1
                print(f"tpudist: sharded ckpt {kind} failed: {e!r}",
                      file=sys.stderr, flush=True)
            finally:
                self._q.task_done()

    def _write_retrying(self, step: int, epoch: int, step_in_epoch: int,
                        index: Dict[str, Any],
                        arrays: Dict[str, np.ndarray]) -> bool:
        """Bounded retry-with-backoff around the shard write: transient
        filesystem errors (a flaky NFS EIO, momentary ENOSPC) retry;
        exhaustion skips THIS STEP's commit — the writer thread never
        wedges and the previous manifest stays authoritative. Non-OSError
        failures keep their old path (sync raises, async is caught by
        the worker loop's generic handler)."""
        delay = self.write_retry_backoff_s
        for attempt in range(self.write_retries_max + 1):
            try:
                self._write(step, epoch, step_in_epoch, index, arrays)
                return True
            except OSError as e:
                if attempt >= self.write_retries_max:
                    self.write_errors += 1
                    self.write_skips += 1
                    self._skip_commit_steps.add(step)
                    print(f"tpudist: sharded ckpt write of step {step} "
                          f"failed {attempt + 1}x ({e!r}); skipping this "
                          f"step's commit — the previous manifest stays "
                          f"committed", file=sys.stderr, flush=True)
                    return False
                self.write_retries += 1
                time.sleep(delay)
                delay *= 2
        return False

    def _write(self, step: int, epoch: int, step_in_epoch: int,
               index: Dict[str, Any], arrays: Dict[str, np.ndarray]
               ) -> None:
        d = step_dir(self.root, step)
        os.makedirs(d, exist_ok=True)
        npz = os.path.join(d, shards_name(self.process_index))
        _fault("shard_write", step=step, epoch=epoch,
               step_in_epoch=step_in_epoch, path=npz)
        tmp = f"{npz}.tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, npz)
        _fault("shard_written", step=step, epoch=epoch,
               step_in_epoch=step_in_epoch, path=npz)
        # the index lands LAST: its presence is this worker's "shards
        # landed" marker — the commit's filesystem rendezvous
        ipath = os.path.join(d, index_name(self.process_index))
        _atomic_json(ipath, {
            "schema": MANIFEST_SCHEMA_VERSION, "step": step,
            "epoch": epoch, "step_in_epoch": step_in_epoch,
            "process_index": self.process_index,
            "requeue_attempt": self._attempt, "leaves": index})
        _fault("index_written", step=step, epoch=epoch,
               step_in_epoch=step_in_epoch, path=ipath)

    # --------------------------------------------------------- commit
    def _worker_landed(self, step: int, i: int) -> bool:
        p = os.path.join(step_dir(self.root, step), index_name(i))
        if not os.path.exists(p):
            return False
        try:
            with open(p) as f:
                idx = json.load(f)
        except (ValueError, OSError):
            return False
        if int(idx.get("step", -1)) != step:
            return False
        # freshness: a previous attempt's leftover index in a re-reached
        # step dir must not satisfy THIS attempt's rendezvous — wait for
        # the peer to rewrite (unstamped indexes keep the old behavior)
        stamped = idx.get("requeue_attempt")
        if self._attempt is not None and stamped is not None \
                and int(stamped) != self._attempt:
            return False
        return True

    def _landed(self, step: int, verified: Optional[set] = None) -> bool:
        """All workers' shard indexes landed for ``step``. ``verified``
        carries the workers already validated across the commit loop's
        polls — an index is written once, atomically, so re-parsing a
        landed worker's file 20×/s for the whole wait would hammer the
        shared filesystem the save itself is contending for (256
        workers × full per-leaf metadata per poll)."""
        if verified is None:
            verified = set()
        for i in range(self.process_count):
            if i in verified:
                continue
            if not self._worker_landed(step, i):
                return False
            verified.add(i)
        return True

    def _commit(self, step: int, epoch: int, step_in_epoch: int) -> None:
        """Coordinator only: wait (bounded) for every worker's shard
        index, then atomically flip ``manifest.json`` to this step and
        apply retention. On timeout the previous manifest simply stays
        authoritative — never a partial commit. A per-step copy of the
        manifest lands inside the step dir FIRST: that copy is what
        restore falls back onto when a newer checkpoint's shards fail
        their crc check (it only becomes meaningful once the top-level
        flip succeeds, so a kill between the two writes changes
        nothing)."""
        if step in self._skip_commit_steps:
            # this worker's own shard write was abandoned after retry
            # exhaustion: the rendezvous can never complete — don't sit
            # out the full timeout on a commit that must not happen
            return
        deadline = time.monotonic() + self.commit_timeout_s
        verified: set = set()
        while not self._landed(step, verified):
            if time.monotonic() >= deadline:
                self.commit_failures += 1
                print(f"tpudist: sharded ckpt commit of step {step} timed "
                      f"out after {self.commit_timeout_s}s waiting for "
                      f"worker shards; previous manifest stays committed",
                      file=sys.stderr, flush=True)
                return
            time.sleep(min(0.05, self.commit_timeout_s / 10 or 0.05))
        with open(os.path.join(step_dir(self.root, step),
                               index_name(0))) as f:
            leaves = {name: {"shape": rec["shape"], "dtype": rec["dtype"]}
                      for name, rec in json.load(f)["leaves"].items()}
        payload = {
            "schema": MANIFEST_SCHEMA_VERSION,
            "step": step, "epoch": epoch, "step_in_epoch": step_in_epoch,
            "process_count": self.process_count,
            "ts": time.time(), "run": self.run_meta, "leaves": leaves,
            "dir": os.path.relpath(step_dir(self.root, step), self.root)}
        _atomic_json(os.path.join(step_dir(self.root, step),
                                  "manifest.json"), payload)
        _atomic_json(manifest_path(self.save_dir), payload)
        self.commits += 1
        self._retain(step)

    def _retain(self, committed: int) -> None:
        if self.keep is None:
            return
        sdir = _steps_dir(self.root)
        if not os.path.isdir(sdir):
            return
        steps = sorted(int(n) for n in os.listdir(sdir) if n.isdigit())
        old = [s for s in steps if s <= committed]
        for s in old[:-max(self.keep, 1)]:
            shutil.rmtree(os.path.join(sdir, f"{s:08d}"),
                          ignore_errors=True)

    # ---------------------------------------------------------- drain
    def wait(self) -> None:
        t0 = time.perf_counter()
        from tpudist.obs import trace as trace_lib
        with trace_lib.span("ckpt_drain", cat="ckpt", mode="sharded"):
            if self.use_async:
                self._q.join()
        self.last_drain_ms = (time.perf_counter() - t0) * 1000
        self.drain_ms += self.last_drain_ms

    def close(self) -> None:
        t0 = time.perf_counter()
        from tpudist.obs import trace as trace_lib
        with trace_lib.span("ckpt_drain", cat="ckpt", close=True,
                            mode="sharded"):
            if self.use_async and self._thread is not None:
                self._q.join()
                self._q.put(("stop", None))
                self._thread.join(timeout=10.0)
                self._thread = None
        self.last_drain_ms = (time.perf_counter() - t0) * 1000
        self.drain_ms += self.last_drain_ms

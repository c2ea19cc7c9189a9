"""CheckpointCoordinator: epoch generation, ack collection, atomic commit.

One coordinator per running PipeGraph. Triggering is a single integer bump
of ``requested_id``; source replicas poll it on their own threads at tuple
boundaries and inject the ``Barrier`` themselves, so the coordinator never
touches a channel and needs no per-message synchronization. Each worker
acknowledges a checkpoint exactly once, shipping all of its fused
replicas' snapshot blobs; the checkpoint commits (manifest + atomic
rename, ``store.py``) when every worker of the graph has acked. Finalize
listeners run on the acking worker's thread — they must be cheap and
thread-safe (the Kafka source only flips a flag and commits offsets from
its own consume loop).

A checkpoint that can never complete (a source finished before the
barrier, a worker crashed) simply stays uncommitted: restore only ever
sees fully-acked checkpoints, which is the correctness contract.
"""

from __future__ import annotations

import os
import queue
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Set

from .delta import env_ckpt_async
from .store import CheckpointStore


def env_ckpt_timeout() -> float:
    """``WF_CKPT_TIMEOUT`` (seconds): how long an epoch may stay pending
    before it is failed with a descriptive error naming the unacked
    workers. 0 / unset = no timeout (the pre-timeout behavior: an epoch
    that can never complete simply stays uncommitted)."""
    try:
        return float(os.environ.get("WF_CKPT_TIMEOUT", "0") or 0)
    except ValueError:
        return 0.0  # malformed knob must not take down the graph


class EpochFailed(Exception):
    """Internal marker: an epoch was failed (timeout); ``wait_committed``
    converts it into the user-facing WindFlowError."""


class CheckpointCoordinator:
    def __init__(self, store: CheckpointStore, graph_name: str = "pipegraph",
                 interval_s: Optional[float] = None) -> None:
        self.store = store
        self.graph_name = graph_name
        self.interval_s = interval_s
        # the epoch counter source replicas poll (reads are a single
        # attribute load — safe without the lock; writes hold it).
        # _alloc_id hands out ids BEFORE they publish, so two concurrent
        # triggers can never share an epoch
        self.requested_id = 0
        self._alloc_id = 0
        # workers expected to ack each checkpoint; set by PipeGraph once
        # the topology is built (0 = not running, acks park as pending)
        self.expected_acks = 0
        self._lock = threading.Lock()
        # serializes blob writes against the commit rename: an ack's
        # pending-check + write must be atomic w.r.t. _finalize renaming
        # the staging dir away, or a late writer (a retiring worker
        # racing the last live ack) loses its temp file mid-write and
        # leaks unmanifested blobs into the committed dir. Ordering:
        # _store_lock outside _lock, never the reverse.
        self._store_lock = threading.Lock()
        self._pending: Dict[int, Dict[str, Any]] = {}
        # epochs ``_finalize`` has taken out of ``_pending`` and is
        # writing the commit of (outside the lock): no longer pending, not
        # yet ``last_completed_id`` — ``wait_committed`` must keep waiting
        self._committing: Set[int] = set()
        # workers that exited cleanly, with their final state blobs: a
        # finished worker's state is frozen, so its final snapshot is
        # valid for every later epoch (Flink's finished-task semantics —
        # without this, one short-lived source would forever block
        # checkpoints of a still-running graph)
        self._retired: Dict[str, Dict[Any, Any]] = {}
        self._listeners: List[Callable[[int], None]] = []
        # abort listeners (exactly-once sinks): notified with the epoch
        # id when a pending epoch is failed (WF_CKPT_TIMEOUT) or dropped
        # wholesale (rescale teardown) — the epoch will never finalize,
        # so a transactional sink knows its staged records ride the next
        # committed epoch's watermark instead
        self._abort_listeners: List[Callable[[int], None]] = []
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # aggregate stats (PipeGraph.get_stats / the /metrics plane)
        self.completed = 0
        self.last_completed_id = 0
        self.last_duration_s = 0.0
        self.last_bytes = 0
        self.total_bytes = 0
        # epoch timeout (WF_CKPT_TIMEOUT): pending epochs older than this
        # fail loudly instead of hanging trigger_checkpoint()/rescale()
        # forever when a worker never acks
        self.epoch_timeout_s = env_ckpt_timeout()
        self.failed_epochs = 0
        # epochs failed by an OSError while staging blobs (disk full,
        # permission loss): the epoch dies loudly, the worker survives
        self.storage_failures = 0
        self.last_failure: Optional[str] = None
        self._failed: Dict[int, str] = {}  # cid -> failure message
        # wait_committed() sleeps here; notified on finalize and failure
        self._commit_cond = threading.Condition(self._lock)
        # worker roster + diagnostics hook, wired by PipeGraph: names make
        # the timeout error actionable, diagnose() adds Worker_last_error
        # / stall-watchdog state for the unacked workers when available
        self.worker_names: List[str] = []
        self.diagnose: Optional[Callable[[List[str]], str]] = None
        # rescale hold point (windflow_tpu.scaling): when an epoch is
        # triggered with hold=True, every worker parks inside
        # ``checkpoint_now`` right after acking it, so the whole graph
        # quiesces exactly at the aligned barrier. The controller then
        # releases them with a directive: "resume" (rescale aborted) or
        # "abandon" (unwind; the runtime plane is rebuilt)
        self._hold_epoch: Optional[int] = None
        self._hold_evt = threading.Event()
        self._hold_directive = "resume"
        self.parked: Set[str] = set()
        self._commit_acked: Dict[int, Set[str]] = {}  # cid -> acked names
        # async snapshot upload (WF_CKPT_ASYNC): an ack only registers
        # the captured blobs as a PENDING upload handle and returns —
        # the worker's cut pause ends there. A single background
        # uploader serializes + writes off the hot path; the epoch
        # finalizes only when every worker acked AND every upload
        # landed (ent["uploads"] == 0). A crash/OSError mid-upload
        # fails the epoch loudly through the same storage-failure path
        # as a synchronous write — exactly-once epoch-id semantics and
        # the fallback ladder are unchanged.
        self.async_enabled = env_ckpt_async()
        self._upload_q: Optional[queue.Queue] = None
        self._upload_thread: Optional[threading.Thread] = None
        self.async_uploads = 0       # uploads completed (any outcome)
        self.async_pending = 0       # uploads currently in flight
        self.upload_usec_total = 0.0

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        if self.interval_s is None or self.interval_s <= 0 \
                or self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name=f"{self.graph_name}/ckpt-coord",
            daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=3)
            self._thread = None
        q = self._upload_q
        if q is not None and self._upload_thread is not None:
            q.put(None)  # sentinel: drain remaining uploads, then exit
            self._upload_thread.join(timeout=5)
            self._upload_thread = None
            self._upload_q = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.check_epoch_timeouts()
            self.trigger()

    # -- triggering --------------------------------------------------------
    def trigger(self, force: bool = False, hold: bool = False
                ) -> Optional[int]:
        """Open a new checkpoint epoch and return its id. Without
        ``force``, declines while an earlier checkpoint is still
        in flight (aligned barriers serialize naturally; overlapping
        epochs would only race each other at the aligners).

        ``hold=True`` marks the epoch as a rescale quiesce point: every
        worker parks in ``park_if_held`` right after acking it, until
        ``release_hold`` hands down a directive."""
        timeout = max(2.0 * (self.interval_s or 0.0), 10.0)
        with self._lock:
            if not force:
                now = time.monotonic()
                for ent in self._pending.values():
                    if now - ent["t0"] < timeout:
                        return None
            self._alloc_id = max(self._alloc_id, self.requested_id) + 1
            cid = self._alloc_id
            self._pending[cid] = {"acked": set(), "bytes": 0,
                                  "t0": time.monotonic()}
            if hold:
                # armed BEFORE the epoch publishes: a source may poll the
                # new requested_id and park before trigger() returns
                self._hold_epoch = cid
                self._hold_directive = "resume"
                self._hold_evt.clear()
                self.parked = set()
        # stage BEFORE publishing the epoch: sources poll requested_id and
        # may ack immediately — clearing crashed-run debris after that
        # would race their blob writes
        with self._store_lock:
            self.store.begin(cid)
        with self._lock:
            if cid > self.requested_id:
                self.requested_id = cid
            retired = list(self._retired.items())
        for wname, blobs in retired:
            self.ack(cid, wname, blobs)
        return cid

    # -- acks --------------------------------------------------------------
    def ack(self, ckpt_id: int, worker_name: str,
            blobs: Dict[Any, Any]) -> int:
        """One worker's snapshot for one checkpoint: ``blobs`` maps
        ``(op_name, replica_idx)`` to the replica's state dict. Returns
        bytes written (0 when the checkpoint is unknown/already
        committed — a late barrier after a commit-by-timeout; also 0 in
        async mode, where the write happens off this thread and the
        bytes land in the epoch's tally when the upload does)."""
        if self.async_enabled:
            return self._ack_async(ckpt_id, worker_name, blobs)
        nbytes = 0
        with self._store_lock:
            with self._lock:
                if ckpt_id not in self._pending:
                    return 0
            try:
                for (op_name, idx), state in blobs.items():
                    nbytes += self.store.write_blob(ckpt_id, op_name, idx,
                                                    state)
            except OSError as e:
                # disk full / write failure while staging: fail the EPOCH
                # loudly, never the worker. Staging debris is pruned so a
                # full disk isn't made worse; the next interval retries a
                # fresh epoch with fresh staging.
                shutil.rmtree(self.store._dirname(ckpt_id, staging=True),
                              ignore_errors=True)
                with self._lock:
                    self._fail_epoch_storage_locked(ckpt_id, worker_name, e)
                self._notify_aborted(ckpt_id)
                return 0
        with self._lock:
            ent = self._pending.get(ckpt_id)
            if ent is None:
                return nbytes
            ent["acked"].add(worker_name)
            ent["bytes"] += nbytes
            done = (self.expected_acks > 0
                    and len(ent["acked"]) >= self.expected_acks
                    and ent.get("uploads", 0) == 0)
        if done:
            self._finalize(ckpt_id)
        return nbytes

    # -- async snapshot upload (WF_CKPT_ASYNC) -----------------------------
    def _ack_async(self, ckpt_id: int, worker_name: str,
                   blobs: Dict[Any, Any]) -> int:
        """Register the captured blobs as a pending upload handle and
        return immediately: the barrier fenced only the state CUT. The
        epoch cannot finalize until this upload lands."""
        from ..monitoring.flightrec import thread_recorder

        with self._lock:
            ent = self._pending.get(ckpt_id)
            if ent is None:
                return 0
            ent["acked"].add(worker_name)
            ent["uploads"] = ent.get("uploads", 0) + 1
            self.async_pending += 1
        self._ensure_uploader()
        # the entry object rides along as an incarnation token: after a
        # crash + in-process restart the same ckpt_id can be re-begun
        # with a FRESH entry, and a stale pre-crash upload must not
        # write into (or fail) the reincarnated epoch
        self._upload_q.put((ckpt_id, worker_name, blobs,
                            thread_recorder(), ent))
        return 0

    def _ensure_uploader(self) -> None:
        with self._lock:
            if self._upload_thread is not None:
                return
            self._upload_q = queue.Queue()
            self._upload_thread = threading.Thread(
                target=self._upload_loop,
                name=f"{self.graph_name}/ckpt-upload", daemon=True)
        self._upload_thread.start()

    def _upload_loop(self) -> None:
        while True:
            item = self._upload_q.get()
            if item is None:
                return
            self._upload_one(*item)

    def _upload_one(self, ckpt_id: int, worker_name: str,
                    blobs: Dict[Any, Any], rec: Any, ent: dict) -> None:
        from ..monitoring.flightrec import rec_evt_safe

        t0 = time.perf_counter()
        nbytes = 0
        failed = None
        try:
            with self._store_lock:
                with self._lock:
                    # identity, not id: a reincarnated epoch (crash +
                    # restart re-begins the same ckpt_id) has a fresh
                    # entry and this upload is abandoned
                    alive = self._pending.get(ckpt_id) is ent
                if alive:
                    for (op_name, idx), state in blobs.items():
                        nbytes += self.store.write_blob(
                            ckpt_id, op_name, idx, state)
        except OSError as e:
            # same loud-epoch-failure contract as a synchronous write:
            # the epoch dies, the worker (long resumed) never notices
            failed = e
            shutil.rmtree(self.store._dirname(ckpt_id, staging=True),
                          ignore_errors=True)
        dur_us = (time.perf_counter() - t0) * 1e6
        done = False
        with self._lock:
            self.async_pending -= 1
            self.async_uploads += 1
            self.upload_usec_total += dur_us
            stale = self._pending.get(ckpt_id) is not ent
            if failed is not None:
                if not stale:
                    self._fail_epoch_storage_locked(ckpt_id, worker_name,
                                                    failed)
            elif not stale:
                ent["uploads"] -= 1
                ent["bytes"] += nbytes
                done = (self.expected_acks > 0
                        and len(ent["acked"]) >= self.expected_acks
                        and ent["uploads"] == 0)
        if failed is not None:
            if not stale:
                self._notify_aborted(ckpt_id)
            return
        if rec is not None:
            # the acking worker's ring, written cross-thread: one racy
            # slot write, tolerated the same way the stall watchdog's is
            rec_evt_safe(rec, "ckpt:upload", dur_us,
                         {"ckpt_id": ckpt_id, "worker": worker_name,
                          "bytes": nbytes})
        if done:
            self._finalize(ckpt_id)

    def retire(self, worker_name: str, blobs: Dict[Any, Any]) -> None:
        """A worker finished cleanly: remember its final blobs and ack
        them into every epoch it had not answered yet (its barrier can no
        longer be in flight — it saw EOS on every channel)."""
        with self._lock:
            self._retired[worker_name] = blobs
            open_cids = [cid for cid, ent in self._pending.items()
                         if worker_name not in ent["acked"]]
        for cid in open_cids:
            self.ack(cid, worker_name, blobs)

    def _finalize(self, ckpt_id: int) -> None:
        with self._lock:
            ent = self._pending.pop(ckpt_id, None)
            if ent is None:
                return  # raced another finalize
            self._committing.add(ckpt_id)
            # any older still-open checkpoint can no longer matter: the
            # newer one strictly supersedes it
            for old in [c for c in self._pending if c < ckpt_id]:
                self._pending.pop(old, None)
            listeners = list(self._listeners)
        duration = time.monotonic() - ent["t0"]
        try:
            with self._store_lock:
                self.store.commit(ckpt_id, {
                    "graph": self.graph_name,
                    "created_unix": time.time(),
                    "duration_sec": round(duration, 6),
                    "n_workers": self.expected_acks,
                    "bytes": ent["bytes"],
                })
        except BaseException:
            with self._lock:
                self._committing.discard(ckpt_id)
                self._commit_cond.notify_all()
            raise
        with self._lock:
            self._committing.discard(ckpt_id)
            self.completed += 1
            self.last_completed_id = ckpt_id
            self.last_duration_s = duration
            self.last_bytes = ent["bytes"]
            self.total_bytes += ent["bytes"]
            # the rescale controller needs to know WHO acked a held epoch
            # (parked ∪ retired must cover them before teardown is safe)
            self._commit_acked[ckpt_id] = set(ent["acked"])
            for old in [c for c in self._commit_acked if c < ckpt_id]:
                self._commit_acked.pop(old, None)
            self._commit_cond.notify_all()
        # _finalize runs on the LAST acking worker's thread: its flight
        # ring (when recording) gets the commit marker, closing the
        # barrier_open -> align -> snapshot -> commit timeline
        from ..monitoring.flightrec import thread_recorder
        rec = thread_recorder()
        if rec is not None:
            rec.event("ckpt_commit", duration * 1e6,
                      {"ckpt_id": ckpt_id, "bytes": ent["bytes"]})
        for fn in listeners:
            try:
                fn(ckpt_id)
            except Exception:  # listener bugs must not kill the worker
                pass

    # -- epoch timeout (WF_CKPT_TIMEOUT) -----------------------------------
    def _unacked_of(self, acked: Set[str]) -> List[str]:
        names = self.worker_names or []
        return [n for n in names if n not in acked] \
            or [f"<{self.expected_acks - len(acked)} unnamed worker(s)>"]

    def _fail_epoch_locked(self, cid: int, age_s: float) -> str:
        """Drop a pending epoch and compose the descriptive error (lock
        held). The staging dir stays on disk; store.prune cleans it once
        a newer checkpoint commits. ``diagnose`` (when wired — it only
        reads already-collected stats) appends per-worker evidence:
        ``Worker_last_error`` tracebacks, stall-watchdog flags."""
        ent = self._pending.pop(cid, None)
        acked = ent["acked"] if ent else set()
        unacked = self._unacked_of(acked)
        msg = (f"checkpoint epoch {cid} timed out after {age_s:.1f}s "
               f"(WF_CKPT_TIMEOUT): {len(acked)}/{self.expected_acks} "
               f"workers acked; never acked: {', '.join(unacked)}")
        if self.diagnose is not None:
            try:
                extra = self.diagnose(unacked)
            except Exception:
                extra = ""
            if extra:
                msg += f" — {extra}"
        self._failed[cid] = msg
        for old in [c for c in self._failed if c < cid - 16]:
            self._failed.pop(old, None)
        self.failed_epochs += 1
        self.last_failure = msg
        self._commit_cond.notify_all()
        return msg

    def _fail_epoch_storage_locked(self, cid: int, worker_name: str,
                                   err: OSError) -> str:
        """Drop a pending epoch whose blob staging hit an OSError (lock
        held). Same bookkeeping as the timeout path — the epoch will
        never finalize, abort listeners fire, and restore only ever sees
        fully-committed checkpoints."""
        self._pending.pop(cid, None)
        msg = (f"checkpoint epoch {cid} aborted: storage write failure "
               f"while worker {worker_name!r} staged its snapshot "
               f"({type(err).__name__}: {err}) — staging debris pruned, "
               "next interval retries")
        self._failed[cid] = msg
        for old in [c for c in self._failed if c < cid - 16]:
            self._failed.pop(old, None)
        self.failed_epochs += 1
        self.storage_failures += 1
        self.last_failure = msg
        self._commit_cond.notify_all()
        return msg

    def check_epoch_timeouts(self) -> None:
        """Fail pending epochs older than ``WF_CKPT_TIMEOUT``. Called by
        the interval thread each tick and by ``wait_committed``; a
        no-op when the timeout is unset."""
        t = self.epoch_timeout_s
        if t <= 0:
            return
        with self._lock:
            now = time.monotonic()
            stale = [(cid, now - ent["t0"])
                     for cid, ent in self._pending.items()
                     if now - ent["t0"] >= t]
            for cid, age in stale:
                self._fail_epoch_locked(cid, age)
        for cid, _ in stale:
            self._notify_aborted(cid)

    def wait_committed(self, cid: int, timeout_s: Optional[float] = None
                       ) -> None:
        """Block until epoch ``cid`` commits. Raises ``WindFlowError``
        when the epoch fails (WF_CKPT_TIMEOUT elapsed, or ``timeout_s``
        as an explicit override) naming the workers that never acked."""
        from ..basic import WindFlowError

        t = timeout_s if timeout_s is not None else self.epoch_timeout_s
        deadline = time.monotonic() + t if t and t > 0 else None
        while True:
            timed_out_msg = None
            with self._lock:
                if self.last_completed_id >= cid:
                    return
                if cid in self._failed:
                    raise WindFlowError(self._failed[cid])
                if cid not in self._pending and cid not in self._committing:
                    raise WindFlowError(
                        f"checkpoint epoch {cid} was dropped without "
                        "committing (superseded by a newer checkpoint)")
                if deadline is not None and time.monotonic() >= deadline:
                    timed_out_msg = self._fail_epoch_locked(cid, t)
                else:
                    self._commit_cond.wait(0.05)
            if timed_out_msg is not None:
                self._notify_aborted(cid)
                raise WindFlowError(timed_out_msg)

    # -- rescale hold point (windflow_tpu.scaling) -------------------------
    def park_if_held(self, ckpt_id: int, worker_name: str) -> Optional[str]:
        """Called by every worker right after acking ``ckpt_id``. For a
        held (rescale) epoch the worker blocks here — the graph quiesces
        exactly at the aligned barrier, with every pre-barrier tuple
        already flushed downstream and nothing post-barrier produced —
        until the controller releases it. Returns the release directive
        ("resume" / "abandon"), or None when the epoch is not held."""
        with self._lock:
            if self._hold_epoch != ckpt_id:
                return None
            self.parked.add(worker_name)
            self._commit_cond.notify_all()
            evt = self._hold_evt
        evt.wait()
        with self._lock:
            return self._hold_directive

    def wait_all_parked(self, cid: int, timeout_s: float) -> bool:
        """True once every worker that acked the held epoch ``cid`` live
        (i.e. not via retirement) is parked — the moment teardown/rewire
        is safe. The epoch must already be committed."""
        deadline = time.monotonic() + timeout_s
        while True:
            with self._lock:
                acked = self._commit_acked.get(cid)
                if acked is not None \
                        and acked <= (self.parked | set(self._retired)):
                    return True
                if time.monotonic() >= deadline:
                    return False
                self._commit_cond.wait(0.05)

    def release_hold(self, directive: str = "resume") -> None:
        """Release every parked worker with ``directive``: "resume"
        continues processing as after a normal checkpoint (aborted
        rescale), "abandon" unwinds the worker silently (the runtime
        plane is being rebuilt)."""
        with self._lock:
            self._hold_directive = directive
            self._hold_epoch = None
            evt = self._hold_evt
        evt.set()

    def abort_pending(self) -> None:
        """Drop every still-pending epoch (rescale teardown: epochs
        opened against the old runtime plane can never complete once its
        workers are gone)."""
        with self._lock:
            dropped = list(self._pending)
            self._pending.clear()
            self._retired.clear()
            self._commit_cond.notify_all()
        for cid in dropped:
            self._notify_aborted(cid)

    def _notify_aborted(self, cid: int) -> None:
        for fn in list(self._abort_listeners):
            try:
                fn(cid)
            except Exception:
                pass  # listener bugs must not kill the coordinator

    # -- listeners ---------------------------------------------------------
    def add_finalize_listener(self, fn: Callable[[int], None]) -> None:
        with self._lock:
            self._listeners.append(fn)

    def add_abort_listener(self, fn: Callable[[int], None]) -> None:
        with self._lock:
            self._abort_listeners.append(fn)

    # -- introspection -----------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "Checkpoints_completed": self.completed,
                "Checkpoints_requested": self.requested_id,
                "Checkpoint_last_id": self.last_completed_id,
                "Checkpoint_last_duration_sec": round(self.last_duration_s,
                                                      6),
                "Checkpoint_last_bytes": self.last_bytes,
                "Checkpoint_bytes_total": self.total_bytes,
                "Checkpoint_store_dir": self.store.root,
                "Checkpoint_failed_epochs": self.failed_epochs,
                "Checkpoint_failures": self.failed_epochs,
                "Checkpoint_storage_failures": self.storage_failures,
                "Checkpoint_verify_failures": self.store.verify_failures,
                "Checkpoint_last_failure": self.last_failure,
                # incremental/async plane (WF_CKPT_DELTA / WF_CKPT_ASYNC)
                "Checkpoint_delta_blobs": self.store.delta_blobs,
                "Checkpoint_delta_bytes": self.store.delta_bytes,
                "Checkpoint_full_bytes": self.store.full_bytes,
                "Checkpoint_async_pending": self.async_pending,
                "Checkpoint_async_uploads": self.async_uploads,
                "Checkpoint_upload_usec_total": round(
                    self.upload_usec_total, 1),
            }

"""DeviceDispatchQueue: the per-replica device-ahead dispatch pipeline.

Every TPU replica's per-batch work has two halves:

- a HOST-PREP stage — pure host control plane over the batch's host
  metadata: key -> slot resolution, leaf/pane bookkeeping, fire-pack and
  grid assembly (numpy, no device handles touched);
- a DEVICE-COMMIT stage — the XLA program call(s) on the replica's
  device state plus the downstream emit, including any readback the emit
  path needs (compaction counts, routing columns).

XLA's async dispatch already overlaps *device execution* with later host
work, but the commit stage itself still serializes with the next batch's
host prep: its Python-side program-call overhead and the donation
hand-off of the replica's device state. This queue defers the commit
stage of up to ``depth`` batches, mirroring ``_D2HPipeline`` on the exit
edges. ``WF_DISPATCH_DEPTH=0`` restores the fully synchronous path
(commit runs inside ``submit``), which the differential tests pin
against depth >= 2 for exact result equality.

LAUNCH AND FINISH, ONE LAUNCH APART. Deferring a commit does not hide the
wait of a commit that READS a fresh output of the program it launches (a
compaction's ``int(count)`` / ``np.asarray(order)``, a chain-exit
reduce's counts): however late the pair runs, the read follows its own
launch at once and blocks until the device ran the program. Such a
commit is split (``split_commit``): it launches, starts the host copies
its emit will read, and RETURNS that readback-and-emit as a *finish*
thunk. The queue keeps one pending finish and runs it right after the
launch half of the replica's NEXT commit: by then the device has had a
whole batch's time for the program, and the read is a copy that has
landed. The lag is one launch, fixed, at every depth > 0; at depth 0
both halves run inside ``submit``. A commit that does not split emits
inside its one call, so it runs only AFTER the pending finish: a
replica's batches leave in submission order whatever their kind. The
batch a finish reads (``ts_host``, ``host_keys``) stays referenced by
the thunk until it has run. ``Dispatch_readbacks`` counts the finishes,
``Dispatch_readbacks_deferred`` those that ran with a later launch of
the replica already issued (the last finish of a drain or of an idle
tick has none).

Ordering contract: commits run strictly in submission order, on the
replica's own worker thread (no cross-thread hand-off — the queue is a
deferral buffer, not a concurrency primitive), and so do their finishes.
The replica drains it at every ordering point: before punctuation
propagates, at EOS/terminate, before any host code touches the replica's
device state (forest/table growth, program warm-up, a snapshot), and on
the worker's idle tick so a quiet stream never parks prepared batches. A
drain runs every launch AND every finish before it returns. A commit or
a finish that raises marks the pipeline broken and discards the
remaining entries and the pending finish — they were prepped against
control-plane state the failed batch already advanced, so re-running
them after the error would emit from an inconsistent forest; the error
itself unwinds the worker (drain-inputs + emergency EOS).

MEGABATCH (``WF_MEGABATCH=K``, default 1 = off): when the queue
overflows, a FRONT run of commits carrying the same ``scan_sig`` (same
fused chain, same program signature, same capacity bucket —
``tpu/fused_ops.py`` attaches the attribute) is popped as ONE group and
handed to the commits' ``scan_runner``, which executes all of them in a
single jitted ``lax.scan`` over the chain program: K batches, ONE host
dispatch (after the pending finish; the group runs its own finishes
inside its commit). Only the largest power-of-two prefix of the run
groups (so the set of compiled scan programs stays enumerable for the
pre-warm);
mixed-signature, non-fused, or lone commits run as singles. ``drain``
always runs singles, so every ordering point — punctuation, EOS,
checkpoint snapshot, device-state access, error unwind — degrades to
K=1 and the alignment/exactly-once/rescale semantics are untouched.
Commits still run strictly in submission order either way (the scan
body IS the chain program, threading the same carried state
batch-to-batch).

Per-stage instrumentation lands in the replica's ``StatsRecord``
through the stage helper (``monitoring/tracing.py``): ``wf:prep`` around
the host prep (``prep()``), ``wf:commit`` around each commit and around
each finish (under its own batch's id), and the time a prepared batch
sat in the queue as the ``wait:queue`` residency
(``Dispatch_host_prep/commit_total_usec``,
``Dispatch_queue_wait_total_usec``, forced-drain stall count, max queue
depth), so the host-prep/device split is measured, not asserted.
"""

from __future__ import annotations

import os
from collections import deque
from typing import Callable, Optional

from ..monitoring.tracing import StageCounters, stamp_ns

_DEFAULT_DEPTH = 2


def dispatch_depth(default: int = _DEFAULT_DEPTH) -> int:
    """The configured pipeline depth (``WF_DISPATCH_DEPTH``, default 2;
    0 = synchronous). Malformed values fall back to the default — a bad
    knob must not take down the graph."""
    try:
        return max(0, int(os.environ.get("WF_DISPATCH_DEPTH",
                                         str(default))))
    except ValueError:
        return default


def megabatch_k(default: int = 1) -> int:
    """The configured megabatch width (``WF_MEGABATCH``, default 1;
    0/1 = off — every commit runs as its own program). Malformed values
    fall back to the default."""
    try:
        return max(1, int(os.environ.get("WF_MEGABATCH", str(default))))
    except ValueError:
        return default


def split_commit(launch: Callable[[], Optional[Callable[[], None]]]):
    """Mark a commit thunk as a LAUNCH half that returns its finish (the
    readback-and-emit of the program it launched; see module doc). The
    queue must know before it runs a commit whether the call emits, so
    the mark rides on the thunk like the megabatch metadata does."""
    launch.returns_finish = True
    return launch


class DeviceDispatchQueue:
    """Bounded FIFO of deferred device-commit thunks, and the one pending
    finish of the last commit that split (see module doc)."""

    def __init__(self, stats=None, depth: Optional[int] = None,
                 megabatch: Optional[int] = None) -> None:
        self.depth = dispatch_depth() if depth is None else max(0, depth)
        self.megabatch = (megabatch_k() if megabatch is None
                          else max(1, megabatch))
        # a K-wide megabatch can only form if K prepped commits can sit
        # in the queue; the scan loop implies at least that much lag.
        # depth 0 (synchronous) wins: commits never queue at all.
        if self.depth > 0 and self.megabatch > 1:
            self.depth = max(self.depth, self.megabatch)
        self.stats = stats
        owner = stats if stats is not None else StageCounters()
        self._st_prep = owner.stage("prep")
        self._st_queue = owner.stage("queue")
        self._st_commit = owner.stage("commit")
        # entries are (commit, batch id, enqueue stamp): the stamp feeds
        # the wait:queue residency (how long the prepared batch sat in
        # the queue before its commit ran)
        self._q: "deque" = deque()
        # (finish, batch id) of the last split commit launched, until
        # the next launch (or a drain) has run
        self._pending: Optional[tuple] = None

    def __len__(self) -> int:
        return len(self._q) + (self._pending is not None)

    # ------------------------------------------------------------------
    def prep(self, b: int = 0):
        """The ``wf:prep`` stage of batch ``b``: the replica wraps its
        host-prep in it (``with dispatch.prep(batch.bid):``), which also
        counts the batch (``Dispatch_batches``)."""
        return self._st_prep(b)

    def submit(self, commit: Callable, b: int = 0) -> None:
        """Queue (or, at depth 0, run, both halves) the device-commit
        stage of batch ``b``. Overflowing ``depth`` commits the oldest
        entry — the blocking pop that gives the pipeline its bounded
        lag."""
        if self.depth == 0:
            self._run(commit, b)
            self._finish_pending()
            return
        self._q.append((commit, b, stamp_ns()))
        # record the PEAK occupancy (post-append, pre-pop): a pipeline
        # running steady-state at full depth overflows on every submit,
        # and recording only the post-pop length would under-report
        # Dispatch_queue_depth_max as never-saturated
        if self.stats is not None:
            self.stats.note_dispatch_depth(len(self._q))
        while len(self._q) > self.depth:
            self._pop_run()

    def drain(self, forced: bool = False) -> None:
        """Commit everything in flight, every launch and every finish.
        ``forced=True`` marks an ordering-point drain (punctuation/EOS/
        device-state access) in the stats as a readback stall — the
        pipeline had to give up its lag."""
        if forced and len(self) and self.stats is not None:
            self.stats.note_dispatch_stall()
        while self._q:
            self._run(*self._q.popleft())
        self._finish_pending()

    def on_idle(self) -> bool:
        """Worker idle tick: a quiet stream must not park prepared
        batches (same contract as ``_D2HPipeline.on_idle``). Returns
        whether anything was committed (drives the worker's backoff)."""
        had = len(self) > 0
        self.drain()
        return had

    def abort(self) -> None:
        """Discard pending commits and the pending finish WITHOUT running
        them (error unwind: the entries were prepped against
        control-plane state the failed batch already advanced)."""
        self._q.clear()
        self._pending = None

    # ------------------------------------------------------------------
    def _pop_run(self) -> None:
        """Overflow pop: commit the oldest entry — or, with megabatching
        on, the longest same-signature power-of-two FRONT run as one
        grouped scan dispatch. Popping never reorders: the group is a
        contiguous prefix and the scan walks it in submission order."""
        q = self._q
        k = self.megabatch
        sig = (getattr(q[0][0], "scan_sig", None) if k > 1 else None)
        if sig is None:
            self._run(*q.popleft())
            return
        run = 1
        while run < k and run < len(q) \
                and getattr(q[run][0], "scan_sig", None) == sig:
            run += 1
        g = 1 << (run.bit_length() - 1)  # largest power of two <= run
        if g < 2:
            self._run(*q.popleft())
            return
        self._run_group([q.popleft() for _ in range(g)])

    def _run_group(self, entries) -> None:
        """Run a same-signature group through the commits' scan runner
        (``FusedTPUReplica._run_megabatch``): one program, one dispatch,
        len(entries) batches. Error unwind matches ``_run`` — a failed
        group aborts the remaining pipeline entries."""
        for _commit, b, enq_ns in entries:
            self._st_queue.since(enq_ns, b)
        commits = [commit for commit, _b, _t in entries]
        self._finish_pending()  # the group emits inside its commit
        try:
            with self._st_commit(entries[0][1]):  # named for its first batch
                commits[0].scan_runner(commits)
        except BaseException:
            self.abort()
            raise

    def _run(self, commit: Callable, b: int = 0,
             enq_ns: Optional[int] = None) -> None:
        """One commit: whole (after the pending finish: it emits), or a
        launch half, after which the PREVIOUS launch's finish runs."""
        if enq_ns is not None:
            self._st_queue.since(enq_ns, b)
        split = getattr(commit, "returns_finish", False)
        if not split:
            self._finish_pending()
        try:
            with self._st_commit(b):
                finish = commit()
        except BaseException:
            self.abort()
            raise
        prev = self._pending
        self._pending = (finish, b) if split and finish is not None else None
        if prev is not None:
            self._finish(*prev, deferred=True)

    def _finish_pending(self) -> None:
        """Run the pending finish with no later launch issued (depth 0,
        the end of a drain, ahead of a commit that emits)."""
        prev = self._pending
        if prev is not None:
            self._pending = None
            self._finish(*prev, deferred=False)

    def _finish(self, finish: Callable[[], None], b: int,
                deferred: bool) -> None:
        """A finish half, under ``wf:commit`` with its own batch's id;
        one that raises aborts the rest as a commit does."""
        try:
            with self._st_commit(b):
                finish()
        except BaseException:
            self.abort()
            raise
        if self.stats is not None:
            self.stats.note_dispatch_readback(deferred)

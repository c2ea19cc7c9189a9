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
host prep: its Python-side program-call overhead, the donation hand-off
of the replica's device state, and above all the emit path's readbacks
(an ``np.asarray``/``int()`` on a fresh program output blocks until that
program ran). This queue defers the commit stage of up to ``depth``
batches, mirroring ``_D2HPipeline`` on the exit edges: by the time a
commit is popped, ``depth`` later batches have been prepped and the
deferred readbacks land on long-materialized results instead of
stalling. ``WF_DISPATCH_DEPTH=0`` restores the fully synchronous path
(commit runs inside ``submit``), which the differential tests pin
against depth >= 2 for exact result equality.

Ordering contract: commits run strictly in submission order, on the
replica's own worker thread (no cross-thread hand-off — the queue is a
deferral buffer, not a concurrency primitive). The replica drains it at
every ordering point: before punctuation propagates, at EOS/terminate,
before any host code touches the replica's device state (forest/table
growth, program warm-up), and on the worker's idle tick so a quiet
stream never parks prepared batches. A commit that raises marks the
pipeline broken and discards the remaining entries — they were prepped
against control-plane state the failed batch already advanced, so
re-running them after the error would emit from an inconsistent forest;
the error itself unwinds the worker (drain-inputs + emergency EOS).

MEGABATCH (``WF_MEGABATCH=K``, default 1 = off): when the queue
overflows, a FRONT run of commits carrying the same ``scan_sig`` (same
fused chain, same program signature, same capacity bucket —
``tpu/fused_ops.py`` attaches the attribute) is popped as ONE group and
handed to the commits' ``scan_runner``, which executes all of them in a
single jitted ``lax.scan`` over the chain program: K batches, ONE host
dispatch. Only the largest power-of-two prefix of the run groups (so
the set of compiled scan programs stays enumerable for the pre-warm);
mixed-signature, non-fused, or lone commits run as singles. ``drain``
always runs singles, so every ordering point — punctuation, EOS,
checkpoint snapshot, device-state access, error unwind — degrades to
K=1 and the alignment/exactly-once/rescale semantics are untouched.
Commits still run strictly in submission order either way (the scan
body IS the chain program, threading the same carried state
batch-to-batch).

Per-stage instrumentation lands in the replica's ``StatsRecord``
through the stage helper (``monitoring/tracing.py``): ``wf:prep`` around
the host prep (``prep()``), ``wf:commit`` around each commit, and the
time a prepared batch sat in the queue as the ``wait:queue`` residency
(``Dispatch_host_prep/commit_total_usec``, ``Dispatch_queue_wait_total_usec``
and their EWMAs, forced-drain stall count, max queue depth), so the
host-prep/device split is measured, not asserted.
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


class DeviceDispatchQueue:
    """Bounded FIFO of deferred device-commit thunks (see module doc)."""

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

    def __len__(self) -> int:
        return len(self._q)

    # ------------------------------------------------------------------
    def prep(self, b: int = 0):
        """The ``wf:prep`` stage of batch ``b``: the replica wraps its
        host-prep in it (``with dispatch.prep(batch.bid):``), which also
        counts the batch (``Dispatch_batches``)."""
        return self._st_prep(b)

    def submit(self, commit: Callable[[], None], b: int = 0) -> None:
        """Queue (or, at depth 0, run) the device-commit stage of batch
        ``b``. Overflowing ``depth`` commits the oldest entry — the
        blocking pop that gives the pipeline its bounded lag."""
        if self.depth == 0:
            self._run(commit, b)
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
        """Commit everything in flight. ``forced=True`` marks an
        ordering-point drain (punctuation/EOS/device-state access) in the
        stats as a readback stall — the pipeline had to give up its lag."""
        if forced and self._q and self.stats is not None:
            self.stats.note_dispatch_stall()
        while self._q:
            self._run(*self._q.popleft())

    def on_idle(self) -> bool:
        """Worker idle tick: a quiet stream must not park prepared
        batches (same contract as ``_D2HPipeline.on_idle``). Returns
        whether anything was committed (drives the worker's backoff)."""
        had = bool(self._q)
        self.drain()
        return had

    def abort(self) -> None:
        """Discard pending commits WITHOUT running them (error unwind:
        the entries were prepped against control-plane state the failed
        batch already advanced)."""
        self._q.clear()

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
        try:
            with self._st_commit(entries[0][1]):  # named for its first batch
                commits[0].scan_runner(commits)
        except BaseException:
            self.abort()
            raise

    def _run(self, commit: Callable[[], None], b: int = 0,
             enq_ns: Optional[int] = None) -> None:
        if enq_ns is not None:
            self._st_queue.since(enq_ns, b)
        try:
            with self._st_commit(b):
                commit()
        except BaseException:
            self.abort()
            raise

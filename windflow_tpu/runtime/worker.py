"""Worker threads: one OS thread per replica-chain.

Parity: FastFlow spawns one pinned thread per node
(``wf/pipegraph.hpp:610-764`` run path); chained operators share a thread
(``wf/multipipe.hpp:569-585``), and the stage collector is fused in front of
the first replica. Termination mirrors the reference's EOS cascade: sources
finish their loop, EOS flows per-edge, each replica flushes windows/partial
batches on the way down (``wf/basic_operator.hpp:180-189``).

Checkpointing (no reference analog — ``windflow_tpu.checkpoint``): the
worker is also the alignment point for checkpoint barriers. ``Barrier``
messages ride the channels like EOS (one per producer edge, intercepted
here, never delivered to collectors/replicas); a ``BarrierAligner`` buffers
post-barrier input from already-barriered channels until every live channel
delivered the barrier, then ``checkpoint_now`` drains the chain's device
dispatch queues, flushes partial output batches, forwards the barrier
downstream, snapshots every fused node (collector included) and acks the
coordinator with the blobs.

Error handling is stricter than the reference (which prints and
``exit(EXIT_FAILURE)``): a replica that throws records the error, drains its
inputs, and force-propagates EOS downstream so the whole graph unwinds and
``PipeGraph.wait_end`` can re-raise in the caller's thread.
"""

from __future__ import annotations

import threading
import time
from typing import Any, List, Optional, Tuple

from ..basic import RescaleTeardown
from ..message import EOS, Barrier
from ..monitoring.tracing import (account_waits, new_thread_account,
                                  set_thread_account)
from .channel import Channel
from .collectors import BarrierAligner

# Linux and most Unixes; where it is missing Thread_cpu_usec reads 0 until
# the thread has ended
_THREAD_CPU_CLOCK = getattr(time, "pthread_getcpuclockid", None)


class Worker(threading.Thread):
    """Runs a chain ``[collector?] + [replica_op1, replica_op2, ...]``.

    For source stages ``channel`` is None and the first chain node must be a
    SourceReplica (drives its own generation loop).
    """

    def __init__(self, wname: str, chain: List[Any],
                 channel: Optional[Channel] = None,
                 coordinator: Optional[Any] = None,
                 flightrec: Optional[Any] = None) -> None:
        super().__init__(name=wname, daemon=True)
        self.chain = chain
        self.channel = channel
        self.coordinator = coordinator  # CheckpointCoordinator or None
        self.error: Optional[BaseException] = None
        # flight recorder (monitoring/flightrec.py): this worker's event
        # ring, shared with every chain node's StatsRecord so the stats
        # hooks (svc/prep/commit/snapshot) append spans to it
        self.flightrec = flightrec
        # crash hook (PipeGraph wires a post-mortem trace dump); the
        # watchdog needs idle ticks even without idle sinks, so a
        # blocked-forever-on-input worker still advances its counter
        self.on_crash: Optional[Any] = None
        # supervised recovery (windflow_tpu.supervision): when wired, a
        # dying worker notifies the supervisor and exits WITHOUT the
        # drain + emergency-EOS unwind — an EOS mid-recovery would tell
        # sinks the stream completed; the supervisor owns the teardown
        self.on_failure: Optional[Any] = None
        self.force_idle_tick = False
        self._progress = 0  # channel deliveries + idle ticks (watchdog)
        self._eos_seen = 0
        self._has_coll = hasattr(chain[0], "on_channel_eos")
        # replicas = chain nodes that carry operator state (the collector,
        # when present, is snapshotted alongside the first replica).
        # Deduped by identity: every sub-op of a fused device stage
        # aliases ONE FusedTPUReplica, which must drain/snapshot/
        # terminate exactly once
        self._replicas = []
        for n in chain:
            if hasattr(n, "snapshot_state") and hasattr(n, "op") \
                    and not any(n is r for r in self._replicas):
                self._replicas.append(n)
        if flightrec is not None:
            for n in chain:
                st = getattr(n, "stats", None)
                if st is not None:
                    st.recorder = flightrec
        # this thread's CPU and wall clocks, reported by the first chain
        # node that owns a StatsRecord (Thread_cpu_usec / Thread_wall_usec;
        # where Worker_idle_ticks goes). Nothing on the hot path: another
        # thread reads the clocks at poll time, under a lock that keeps
        # this thread alive for the read, and run() leaves the final
        # values behind when it ends
        self._clock_lock = threading.Lock()
        self._t0_ns: Optional[int] = None
        self._end_clocks: Optional[Tuple[int, int]] = None
        # this thread's own waits in nanoseconds, by the slots of
        # monitoring/tracing.py (BACKPRESSURED, STARVED, DEVICE_WAIT): the
        # stage helper adds a closed ``wait:put`` / ``wait:get`` span, and
        # the wall less the CPU of a readback, d2h or launch span, on the
        # thread that ran it (Worker_blocked_put/get_usec,
        # Worker_device_wait_usec beside the two clocks)
        self._account = new_thread_account()
        st = self._stats()
        if st is not None:
            st.worker = self
        self._aligner: Optional[BarrierAligner] = None
        if coordinator is not None and channel is None and chain:
            # source chain: the source replica injects barriers at tuple
            # boundaries and hands the chain snapshot back to us
            bind = getattr(chain[0], "bind_checkpoint", None)
            if bind is not None:
                bind(coordinator, self.checkpoint_now)
        if coordinator is not None:
            # exactly-once sinks (windflow_tpu.sinks.transactional):
            # register their commit-on-finalize listener with the
            # coordinator that drives their epochs
            for n in self._replicas:
                bind = getattr(n, "bind_txn_coordinator", None)
                if bind is not None:
                    bind(coordinator)

    def run(self) -> None:
        set_thread_account(self._account)
        self._t0_ns = time.perf_counter_ns()
        try:
            self._run()
        finally:
            with self._clock_lock:
                self._end_clocks = (
                    time.thread_time_ns(),
                    time.perf_counter_ns() - self._t0_ns)

    def thread_clocks(self) -> Tuple[int, int]:
        """``(cpu_ns, wall_ns)`` of this worker's thread: CPU time it has
        used and time since it started, frozen once it has ended. Called
        from other threads (``StatsRecord.to_dict``)."""
        with self._clock_lock:
            if self._end_clocks is not None:
                return self._end_clocks
            if self._t0_ns is None:
                return 0, 0  # not started
            cpu_ns = (time.clock_gettime_ns(_THREAD_CPU_CLOCK(self.ident))
                      if _THREAD_CPU_CLOCK is not None else 0)
            return cpu_ns, time.perf_counter_ns() - self._t0_ns

    def thread_waits(self) -> List[int]:
        """``[backpressured, starved, on the device]`` nanoseconds of this
        worker's thread so far, a wait it stands in now included."""
        return account_waits(self._account)

    def _run(self) -> None:
        if self.flightrec is not None:
            # blocked channel puts/gets and shared-program compiles find
            # this thread's ring through the TLS slot
            from ..monitoring.flightrec import set_thread_recorder
            set_thread_recorder(self.flightrec)
        try:
            self._process()
            self._retire()
            self._shutdown()
        except RescaleTeardown:
            # elastic rescale (windflow_tpu.scaling): the controller is
            # rebuilding the runtime plane from the checkpoint we just
            # acked — exit silently, no EOS cascade, no retirement (our
            # channels and emitters are about to be discarded)
            return
        except BaseException as e:
            self.error = e
            # crash visibility FIRST (while the ring still holds the
            # run-up): record the error into the stats plane, then the
            # post-mortem dump hook — only then unwind
            try:
                self._record_crash(e)
            except BaseException:
                pass
            if self.on_failure is not None:
                # supervised: the supervisor tears the plane down and
                # restores from checkpoint — no drain (the channels are
                # about to be discarded) and NO emergency EOS (sinks
                # must not see an end-of-stream marker mid-recovery)
                try:
                    self.on_failure(self)
                except BaseException:
                    pass
                return
            # unwind so sibling workers never block on us: swallow the rest
            # of our input, then force EOS downstream
            try:
                self._drain_inputs()
            except BaseException:
                pass
            try:
                self._emergency_eos()
            except BaseException:
                pass

    def _record_crash(self, e: BaseException) -> None:
        """The BaseException path used to die as a silent daemon thread;
        now the exception type + traceback land in ``Worker_last_error``
        (surfaced by ``PipeGraph.get_stats`` and the
        ``windflow_worker_crashes_total`` metric family), a ``crash``
        event enters the flight ring, and the PipeGraph's post-mortem
        hook dumps the trace."""
        import traceback

        stats = self._stats()
        if stats is not None:
            stats.worker_crashes += 1
            stats.worker_last_error = "".join(
                traceback.format_exception(type(e), e, e.__traceback__))
        if self.flightrec is not None:
            self.flightrec.event("crash", 0.0,
                                 f"{type(e).__name__}: {e}")
        if self.on_crash is not None:
            self.on_crash(self, e)

    def progress_value(self) -> int:
        """Monotone liveness counter for the stall watchdog: advances on
        every channel delivery and idle tick, plus tuples moved by the
        head replica (a source's loop never returns to ``_process``, and
        a worker stuck INSIDE one long message would otherwise look
        live)."""
        v = self._progress
        stats = self._stats()
        if stats is not None:
            # shed records count as progress: a source under admission
            # control is actively REFUSING work, not wedged
            v += (stats.inputs_received + stats.outputs_sent
                  + stats.shed_records)
        return v

    # -- normal path -------------------------------------------------------
    def _process(self) -> None:
        head = self.chain[0]
        if self.channel is None:
            head.run_source()
            # a pending epoch the loop never reached injects at EOS time:
            # a finished source's final position is a valid snapshot
            # (restore resumes it as already-complete), and without it the
            # checkpoint could never gather all acks
            fin = getattr(head, "final_checkpoint", None)
            if fin is not None:
                fin()
            return
        n_inputs = self.channel.n_inputs
        if self.coordinator is not None:
            self._aligner = BarrierAligner(n_inputs)
        # anything that pipelines work (replica dispatch queues, emitter
        # D2H FIFOs) must not withhold results forever on an idle stream:
        # poll with a timeout and give it an idle tick when the channel
        # stays quiet. Chain order, node before its emitter — a drained
        # dispatch queue emits INTO the emitter's FIFO, which the same
        # tick then delivers.
        import os

        idle_sinks = []
        for node in self.chain:
            if hasattr(node, "on_idle"):
                idle_sinks.append(node)
            em = getattr(node, "emitter", None)
            if em is not None and hasattr(em, "on_idle"):
                idle_sinks.append(em)
        try:
            idle_ms = float(os.environ.get("WF_IDLE_DRAIN_MS", "50"))
        except ValueError:
            idle_ms = 50.0  # malformed knob must not take down the graph
        # <= 0 disables the tick (a 0 timeout would busy-spin when idle)
        # (the stall watchdog forces the tick even without idle sinks:
        # a worker parked forever in channel.get would otherwise never
        # advance its progress counter and read as stalled)
        idle_s = idle_ms / 1e3 \
            if (idle_sinks or self.force_idle_tick) and idle_ms > 0 else None
        # back off (up to 16x) when consecutive idle ticks find nothing to
        # drain, so a fully idle graph doesn't wake every worker at 20 Hz
        # on a small host; any real message resets the cadence
        idle_streak = 0
        # idle ticks are observability too: attribute them to the first
        # chain node that owns a StatsRecord (Worker_idle_ticks)
        stats = self._stats()
        while self._eos_seen < n_inputs:
            backoff = idle_s if idle_s is None else idle_s * min(
                16, 1 << min(idle_streak, 4))
            item = self.channel.get(backoff)
            self._progress += 1  # liveness for the stall watchdog
            if item is None:  # idle tick
                if stats is not None:
                    stats.worker_idle_ticks += 1
                did_work = False
                for sink in idle_sinks:
                    did_work = bool(sink.on_idle()) or did_work
                idle_streak = 0 if did_work else idle_streak + 1
                continue
            idle_streak = 0
            self._handle_item(item[0], item[1])

    def _handle_item(self, ch: int, msg: Any) -> None:
        """One channel delivery: barrier alignment first, then the normal
        EOS / message path. Re-entered for buffered post-barrier items
        after a snapshot (a buffered item may itself be the next Barrier,
        opening the next alignment)."""
        al = self._aligner
        if al is not None and al.blocked(ch):
            # post-barrier input on an aligned channel: park it. EOS too
            # (consuming it early would mutate collector state
            # mid-snapshot), and so is a next-epoch Barrier — channels are
            # FIFO, so anything behind the current epoch's barrier belongs
            # to the next alignment and replays after the snapshot.
            al.buffered.append((ch, msg))
            return
        if isinstance(msg, Barrier):
            if al is not None and al.on_barrier(ch, msg):
                self._complete_alignment()
            return  # checkpointing off: stray barriers are dropped
        if isinstance(msg, EOS):
            self._eos_seen += 1
            if self._has_coll:
                self.chain[0].on_channel_eos(ch)
            if al is not None and al.on_eos(ch):
                self._complete_alignment()
            return
        self.chain[0].handle_msg(ch, msg)

    def _complete_alignment(self) -> None:
        barrier, stall_us, buffered = self._aligner.take()
        self.checkpoint_now(barrier, stall_us)
        for ch, msg in buffered:
            self._handle_item(ch, msg)

    # -- checkpointing -----------------------------------------------------
    def _stats(self):
        return next((n.stats for n in self.chain
                     if getattr(n, "stats", None) is not None), None)

    def checkpoint_now(self, barrier: Barrier, stall_us: float = 0.0) -> None:
        """Snapshot the whole chain for one aligned barrier. Runs on this
        worker's own thread (from ``_complete_alignment``, or from the
        source replica's injection hook mid-``run_source``), so no tuple
        is in flight anywhere in the chain.

        Order matters: (1) chain-ordered drain of each node's device
        dispatch queue + flush of its emitter, so every pre-barrier tuple
        lands in downstream channels (or fused successors) BEFORE the
        barrier; (2) barrier downstream via the last emitter (which
        flushes again first); (3) state capture; (4) ack with blobs —
        the coordinator commits once every worker acked."""
        coord = self.coordinator
        if coord is None:
            return
        t0 = time.perf_counter()
        replicas = self._replicas
        last = replicas[-1] if replicas else None
        for node in replicas:
            dq = getattr(node, "dispatch", None)
            if dq is not None:
                dq.drain(forced=True)
            em = node.emitter
            if em is not None and node is not last:
                em.flush()  # inline edge: feeds the next fused node now
        if last is not None and last.emitter is not None:
            last.emitter.send_barrier_all(barrier)
        # exactly-once sinks pre-commit the epoch BEFORE the blobs are
        # captured (and before our ack can let the coordinator finalize
        # it): everything staged since the previous barrier becomes this
        # epoch's durable, not-yet-visible segment/transaction
        for node in replicas:
            hook = getattr(node, "precommit_epoch", None)
            if hook is not None:
                hook(barrier.ckpt_id)
        # the capture runs under the snapshot context: engines that
        # track touched slots may emit delta-form states (WF_CKPT_DELTA)
        # for THIS epoch against their last full snapshot. The capture
        # is a copy (device_get / host copies), so in async mode
        # (WF_CKPT_ASYNC) the ack returns as soon as the blobs are
        # registered and the pause the barrier imposes ends HERE — the
        # serialization + writes happen on the coordinator's uploader.
        from ..checkpoint import delta as _ckpt_delta
        with _ckpt_delta.capturing(barrier.ckpt_id, coord.store):
            blobs = self._capture_blobs()
        nbytes = coord.ack(barrier.ckpt_id, self.name, blobs)
        cut_us = (time.perf_counter() - t0) * 1e6
        stats = self._stats()
        if stats is not None:
            stats.note_checkpoint(cut_us, nbytes, stall_us, cut_us=cut_us)
        if self.flightrec is not None:
            self.flightrec.event("ckpt:cut", cut_us,
                                 {"ckpt_id": barrier.ckpt_id,
                                  "bytes": nbytes})
            if _ckpt_delta.env_ckpt_delta():
                ndelta = sum(1 for st in blobs.values()
                             if _ckpt_delta.delta_bases(st))
                if ndelta:
                    self.flightrec.event("ckpt:delta", 0.0,
                                         {"ckpt_id": barrier.ckpt_id,
                                          "delta_blobs": ndelta})
            self.flightrec.event("ckpt_ack", 0.0,
                                 {"ckpt_id": barrier.ckpt_id,
                                  "bytes": nbytes})
        # rescale quiesce point (windflow_tpu.scaling): a held epoch
        # parks every worker right here — after the ack, with all
        # pre-barrier output flushed and the barrier forwarded, before
        # any post-barrier tuple is produced
        t_park = time.perf_counter()
        directive = coord.park_if_held(barrier.ckpt_id, self.name)
        if directive is not None:
            if self.flightrec is not None:
                self.flightrec.event(
                    "rescale:parked",
                    (time.perf_counter() - t_park) * 1e6,
                    {"ckpt_id": barrier.ckpt_id,
                     "directive": directive})
            if directive == "abandon":
                raise RescaleTeardown()

    def _capture_blobs(self) -> dict:
        blobs = {}
        for node in self._replicas:
            dq = getattr(node, "dispatch", None)
            if dq is not None:
                dq.drain(forced=True)
            state = node.snapshot_state()
            if node.emitter is not None:
                state["__emitter__"] = node.emitter.emitter_state()
            blobs[(node.op.name, node.idx)] = state
        if self._has_coll and self._replicas:
            coll_state = self.chain[0].snapshot_state()
            if coll_state:
                blobs[(self._replicas[0].op.name,
                       self._replicas[0].idx)]["__collector__"] = coll_state
        return blobs

    def _retire(self) -> None:
        """Clean exit with checkpointing on: hand the coordinator our
        final state so epochs opened after we finish still complete (a
        finished worker's state is frozen — captured BEFORE the EOS
        flush, so a restore re-runs the flush exactly like a live
        replica would)."""
        if self.coordinator is not None:
            self.coordinator.retire(self.name, self._capture_blobs())

    def _shutdown(self) -> None:
        # EOS cascade: terminate in chain order so that anything emitted by
        # an upstream node's flush is processed by the downstream fused nodes
        # before they flush themselves.
        for node in self.chain:
            node.terminate()
        last = self.chain[-1]
        if getattr(last, "emitter", None) is not None:
            last.emitter.send_eos_all()

    # -- error path --------------------------------------------------------
    def _drain_inputs(self) -> None:
        if self.channel is None:
            return
        n_inputs = self.channel.n_inputs
        while self._eos_seen < n_inputs:
            _, msg = self.channel.get()
            if isinstance(msg, EOS):
                self._eos_seen += 1

    def _emergency_eos(self) -> None:
        last = self.chain[-1]
        em = getattr(last, "emitter", None)
        if em is not None:
            for port in em.eos_ports():
                try:
                    port.send_eos()
                except BaseException:
                    pass

"""The host timeline of the device plane, and the sampling knobs of the
per-tuple latency plane.

**Stages.** Every per-batch site of the served path is timed by ONE
helper, taken from the owning replica's ``StatsRecord`` when the replica
or emitter is built (``self._st_readback = stats.stage("readback")``) and
used as ``with self._st_readback(batch.bid):``. On entry and exit it
reads ``perf_counter_ns`` once each and writes three things at once:

- a ``jax.profiler.TraceAnnotation`` named ``<prefix>:<stage>:<op>`` with
  ``b=<batch id>`` (and ``cause=<id>`` where a batch was made by another
  batch's commit), so a device profile shows the host's work on the
  profiler's clock (inert when no profile runs);
- the stage's cumulative nanoseconds and count on the ``StatsRecord``
  (``get_stats()`` exports them under the names ``STAGES`` lists; a
  reader takes deltas over its window);
- the same span, under the same name and ids, in the thread's flight
  recorder ring when it has one (``monitoring/flightrec.py``).

``STAGES`` is the one name table: a stage that is not in it raises where
the replica or emitter is built, never per batch. The prefix says what
the thread does inside the span: ``wf`` is host work (the benchmark's
trace reduction attributes device idle gaps to ``wf:`` spans only),
``wait`` is a thread blocked on a channel or a batch waiting in a queue
(``Stage.since``: a residency span from an enqueue stamp to now, ring
and counters only), ``blk`` is the envelope of one source block, work
and waits together. Per batch, never per tuple: the CPU plane's
per-tuple path has no stage.

**CPU beside wall, and a worker's own waits.** Six stages (``prep``,
``commit``, ``launch``, ``readback``, ``d2h``, ``ingest``:
``StageDef.cpu``) also read the thread's CPU clock, ``thread_time_ns``,
inside the two wall reads, on one span in ``CPU_EVERY`` and scaled up (the
clock is a system call): a stage's wall less its CPU is time the thread
was off the processor inside it, as an estimate over many spans (where
that clock moves by the scheduler's tick, one span's CPU reads 0 or a
whole tick anyway). The stages
``StageDef.waits`` marks (``put`` and ``get`` by their wall; ``launch``,
``readback`` and ``d2h`` by their wall less their CPU, since a blocking
read may spin) add what they measured to the account of the thread that
RAN them
(``set_thread_account``, a worker's own: ``Worker_blocked_put_usec``,
``Worker_blocked_get_usec``, ``Worker_device_wait_usec`` in
``get_stats()``), so a producer's record says how long it stood
backpressured while the consumer's ``Queue_blocked_put_usec`` says whose
queue was full. Always on, like the counters; no other stage pays for it.

**Batch ids.** ``next_batch_id()`` numbers batches process-wide at the
staging edge; ``BatchTPU.bid`` travels with every batch derived from it
and ``BatchTPU.cause`` names the input batch whose commit made a new one
(a window fire, a re-shard split). ``id`` stays the per-channel sequence
number the ordering collectors read.

**Latency sampling.** Sources stamp a sampled subset of tuples with a
wall-clock origin (``Single.trace_ts``; batches carry ``trace_min`` /
``trace_max``), sinks record end-to-end latency into their replica's
``LatencyHistogram``, and every replica records sampled service time and
(device plane) prep/commit latency. ``WF_LATENCY_SAMPLE`` globally, or
per operator ``with_latency_tracing(rate)``: ``1``, a fraction
``"1/64"``, a float ``0.01``, or ``0`` (off, the default: no clock
reads, no histogram work on the hot path). A rate becomes a sampling
INTERVAL (record every Nth), so sampling is deterministic and divides
exactly under test.
"""

from __future__ import annotations

import itertools
import os
import re
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

__all__ = ["parse_sample_rate", "env_sample_every", "resolve_sample_every",
           "STAGES", "StageDef", "StageCounters", "Stage", "next_batch_id",
           "stamp_ns", "program_name", "new_thread_account",
           "set_thread_account", "account_waits",
           "BACKPRESSURED", "STARVED", "DEVICE_WAIT"]


def parse_sample_rate(value) -> int:
    """Sampling rate -> interval N (record every Nth sample; 0 = off).

    Accepts 1 / "1" (every tuple), "1/64" (every 64th), a float in
    (0, 1], or 0/""/None (off). Malformed values fall back to off — a
    bad knob must not take down the graph. Intervals round UP to a
    power of two: the source's per-tuple sampling gate is then a single
    integer AND against ``interval - 1`` — the same cost whether
    sampling is on or off, so enabling 1/64 tracing costs only the
    sampled work itself."""
    if value is None:
        return 0
    if isinstance(value, str):
        value = value.strip()
        if not value:
            return 0
        if "/" in value:
            try:
                num, den = value.split("/", 1)
                rate = float(num) / float(den)
            except (ValueError, ZeroDivisionError):
                return 0
        else:
            try:
                rate = float(value)
            except ValueError:
                return 0
    else:
        try:
            rate = float(value)
        except (TypeError, ValueError):
            return 0
    if rate <= 0:
        return 0
    if rate >= 1:
        return 1
    n = max(1, round(1.0 / rate))
    return 1 << (n - 1).bit_length()  # next power of two >= n


def env_sample_every() -> int:
    """The global sampling interval from ``WF_LATENCY_SAMPLE`` (0=off)."""
    return parse_sample_rate(os.environ.get("WF_LATENCY_SAMPLE"))


def resolve_sample_every(op) -> int:
    """Per-operator interval: the builder knob wins over the env. The
    result is always 0 or a power of two (the mask-gate contract)."""
    s = getattr(op, "latency_sample", None)
    if s is None:
        return env_sample_every()
    s = max(0, int(s))
    if s & (s - 1):  # direct op.latency_sample writes may skip the parse
        s = 1 << (s - 1).bit_length()
    return s


# ---------------------------------------------------------------------------
# stages: one helper writes profiler span, cumulative counter and ring event
# ---------------------------------------------------------------------------
class StageDef(NamedTuple):
    prefix: str           # wf (host work) | wait (blocked / queued) | blk
    layer: str            # PERF.md section 3 / BENCHMARK.json layer name
    total: Optional[str]  # get_stats() field of the cumulative usec
    count: Optional[str]  # get_stats() field of the span count
    note: Optional[str] = None  # StatsRecord latency histogram fed each
    # duration (us); bound only where sampling allocated it
    foreign: bool = False  # may run off the owner's worker thread: the
    # ring is then the CALLING thread's (single-writer rings), and a span
    # given no batch id takes the one of the enclosing ``scope`` stage
    scope: bool = False  # its batch id is the thread's while it is open
    cpu: Optional[str] = None  # get_stats() field of the cumulative
    # THREAD-CPU usec (``thread_time_ns`` beside the wall clock, for these
    # stages only): wall less CPU is time off the processor inside it
    waits: Optional[int] = None  # slot of the CALLING worker's account
    # (``set_thread_account``) the span's wall, less its CPU where ``cpu``
    # is set, is added to: a thread blocked, not a thread working


_DISPATCH, _STAGING, _EXIT = "dispatch", "staging, H2D", "exit, D2H"
_CHANNELS = "channels, workers"
# a worker's own waits (``Worker_blocked_put/get_usec``,
# ``Worker_device_wait_usec``): the slots of its thread's account
BACKPRESSURED, STARVED, DEVICE_WAIT = 0, 1, 2

STAGES: Dict[str, StageDef] = {
    # source thread
    "ingest": StageDef("blk", _STAGING, None, "Ingest_blocks",
                       cpu="Ingest_cpu_total_usec"),
    "stage": StageDef("wf", _STAGING, "Stage_copy_total_usec", None),
    "h2d": StageDef("wf", _STAGING, "Stage_h2d_put_total_usec",
                    "Stage_batches"),
    # any producer / the consuming worker, blocked branches only
    "put": StageDef("wait", _CHANNELS, "Queue_blocked_put_usec",
                    "Queue_puts_blocked", foreign=True, waits=BACKPRESSURED),
    # idle ticks too: a ``silent`` span is counted
    "get": StageDef("wait", _CHANNELS, "Queue_blocked_get_usec", None,
                    waits=STARVED),
    # device operator's worker
    "prep": StageDef("wf", _DISPATCH, "Dispatch_host_prep_total_usec",
                     "Dispatch_batches", note="hist_prep", scope=True,
                     cpu="Dispatch_host_prep_cpu_total_usec"),
    # the window operator's fire planning, inside its prep (``count``
    # stays None: the plan runs once per batch, Dispatch_batches counts)
    "fireplan": StageDef("wf", _DISPATCH, "Fire_plan_total_usec", None),
    # the window operator's key turnover, inside its prep (or a dataless
    # fire): new keys given a slot, dead slots given back
    "keys": StageDef("wf", _DISPATCH, "Key_turnover_total_usec", None),
    # the interval join's host half, inside its prep: the batch's event
    # times as offsets, the purge lines, the bound on the archives
    "join": StageDef("wf", _DISPATCH, "Join_host_total_usec", None),
    # a keyed state operator's grid assembly, inside its prep (a fused
    # chain's: one span a stateful member, labelled by the member): the
    # key lookup and admission, table growth, the grid's cells
    "grid": StageDef("wf", _DISPATCH, "Scan_host_total_usec", None),
    "queue": StageDef("wait", _DISPATCH, "Dispatch_queue_wait_total_usec",
                      None),
    "commit": StageDef("wf", _DISPATCH, "Dispatch_commit_total_usec", None,
                       note="hist_commit", scope=True,
                       cpu="Dispatch_commit_cpu_total_usec"),
    # wall less CPU: the Python of a launch told from a wait on the
    # device's queue
    "launch": StageDef("wf", _DISPATCH, "Device_launch_total_usec", None,
                       foreign=True, cpu="Device_launch_cpu_total_usec",
                       waits=DEVICE_WAIT),
    # a blocking read may spin on the CPU while the device works: that is
    # the thread busy, and only the wall less it a wait
    "readback": StageDef("wf", _DISPATCH,
                         "Dispatch_readback_wait_total_usec", None,
                         cpu="Dispatch_readback_cpu_total_usec",
                         waits=DEVICE_WAIT),
    "emit": StageDef("wf", _DISPATCH, "Dispatch_emit_total_usec", None),
    # exit edge and sink
    "fifo": StageDef("wait", _EXIT, "Exit_fifo_wait_total_usec",
                     "Exit_fifo_batches"),
    "exit": StageDef("wf", _EXIT, "Exit_process_total_usec", None),
    "d2h": StageDef("wf", _EXIT, "Sink_d2h_wait_total_usec", None,
                    cpu="Sink_d2h_cpu_total_usec", waits=DEVICE_WAIT),
    "sink": StageDef("wf", _EXIT, "Sink_functor_total_usec", None),
}
_INDEX = {name: i for i, name in enumerate(STAGES)}

# enqueue stamps handed to ``Stage.since`` come from the helper's clock
stamp_ns = _now_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns  # the calling thread's CPU clock
# ... read on every CPU_EVERY-th span of a stage, the first one too, and
# scaled up: that clock is a system call (5.8 us a read on the benchmark's
# host, where perf_counter_ns is 0.09; on every span it cost the cell with
# the shortest block 5.6%), so a stage's CPU total is an estimate
CPU_EVERY = 16

_batch_ids = itertools.count(1)


def next_batch_id() -> int:
    """A process-wide batch identifier (``BatchTPU.bid``); 0 means none."""
    return next(_batch_ids)


_NON_WORD = re.compile(r"\W", re.ASCII)


def program_name(kind: str, *ops: str) -> str:
    """XLA module name of a device program, ``jit_<this>`` in a profile:
    ``chain_views_join``, ``map_enrich``. Non-word characters of an
    operator's name become ``_`` (module names are ASCII identifiers)."""
    return "_".join([kind] + [_NON_WORD.sub("_", o) for o in ops])


# -- per thread: its flight recorder ring (monitoring/flightrec.py owns the
# ring itself; the slot lives here so this module imports nothing of it),
# the batch id of the open prep/commit stage, and a worker's account of
# its own waits --------------------------------------------------------------
_tls = threading.local()


_OPEN_SLOT, _OPEN_SINCE = 3, 4


def new_thread_account() -> List[int]:
    """A worker's account of its own waits: nanoseconds by slot
    (``BACKPRESSURED``, ``STARVED``, ``DEVICE_WAIT``), then the slot of
    the wait it stands in now (-1: none) and when that began."""
    return [0, 0, 0, -1, 0]


def set_thread_account(acct: List[int]) -> None:
    """``acct`` takes the waits of the stages this thread closes from now
    on. ``Worker.run`` sets its own; only the thread itself writes it,
    another reads it at poll time (``account_waits``)."""
    _tls.acct = acct


def account_waits(acct: List[int]) -> List[int]:
    """The three waits of ``acct`` now, a wait still open counted up to
    this instant (a worker parked on an empty channel is starved, not
    unaccounted). One slice is one consistent reading."""
    now = acct[:]
    slot = now[_OPEN_SLOT]
    if slot >= 0:
        now[slot] += _now_ns() - now[_OPEN_SINCE]
    return [max(0, ns) for ns in now[:3]]


def set_thread_recorder(rec) -> None:
    _tls.rec = rec


def thread_recorder():
    return getattr(_tls, "rec", None)


_ANNOTATION: Any = None  # jax.profiler.TraceAnnotation once jax is loaded


def _annotation_class():
    """``TraceAnnotation`` if this process has imported jax (only then can
    a profile run); the CPU plane never pays a jax import for a span."""
    global _ANNOTATION
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation
    _ANNOTATION = TraceAnnotation
    return TraceAnnotation


class _Span:
    """One timed pass through a stage (``with stage(b):``). Setting
    ``silent`` inside the block keeps the span out of the ring (a timed
    ``Channel.get`` that ends in an idle tick would flood it)."""

    __slots__ = ("_stage", "_b", "_cause", "_ann", "_t0", "_c0",
                 "_outer_b", "silent")

    def __init__(self, stage: "Stage", b: int, cause: int) -> None:
        self._stage = stage
        self._b = b
        self._cause = cause
        self._ann = None
        self.silent = False

    def __enter__(self) -> "_Span":
        stage = self._stage
        if stage._scope:
            self._outer_b = getattr(_tls, "b", 0)
            _tls.b = self._b
        elif stage._foreign and not self._b:
            self._b = getattr(_tls, "b", 0)
        cls = _ANNOTATION or _annotation_class()
        if cls is not None:
            if self._cause:
                ann = cls(stage.label(), b=self._b, cause=self._cause)
            else:
                ann = cls(stage.label(), b=self._b)
            ann.__enter__()
            self._ann = ann
        self._t0 = _now_ns()
        if stage._cpu:
            # inside the wall reads: a span's CPU <= its wall; a span in
            # CPU_EVERY (-1: not this one)
            n = stage._cpu_n
            stage._cpu_n = n + 1
            self._c0 = -1 if n % CPU_EVERY else _cpu_ns()
        elif stage._waits is not None:
            # a pure wait (a blocked put or get may last for ever): the
            # account says the thread stands in it, for a reader meanwhile
            acct = getattr(_tls, "acct", None)
            if acct is not None:
                acct[_OPEN_SINCE] = self._t0
                acct[_OPEN_SLOT] = stage._waits
        return self

    def __exit__(self, et, ev, tb) -> bool:
        stage = self._stage
        cpu = (_cpu_ns() - self._c0) * CPU_EVERY \
            if stage._cpu and self._c0 >= 0 else 0
        dt = _now_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        if stage._scope:
            _tls.b = self._outer_b
        stage._done(dt, self._b, self._cause, self.silent, cpu)
        return False


class Stage:
    """A stage of ``STAGES`` bound to the counters of one operator; made
    once where a replica or emitter is built, called once per batch."""

    __slots__ = ("owner", "name", "_idx", "_prefix", "_fixed_op", "_op",
                 "_label", "_note", "_foreign", "_scope", "_cpu", "_cpu_n",
                 "_waits")

    def __init__(self, owner: "StageCounters", name: str,
                 op: Optional[str] = None) -> None:
        sdef = STAGES.get(name)
        if sdef is None:
            raise ValueError(
                f"unknown stage {name!r}: monitoring/tracing.py STAGES "
                f"lists {', '.join(STAGES)}")
        self.owner = owner
        self.name = name
        self._idx = _INDEX[name]
        self._prefix = sdef.prefix
        self._fixed_op = op  # a program's label; else the owner's name
        self._op: Any = self  # sentinel: no label built yet
        self._label = ""
        hist = getattr(owner, sdef.note, None) if sdef.note else None
        self._note = hist.record if hist is not None else None
        self._foreign = sdef.foreign
        self._scope = sdef.scope
        self._cpu = sdef.cpu is not None
        self._cpu_n = 0  # spans entered (a racy count where ``foreign``)
        self._waits = sdef.waits

    def label(self) -> str:
        """``<prefix>:<stage>:<op>``, rebuilt when the owner is renamed
        (a fused replica takes its chain's name after it is built)."""
        op = self._fixed_op or self.owner.op_name
        if op is not self._op:
            self._op = op
            self._label = f"{self._prefix}:{self.name}:{op or '?'}"
        return self._label

    @property
    def total_ns(self) -> int:
        return self.owner.stage_ns[self._idx]

    @property
    def count(self) -> int:
        return self.owner.stage_n[self._idx]

    def __call__(self, b: int = 0, cause: int = 0) -> _Span:
        return _Span(self, b, cause)

    def since(self, t0_ns: int, b: int = 0, cause: int = 0) -> None:
        """A residency span: from the ``stamp_ns()`` taken when the batch
        was queued until now. Counters and ring only — a profiler span
        cannot be opened in the past."""
        self._done(_now_ns() - t0_ns, b, cause, False)

    def _done(self, dt_ns: int, b: int, cause: int, silent: bool,
              cpu_ns: int = 0) -> None:
        owner = self.owner
        i = self._idx
        owner.stage_ns[i] += dt_ns
        owner.stage_n[i] += 1
        if cpu_ns:
            owner.stage_cpu_ns[i] += cpu_ns
        if self._waits is not None:
            acct = getattr(_tls, "acct", None)
            if acct is not None:
                acct[_OPEN_SLOT] = -1
                # not floored a span: where the CPU clock moves by the
                # scheduler's tick (10 ms on some hosts) one span's CPU
                # reads 0 or a whole tick, and only the sums are right
                acct[self._waits] += dt_ns - cpu_ns
        if self._note is not None:
            self._note(dt_ns / 1e3)
        if silent:
            return
        rec = thread_recorder() if self._foreign else owner.recorder
        if rec is not None:
            rec.event(self.label(), dt_ns / 1e3,
                      {"b": b, "cause": cause} if cause else {"b": b})


class StageCounters:
    """Cumulative nanoseconds and count per stage for one operator: the
    base of ``StatsRecord``, and alone the private owner of an emitter or
    channel nobody wired to a replica."""

    __slots__ = ("op_name", "recorder", "stage_ns", "stage_n",
                 "stage_cpu_ns", "h2d_puts", "unpacked_columns")

    def __init__(self, op_name: str = "") -> None:
        self.op_name = op_name
        self.recorder = None  # the owning worker's FlightRecorder
        self.stage_ns: List[int] = [0] * len(STAGES)
        self.stage_n: List[int] = [0] * len(STAGES)
        # thread-CPU nanoseconds of the stages whose StageDef names a
        # ``cpu`` field (0 for the others, which never read that clock)
        self.stage_cpu_ns: List[int] = [0] * len(STAGES)
        # the staging edge's transfers (tpu/batch.py): ``device_put``
        # calls issued inside ``wf:h2d`` (one per dtype group of a batch:
        # over Stage_batches, the groups of the schema), and columns of
        # staged batches that a reader OUTSIDE a program sliced out of
        # their packed buffer (the slow path; 0 where every consumer is a
        # program)
        self.h2d_puts = 0
        self.unpacked_columns = 0

    def stage(self, name: str, op: Optional[str] = None) -> Stage:
        return Stage(self, name, op)

    def stage_usec(self, name: str) -> float:
        return self.stage_ns[_INDEX[name]] / 1e3

    def stage_count(self, name: str) -> int:
        return self.stage_n[_INDEX[name]]

    def stage_cpu_usec(self, name: str) -> float:
        return self.stage_cpu_ns[_INDEX[name]] / 1e3

    def stage_fields(self) -> Dict[str, Any]:
        """The ``get_stats()`` fields ``STAGES`` names."""
        d: Dict[str, Any] = {}
        for i, sdef in enumerate(STAGES.values()):
            if sdef.total is not None:
                d[sdef.total] = round(self.stage_ns[i] / 1e3, 1)
            if sdef.count is not None:
                d[sdef.count] = self.stage_n[i]
            if sdef.cpu is not None:
                d[sdef.cpu] = round(self.stage_cpu_ns[i] / 1e3, 1)
        d["Stage_h2d_puts"] = self.h2d_puts
        d["Stage_unpacked_columns"] = self.unpacked_columns
        return d

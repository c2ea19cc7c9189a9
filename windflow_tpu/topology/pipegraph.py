"""PipeGraph: the streaming environment — build, wire, run, wait.

Parity with ``wf/pipegraph.hpp``:
- ``PipeGraph(name, ExecutionMode, TimePolicy)`` (L545-554);
- ``add_source`` (L593) returns the root MultiPipe;
- ``run`` = ``start`` + ``wait_end`` (L610-764);
- dropped-tuple accounting (L782-785), per-operator stats dump (L464-522),
  dot diagram generation (Graphviz, L525-534).

Wiring rules are described in ``topology/stage.py``; emitter/collector
selection mirrors ``wf/multipipe.hpp:200-362``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from ..basic import (DEFAULT_BUFFER_CAPACITY, ExecutionMode, OpType,
                     RoutingMode, TimePolicy, WindFlowError, env_flag)
from ..operators.base import BasicOperator
from ..runtime.channel import Channel, InlinePort, QueuePort
from ..runtime.collectors import (AtomicCounter, DPJoinCollector,
                                  IDSequencerCollector, KSlackCollector,
                                  OrderingCollector, WatermarkCollector)
from ..runtime.emitters import (BasicEmitter, BroadcastEmitter, ForwardEmitter,
                                KeyByEmitter, NullEmitter, SplitMask,
                                SplittingEmitter)
from ..runtime.worker import Worker
from .multipipe import MultiPipe
from .stage import Stage


class PipeGraph:
    def __init__(self, name: str = "pipegraph",
                 execution_mode: ExecutionMode = ExecutionMode.DEFAULT,
                 time_policy: TimePolicy = TimePolicy.INGRESS_TIME,
                 channel_capacity: int = DEFAULT_BUFFER_CAPACITY) -> None:
        self.name = name
        self.execution_mode = execution_mode
        self.time_policy = time_policy
        self.channel_capacity = channel_capacity
        self._stages: List[Stage] = []
        self._source_pipes: List[MultiPipe] = []
        self._ops: List[BasicOperator] = []
        self._workers: List[Worker] = []
        self.dropped = AtomicCounter()
        self._built = False
        self._started = False
        self._ended = False
        self._monitor = None
        # flight recorder (monitoring/flightrec.py): per-worker event
        # rings + the stall watchdog; with_flight_recorder() or the
        # WF_FLIGHTREC_EVENTS / WF_STALL_SEC env knobs enable them
        self._flightrec_events: Optional[int] = None
        self._recorders: List[Any] = []
        self._watchdog = None
        self.last_postmortem: Optional[str] = None  # newest dump path
        # aligned-barrier checkpointing (windflow_tpu.checkpoint):
        # enabled via with_checkpointing() or the WF_CKPT_INTERVAL /
        # WF_CKPT_DIR env knobs; restore_from enables it implicitly
        self._coordinator = None
        self._ckpt_enabled = False
        self._ckpt_interval: Optional[float] = None
        self._ckpt_dir: Optional[str] = None
        self._ckpt_retain = 3
        # elastic rescaling (windflow_tpu.scaling): live repartitioning
        # via rescale(); with_autoscaler()/WF_AUTOSCALE=1 close the loop
        self._rescale_ctrl = None
        self._autoscale_policy = None
        self._autoscale_enabled = False
        self._autoscaler = None
        self._rescaling = False  # stall watchdog stands down mid-rescale
        # mark-final-then-drop series retirement: replicas removed by a
        # scale-down surface ONCE more (Final=true) in get_stats, then
        # vanish — Prometheus sees a clean series end, not a frozen value
        self._final_series: List[Dict[str, Any]] = []
        # exactly-once sinks (windflow_tpu.sinks.transactional): the
        # graph-wide switch flips every sink that supports the 2PC
        # protocol; per-sink builders (`with_exactly_once()`) opt in
        # individually. Env twin: WF_EXACTLY_ONCE=1
        self._exactly_once = env_flag("WF_EXACTLY_ONCE")
        # self-healing supervision (windflow_tpu.supervision): a
        # supervisor thread auto-recovers the graph from worker deaths
        # and stall episodes under a bounded restart budget; enabled via
        # with_supervision() or WF_SUPERVISE=1. _supervising flips while
        # a recovery is in flight (wait_end spins, watchdog stands down)
        self._supervisor = None
        self._supervise_policy = None
        self._supervise_enabled = env_flag("WF_SUPERVISE")
        self._supervising = False
        # device-health probe (supervision/health.py): dead devices are
        # excluded from rebuilt meshes during supervised recovery;
        # with_device_probe() or WF_HEALTH_PROBE=jax
        self._device_probe = None
        # dead-letter queue (windflow_tpu.supervision.errors): created
        # lazily when any operator carries a quarantining error policy
        self._dlq = None
        # JAX persistent compilation cache (with_compile_cache; placement
        # rule in runtime/compile_cache.py): supervised restarts and
        # rescales re-use compiled chain programs instead of recompiling
        self._compile_cache_dir: Optional[str] = None
        # overload protection (windflow_tpu.overload): with_slo(p99_ms)
        # or WF_SLO_P99_MS attach an OverloadGovernor control loop at
        # start() — SLO-breach escalation (tune -> scale -> shed) with
        # hysteresis/cooldown recovery
        self._slo_p99_ms: Optional[float] = None
        self._overload_policy = None
        self._overload_governor = None
        env_slo = os.environ.get("WF_SLO_P99_MS")
        if env_slo:
            try:
                self._slo_p99_ms = float(env_slo)
            except ValueError:
                pass  # malformed knob must not take down the graph
        # compile-stability pre-warm (with_prewarm / WF_PREWARM=1):
        # compile every bucketed device-chain signature at start(),
        # before the sources open, so no retrace lands mid-stream
        self._prewarm_enabled = env_flag("WF_PREWARM")
        self._prewarm_report: Optional[Dict[str, Any]] = None
        env_iv = os.environ.get("WF_CKPT_INTERVAL")
        if env_iv:
            try:
                self.with_checkpointing(interval=float(env_iv))
            except ValueError:
                pass  # malformed knob must not take down the graph
        if os.environ.get("WF_CKPT_DIR"):
            self._ckpt_dir = os.environ["WF_CKPT_DIR"]

    # ------------------------------------------------------------------
    # exactly-once sinks (windflow_tpu.sinks.transactional)
    # ------------------------------------------------------------------
    def with_exactly_once(self) -> "PipeGraph":
        """Graph-wide exactly-once delivery: every sink runs the
        epoch-fenced two-phase commit (buffer/stage per checkpoint
        epoch, pre-commit at the aligned barrier, commit atomically on
        coordinator finalize). Requires ``with_checkpointing``; a sink
        family that cannot honor the protocol makes ``start()`` refuse
        loudly rather than silently downgrade the guarantee. Env twin:
        ``WF_EXACTLY_ONCE=1``."""
        if self._started:
            raise WindFlowError("with_exactly_once after start()")
        self._exactly_once = True
        return self

    # ------------------------------------------------------------------
    # overload protection (windflow_tpu.overload)
    # ------------------------------------------------------------------
    def with_slo(self, p99_ms: float, policy: Optional[Any] = None
                 ) -> "PipeGraph":
        """Declare the graph's end-to-end p99 latency budget
        (milliseconds) and attach the :class:`OverloadGovernor` at
        ``start()``: when the sink-side windowed p99 breaches the SLO the
        governor walks an escalation ladder — shrink dispatch
        depth/output batching, scale the bottleneck operator (bounded by
        MAX_PAR), then admission-control the sources (token-bucket rate
        limiting + the configured shed policy) — and recovers with
        hysteresis and cooldown. ``policy`` is a
        :class:`GovernorPolicy` (None = defaults, tunable via the
        ``WF_SLO_*`` / ``WF_SHED_*`` env knobs). Per-source budgets via
        ``Source_Builder.with_slo``; the tightest declared budget
        governs. Sink-side latency sampling is enabled automatically
        (1/16) when not already configured — the governor is blind
        without e2e samples. Env twin: ``WF_SLO_P99_MS``."""
        if self._started:
            raise WindFlowError("with_slo after start()")
        if p99_ms <= 0:
            raise WindFlowError("with_slo: p99_ms must be > 0")
        self._slo_p99_ms = float(p99_ms)
        self._overload_policy = policy
        return self

    def _effective_slo_ms(self) -> Optional[float]:
        """Tightest declared budget: graph-level with_slo/WF_SLO_P99_MS
        and every source builder's with_slo."""
        budgets = [self._slo_p99_ms] if self._slo_p99_ms else []
        budgets += [op.slo_p99_ms for op in self._ops
                    if getattr(op, "slo_p99_ms", None)]
        return min(budgets) if budgets else None

    def _setup_overload_governor(self) -> None:
        """Create the governor (started with the other control threads).
        Validation is LOUD and up-front: a key_priority shed policy
        without priorities would only fail mid-surge otherwise."""
        slo_ms = self._effective_slo_ms()
        if slo_ms is None and self._overload_policy is None:
            return
        from ..overload import GovernorPolicy, OverloadGovernor
        policy = self._overload_policy
        if policy is None:
            policy = GovernorPolicy(slo_p99_ms=slo_ms)
        elif slo_ms is not None and slo_ms * 1e3 < policy.slo_us:
            policy.slo_us = slo_ms * 1e3  # a source declared tighter
        if policy.shed_policy == "key_priority":
            for op in self._ops:
                if op.op_type == OpType.SOURCE \
                        and getattr(op, "priority_fn", None) is None:
                    raise WindFlowError(
                        f"with_slo: shed policy 'key_priority' needs "
                        f"with_priority(fn) on source {op.name!r} — "
                        "records have no priority to shed by otherwise")
        self._overload_governor = OverloadGovernor(self, policy)

    def _ensure_slo_sampling(self) -> None:
        """BEFORE ``_build`` (replica histograms allocate at replica
        construction): the governor needs sink-side e2e samples, so an
        SLO declaration turns on 1/16 sampling for sinks (and 1/16
        source stamping) when nothing configured it."""
        if self._effective_slo_ms() is None:
            return
        from ..monitoring.tracing import env_sample_every
        if env_sample_every() > 0:
            return  # WF_LATENCY_SAMPLE already stamps the stream
        for op in self._ops:
            if op.op_type in (OpType.SOURCE, OpType.SINK) \
                    and op.latency_sample is None:
                op.latency_sample = 16

    # ------------------------------------------------------------------
    # compile-stability pre-warm (ROADMAP: kill retrace storms)
    # ------------------------------------------------------------------
    def with_prewarm(self) -> "PipeGraph":
        """Pre-warm the device plane at ``start()``: every stateless
        chain program compiles for every power-of-two bucket capacity up
        to the graph's largest staging batch, BEFORE the sources open —
        so a ragged stream (whose tail batches and keyed repartitions
        land in smaller buckets) never pays a retrace mid-stream.
        Stateful programs (grid scans, FFAT forests) key their
        signatures on runtime cardinality and are skipped (the report
        names them). Compiles land in ``Compile_*`` stats during
        warm-up; ``Compile_count`` then stays flat. Results in
        ``prewarm_report`` / ``get_stats()["Prewarm"]``. Env twin:
        ``WF_PREWARM=1``; pairs with ``with_compile_cache`` so restarts
        re-warm from disk in milliseconds."""
        if self._started:
            raise WindFlowError("with_prewarm after start()")
        self._prewarm_enabled = True
        return self

    def _bucket_caps(self) -> List[int]:
        """The finite bucket set a run can see: powers of two from the
        minimum staging bucket up to the largest declared output batch
        (ragged tails keep the full bucket; device-side keyed
        repartition and compaction produce the smaller ones)."""
        from ..tpu.batch import bucket_capacity
        max_obs = max((op.output_batch_size for op in self._ops),
                      default=0)
        top = bucket_capacity(max(1, max_obs))
        caps, c = [], bucket_capacity(1)
        while c <= top:
            caps.append(c)
            c <<= 1
        return caps

    def _prewarm_device_programs(self) -> None:
        if not any(getattr(op, "is_tpu", False) for op in self._ops):
            # CPU-plane graph: nothing compiles, and we must not drag
            # the device plane (jax) in just to find that out
            self._prewarm_report = {"bucket_caps": [],
                                    "signatures_compiled": 0,
                                    "skipped": ["no device stages"],
                                    "elapsed_s": 0.0}
            return
        t0 = time.monotonic()
        caps = self._bucket_caps()
        warmed = 0
        skipped: List[str] = []
        for s in self._stages:
            first = s.first_op
            if not getattr(first, "is_tpu", False):
                continue
            label = s.describe()
            for r in {id(r): r for r in first.replicas}.values():
                pw = getattr(r, "prewarm", None)
                if pw is None:
                    skipped.append(f"{label}: no prewarm hook "
                                   f"({type(r).__name__})")
                    continue
                n = pw(caps)
                if n is None:
                    skipped.append(f"{label}: runtime-dependent "
                                   "signature (stateful/inferred schema)")
                else:
                    warmed += n
        self._prewarm_report = {
            "bucket_caps": caps,
            "signatures_compiled": warmed,
            "skipped": skipped,
            "elapsed_s": round(time.monotonic() - t0, 4),
        }

    @property
    def prewarm_report(self) -> Optional[Dict[str, Any]]:
        return self._prewarm_report

    # ------------------------------------------------------------------
    # self-healing supervision (windflow_tpu.supervision)
    # ------------------------------------------------------------------
    def with_supervision(self, policy: Optional[Any] = None) -> "PipeGraph":
        """Auto-recover the whole graph from worker deaths and
        stall-watchdog episodes: a supervisor tears the runtime plane
        down, restores from the latest committed checkpoint, resumes the
        sources from their recorded positions and restarts — under a
        jittered exponential-backoff ``RestartPolicy`` with a bounded
        restart budget (budget exhausted => the aggregated error raises
        in ``wait_end``). Exactly-once sinks stay duplicate-free across
        restarts. Enables checkpointing implicitly when not configured
        (set an interval for bounded replay). Env twins: ``WF_SUPERVISE=1``
        plus the ``WF_SUPERVISE_*`` policy knobs."""
        if self._started:
            raise WindFlowError("with_supervision after start()")
        self._supervise_enabled = True
        self._supervise_policy = policy
        if not self._ckpt_enabled:
            self.with_checkpointing()
        return self

    def with_device_probe(self, probe: Any) -> "PipeGraph":
        """Install a device-health probe (``supervision.health``): during
        every supervised recovery the probe's dead devices are excluded
        from the rebuilt device meshes, so mesh operators come back on
        the surviving chips with their sharded state relayouted
        byte-identically; the graph then runs degraded
        (``Recovery_degraded_devices`` > 0, the overload governor sheds
        instead of scaling) until the probe sees the device return and
        one planned restart re-expands to full shape. Env twin:
        ``WF_HEALTH_PROBE=jax`` (paced by ``WF_HEALTH_PROBE_INTERVAL``).
        Implies supervision's value only under supervision — without a
        supervisor the probe is never consulted."""
        if self._started:
            raise WindFlowError("with_device_probe after start()")
        self._device_probe = probe
        return self

    def failure_domains(self) -> Dict[int, List[str]]:
        """Device id -> mesh operators whose sharded state lives on it
        (built replicas only). The unit of loss for device failover."""
        from ..supervision.health import failure_domain_map
        return failure_domain_map(self)

    def with_compile_cache(self, cache_dir: str) -> "PipeGraph":
        """Point JAX's persistent compilation cache at ``cache_dir``
        instead of the default ``<checkout>/.jax_cache``. Ignored (with
        one log line) when ``JAX_COMPILATION_CACHE_DIR`` is set: the
        environment places the cache then."""
        if self._started:
            raise WindFlowError("with_compile_cache after start()")
        self._compile_cache_dir = cache_dir
        return self

    def _capture_initial_positions(self) -> None:
        """Supervision prerequisite (before the first tuple ships): each
        replayable source replica's STARTING cursor. A failure before
        any checkpoint has committed leaves nothing to restore — the
        supervisor then resets sources to these positions (a full
        replay; exactly-once sinks make it duplicate-free) instead of
        silently resuming from the in-memory cursor and losing the
        prefix that sat in the discarded channels."""
        from ..operators.base import arity
        from ..operators.source import Source as _PlainSource
        self._initial_positions: Dict[Any, Any] = {}
        for s in self._stages:
            if not s.is_source or not isinstance(s.first_op, _PlainSource):
                continue
            op = s.first_op
            snap = getattr(op.func, "snapshot_position", None)
            if snap is None:
                continue
            for r in op.replicas:
                pos = r._restore_position  # a restore_from= start
                if pos is None:
                    pos = (snap(r.context) if arity(snap) >= 1 else snap())
                self._initial_positions[(op.name, r.idx)] = pos

    def dead_letter_queue(self):
        """The graph's quarantine side-channel (created on first use; see
        ``windflow_tpu.supervision.errors.DeadLetterQueue``)."""
        if self._dlq is None:
            from ..supervision.errors import DeadLetterQueue
            self._dlq = DeadLetterQueue(self.name)
        return self._dlq

    def dead_letters(self) -> List[Dict[str, Any]]:
        """Records quarantined by DEAD_LETTER error policies (payload,
        exception metadata, traceback), newest last."""
        return [] if self._dlq is None else self._dlq.records()

    def _negotiate_error_policies(self) -> None:
        """First ``_build``: refuse meaningless policies loudly and
        inject the graph's dead-letter queue into every policy that can
        quarantine but was not given an explicit DLQ."""
        for op in self._ops:
            pol = getattr(op, "error_policy", None)
            if pol is None or pol.is_fail:
                continue
            if op.op_type == OpType.SOURCE:
                raise WindFlowError(
                    f"with_error_policy: source {op.name!r} drives its own "
                    "generation loop — there is no per-record invocation "
                    "to contain; use with_supervision() for source "
                    "failures")
            if pol.may_dead_letter:
                # per-OP attribute, never the policy object: the
                # ErrorPolicy.DEAD_LETTER singleton is shared across
                # graphs, and storing one graph's DLQ on it would route
                # every later graph's quarantine into the wrong queue
                # explicit is-None: an (empty) user-provided DLQ is falsy
                op._dlq = pol.dlq if pol.dlq is not None \
                    else self.dead_letter_queue()

    def _negotiate_mesh_checkpoint(self) -> None:
        """Guarantee negotiation for the mesh plane (first ``_build``):
        a mesh operator without a sharded snapshot/restore path under
        checkpointing would produce checkpoints that silently omit its
        device-mesh state — and could never restore it. Refuse loudly
        instead. Every in-tree mesh operator is snapshot-capable; this
        is the standing fallback for any future mesh op that is not."""
        if not self._ckpt_enabled:
            return
        for op in self._ops:
            if getattr(op, "is_mesh", False) \
                    and not getattr(op, "mesh_snapshot_capable", False):
                raise WindFlowError(
                    f"with_checkpointing: mesh operator {op.name!r} "
                    f"({type(op).__name__}) has no sharded "
                    "snapshot/restore path — a checkpoint would silently "
                    "omit its device-mesh state and a restore could not "
                    "rebuild it; run this graph without checkpointing/"
                    "supervision or use a snapshot-capable mesh operator")

    def _negotiate_exactly_once(self) -> None:
        """Guarantee negotiation (first ``_build``): flip graph-wide
        exactly-once onto every sink, then verify every exactly-once
        sink can actually deliver it — loudly, because a guarantee that
        silently downgrades is worse than a refusal."""
        sinks = [op for op in self._ops if op.op_type == OpType.SINK]
        if self._exactly_once:
            for op in sinks:
                if not getattr(op, "supports_exactly_once", False):
                    raise WindFlowError(
                        f"with_exactly_once: sink {op.name!r} "
                        f"({type(op).__name__}) does not implement the "
                        "transactional sink protocol (precommit_epoch / "
                        "commit-on-finalize); it would deliver "
                        "at-least-once and break the graph guarantee")
                op.exactly_once = True
        eo_sinks = [op for op in sinks
                    if getattr(op, "exactly_once", False)]
        for op in eo_sinks:
            if not getattr(op, "supports_exactly_once", False):
                raise WindFlowError(
                    f"sink {op.name!r} ({type(op).__name__}) has "
                    "exactly_once set but does not implement the "
                    "transactional sink protocol")
        if eo_sinks and not self._ckpt_enabled:
            raise WindFlowError(
                "exactly-once sinks need the checkpoint plane that "
                f"drives their commits: sink(s) "
                f"{[op.name for op in eo_sinks]} request exactly-once "
                "but checkpointing is off — call with_checkpointing(...) "
                "(or set WF_CKPT_INTERVAL) before start()")

    # ------------------------------------------------------------------
    # checkpointing configuration
    # ------------------------------------------------------------------
    def with_checkpointing(self, interval: Optional[float] = None,
                           store_dir: Optional[str] = None,
                           retain: int = 3) -> "PipeGraph":
        """Enable aligned-barrier checkpointing (windflow_tpu.checkpoint).

        ``interval`` (seconds) drives periodic checkpoints; None disables
        the timer — checkpoints then happen only on explicit triggers
        (``SourceShipper.request_checkpoint()`` or
        ``graph.trigger_checkpoint()``). ``store_dir`` is the on-disk
        store root (default: ``WF_CKPT_DIR``, else
        ``wf_checkpoints/<graph name>``); the last ``retain`` committed
        checkpoints are kept. Env twins: ``WF_CKPT_INTERVAL`` /
        ``WF_CKPT_DIR``."""
        if self._started:
            raise WindFlowError("with_checkpointing after start()")
        self._ckpt_enabled = True
        if interval is not None:
            self._ckpt_interval = float(interval)
        if store_dir is not None:
            self._ckpt_dir = store_dir
        self._ckpt_retain = retain
        return self

    # ------------------------------------------------------------------
    # elastic rescaling (windflow_tpu.scaling)
    # ------------------------------------------------------------------
    def with_autoscaler(self, policy: Optional[Any] = None) -> "PipeGraph":
        """Attach the autoscaler control loop: a policy thread watches
        the per-operator backpressure/starvation gauges and e2e latency
        and rescales the bottleneck operator up (idle operators down)
        under hysteresis and cooldown. ``policy`` is an
        ``AutoscalePolicy`` (None = defaults, tunable via the
        ``WF_AUTOSCALE_*`` env knobs). Requires checkpointing — enabled
        implicitly here when not already configured. Env twin:
        ``WF_AUTOSCALE=1``."""
        if self._started:
            raise WindFlowError("with_autoscaler after start()")
        self._autoscale_enabled = True
        self._autoscale_policy = policy
        if not self._ckpt_enabled:
            self.with_checkpointing()
        return self

    def _rescale_controller(self):
        if self._rescale_ctrl is None:
            from ..scaling.controller import RescaleController
            self._rescale_ctrl = RescaleController(self)
        return self._rescale_ctrl

    def rescale(self, op_name: str, parallelism: int,
                timeout_s: Optional[float] = None) -> Any:
        """LIVE rescale of one operator (its whole chained stage) to a
        new parallelism: trigger an aligned checkpoint, quiesce at the
        barrier, rebuild the stage's replica list and every affected
        routing table, restore the repartitioned keyed blobs, resume —
        without replaying from source-zero. Returns a ``RescaleReport``
        with the measured ``checkpoint_s`` / ``pause_s`` / ``total_s``.
        Raises ``WindFlowError`` for non-repartitionable operators
        (global reduce, BROADCAST windows, DP join, persistent sqlite
        state, sources) and on quiesce timeout (``WF_CKPT_TIMEOUT``)."""
        self._rescaling = True
        try:
            return self._rescale_controller().rescale(op_name, parallelism,
                                                      timeout_s)
        finally:
            self._rescaling = False

    def _note_retired_replicas(self, stage, new_n: int) -> None:
        """Capture the final stats of replicas a scale-down removes
        (mark-final-then-drop: exported once more, then gone)."""
        for op in stage.ops:
            if getattr(op, "_fused_hidden", False):
                continue
            label = getattr(op, "_fused_stage_label", None) or op.name
            finals = []
            for r in op.replicas[new_n:]:
                d = r.stats.to_dict()
                d["Final"] = True
                finals.append(d)
            if finals:
                self._final_series.append({
                    "name": label, "kind": type(op).__name__,
                    "parallelism": 0, "retired": True,
                    "replicas": finals})

    def _rebuild_runtime(self) -> None:
        """Discard the runtime plane (replicas, channels, collectors,
        workers) and rebuild it from the — possibly re-parallelized —
        stage IR. Callers (the rescale controller) own quiescing: every
        old worker must already be parked or joined. Flight-recorder
        rings of old workers stay registered so the Perfetto timeline
        shows the rescale seam in one trace."""
        for s in self._stages:
            s.channels = []
            s.workers = []
            for op in s.ops:
                op.replicas = []
        self._workers = []
        self._built = False
        self._build()

    def _stage_flightrec_events_max(self) -> int:
        """Largest flight-ring capacity any stage runs with (the rescale
        controller sizes its own ring to match; 0 = recording off)."""
        return max((self._stage_flightrec_events(s) for s in self._stages),
                   default=0)

    def _worker_diagnostics(self, names: List[str]) -> str:
        """Per-worker evidence for checkpoint-timeout errors: crash
        tracebacks (``Worker_last_error``) and stall-watchdog flags for
        the named workers, when available."""
        parts = []
        stalled = set(getattr(self._watchdog, "fired", []) or [])
        for w in self._workers:
            if w.name not in names:
                continue
            if w.error is not None:
                parts.append(f"{w.name} died: {type(w.error).__name__}: "
                             f"{w.error}")
                continue
            stats = w._stats()
            last = getattr(stats, "worker_last_error", None) if stats \
                else None
            if last:
                parts.append(f"{w.name} last error: "
                             f"{last.strip().splitlines()[-1]}")
            if w.name in stalled:
                parts.append(f"{w.name} flagged by the stall watchdog")
        return "; ".join(parts)

    # ------------------------------------------------------------------
    # flight recorder (monitoring/flightrec.py)
    # ------------------------------------------------------------------
    def with_flight_recorder(self, events: int = 0) -> "PipeGraph":
        """Enable the per-worker flight recorder: every worker gets a
        fixed-size single-writer ring of ``events`` span events
        (default ``WF_FLIGHTREC_EVENTS`` or 4096). Export via
        ``dump_trace(path)``, the ``MonitoringServer`` ``GET /trace``
        window, or the automatic post-mortem on a worker crash /
        stall-watchdog fire."""
        if self._started:
            raise WindFlowError("with_flight_recorder after start()")
        from ..monitoring.flightrec import (DEFAULT_EVENTS,
                                            env_flightrec_events)
        self._flightrec_events = (int(events) if events and events > 0
                                  else env_flightrec_events()
                                  or DEFAULT_EVENTS)
        return self

    def _stage_flightrec_events(self, stage: Stage) -> int:
        """Ring capacity for one stage's workers: the largest per-op
        builder override (``with_flight_recorder(events=N)``), else the
        graph-level setting, else ``WF_FLIGHTREC_EVENTS`` (0 = off)."""
        from ..monitoring.flightrec import env_flightrec_events
        per_op = max((op.flightrec_events or 0 for op in stage.ops),
                     default=0)
        if per_op > 0:
            return per_op
        if self._flightrec_events:
            return self._flightrec_events
        return env_flightrec_events()

    def trace_document(self, stacks: bool = False,
                       extra: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
        """The graph's flight rings as a Chrome trace-event document
        (empty ``traceEvents`` when no recorder is enabled)."""
        from ..monitoring.flightrec import thread_stacks, to_chrome_trace
        return to_chrome_trace(
            self._recorders,
            stacks=thread_stacks() if stacks else None, extra=extra)

    def dump_trace(self, path: str, stacks: bool = False) -> str:
        """Write the flight-recorder timeline as Chrome/Perfetto trace
        JSON (loads in ``chrome://tracing`` / https://ui.perfetto.dev).
        ``stacks=True`` adds ``sys._current_frames()`` for every runtime
        thread (the post-mortem dumps always do)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.trace_document(stacks=stacks), f)
        return path

    def _postmortem_path(self, kind: str, wname: str) -> str:
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in f"{self.name}_{kind}_{wname}")
        log_dir = os.environ.get("WF_LOG_DIR", "log")
        return os.path.join(log_dir, f"{safe}.json")

    def _crash_dump(self, worker, exc: BaseException) -> None:
        """Automatic post-mortem on a worker death: the whole graph's
        rings + thread stacks + the traceback, so the runs where a
        timeline matters most leave evidence behind."""
        import traceback
        try:
            path = self._postmortem_path("crash", worker.name)
            doc = self.trace_document(stacks=True, extra={
                "crashedWorker": worker.name,
                "exception": "".join(traceback.format_exception(
                    type(exc), exc, exc.__traceback__))})
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "w") as f:
                json.dump(doc, f)
            self.last_postmortem = path
        except Exception:
            pass  # the dump must never mask the original error

    def _stall_dump(self, wname: str) -> None:
        """Stall-watchdog fire: same dump shape as a crash, flagged with
        the stalled worker (its stack shows WHERE it is wedged)."""
        try:
            path = self._postmortem_path("stall", wname)
            doc = self.trace_document(stacks=True,
                                      extra={"stalledWorker": wname})
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "w") as f:
                json.dump(doc, f)
            self.last_postmortem = path
        except Exception:
            pass

    def trigger_checkpoint(self, wait: bool = False,
                           timeout_s: Optional[float] = None
                           ) -> Optional[int]:
        """Force a checkpoint epoch now (sources inject barriers at their
        next tuple boundary). Returns the checkpoint id, or None when
        checkpointing is not enabled/running. With ``wait=True``, blocks
        until the epoch commits and raises a descriptive
        ``WindFlowError`` naming the unacked workers if it times out
        (``timeout_s``, default ``WF_CKPT_TIMEOUT``)."""
        if self._coordinator is None:
            return None
        cid = self._coordinator.trigger(force=True)
        if wait and cid is not None:
            self._coordinator.wait_committed(cid, timeout_s)
        return cid

    def _ckpt_store_dir(self) -> str:
        if self._ckpt_dir:
            return self._ckpt_dir
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in self.name) or "pipegraph"
        return os.path.join("wf_checkpoints", safe)

    def _setup_checkpointing(self, restore_from: Optional[str]):
        """Create store+coordinator (before _build so _make_workers can
        wire them) and resolve the restore target. Returns
        ``(ckpt_dir, manifest)`` or ``(None, None)``."""
        from ..checkpoint import CheckpointCoordinator, CheckpointStore

        resolved = None
        if restore_from is not None:
            resolved = CheckpointStore.resolve(restore_from)
            if not self._ckpt_enabled:
                # restoring implies checkpointing: keep writing new
                # checkpoints into the same store unless told otherwise
                self._ckpt_enabled = True
                if self._ckpt_dir is None:
                    self._ckpt_dir = os.path.dirname(resolved[1])
        if not self._ckpt_enabled:
            return None, None
        store = CheckpointStore(self._ckpt_store_dir(),
                                retain=self._ckpt_retain)
        self._coordinator = CheckpointCoordinator(
            store, self.name, interval_s=self._ckpt_interval)
        if resolved is not None:
            cid, ckpt_dir, manifest = resolved
            # new epochs continue after the restored one; sources bind
            # their injection cursor to this BEFORE any trigger fires
            self._coordinator.requested_id = cid
            self._coordinator.last_completed_id = cid
            return ckpt_dir, manifest
        return None, None

    def _restore_replicas(self, ckpt_dir: str, manifest: Dict[str, Any]
                          ) -> None:
        self._restore_states(
            self._coordinator.store.load_states(ckpt_dir, manifest))

    def _restore_states(self, states: Dict[Any, Any]) -> None:
        """Push every blob's state into the matching rebuilt replica.
        Topology mismatches fail loudly: silently dropping state would
        trade a crash for wrong answers."""
        by_name = {op.name: op for op in self._ops}
        for (op_name, idx), state in states.items():
            op = by_name.get(op_name)
            if op is None:
                raise WindFlowError(
                    f"restore: checkpoint has state for operator "
                    f"{op_name!r} which this graph does not contain")
            if getattr(op, "_fused_hidden", False):
                raise WindFlowError(
                    f"restore: checkpoint holds standalone state for "
                    f"{op_name!r}, but this graph fuses it into the "
                    "device chain "
                    f"{op.replicas[0].fused_name!r} — the checkpointed "
                    "topology was fused differently (match WF_TPU_FUSION "
                    "/ the chain() calls of the original graph)")
            if idx >= len(op.replicas):
                raise WindFlowError(
                    f"restore: operator {op_name!r} was checkpointed with "
                    f"parallelism > {len(op.replicas)}; a cross-restart "
                    "parallelism change needs a LIVE rescale "
                    "(graph.rescale) — restore_from requires the "
                    "checkpointed topology")
            replica = op.replicas[idx]
            if state.get("__fused__") is not None \
                    and getattr(replica, "fused_signature", None) is None:
                raise WindFlowError(
                    f"restore: checkpoint blob for {op_name!r} holds a "
                    f"fused device chain {'∘'.join(state['__fused__'])!r}, "
                    "but this graph runs the operator standalone — the "
                    "checkpointed topology was fused differently (match "
                    "WF_TPU_FUSION / the chain() calls of the original "
                    "graph)")
            if "txn_last_epoch" in state \
                    and not hasattr(replica, "precommit_epoch"):
                raise WindFlowError(
                    f"restore: checkpoint blob for {op_name!r} was taken "
                    "by an exactly-once sink, but this graph runs the "
                    "sink at-least-once — staged epochs would neither "
                    "commit nor abort; enable with_exactly_once() to "
                    "match the checkpointed guarantee")
            state = dict(state)
            em_state = state.pop("__emitter__", None)
            coll_state = state.pop("__collector__", None)
            replica.restore_state(state)
            if em_state is not None and replica.emitter is not None:
                replica.emitter.restore_emitter_state(em_state)
            coll = getattr(replica, "_collector", None)
            if coll_state is not None:
                if coll is not None:
                    coll.restore_state(coll_state)
                elif any(coll_state.get(k) for k in
                         ("bufs", "heap", "pending")):
                    # buffered pre-barrier MESSAGES with nowhere to go
                    # would silently vanish — refuse instead
                    raise WindFlowError(
                        f"restore: {op_name!r} replica {idx} has buffered "
                        "collector state but the rebuilt stage has no "
                        "collector (input fan-in changed); cannot restore "
                        "without losing data")

    # ------------------------------------------------------------------
    def _register_op(self, op: BasicOperator) -> None:
        self._ops.append(op)

    def add_source(self, source_op: BasicOperator) -> MultiPipe:
        if self._started:
            raise WindFlowError("cannot add sources after start()")
        if source_op.op_type != OpType.SOURCE:
            raise WindFlowError("add_source requires a Source-kind operator")
        mp = MultiPipe(self)
        mp._claim(source_op)
        stage = Stage(source_op)
        self._stages.append(stage)
        mp.tail_groups = [[stage]]
        self._source_pipes.append(mp)
        return mp

    # ------------------------------------------------------------------
    # build & wiring
    # ------------------------------------------------------------------
    def _build(self) -> None:
        if self._built:
            return
        self._built = True
        # guarantee negotiation BEFORE replica construction (replica
        # classes are chosen by op.exactly_once) — here rather than in
        # start() because get_num_threads() builds too, and a build that
        # silently ignored the requested guarantee would be worse than
        # the refusal
        self._negotiate_exactly_once()
        self._negotiate_error_policies()
        self._negotiate_mesh_checkpoint()
        for s in self._stages:
            for op in s.ops:
                op.configure(self.execution_mode, self.time_policy)
            if s.is_fused_tpu:
                # chained device stage: ONE fused replica per slot runs
                # the whole chain as a single XLA program (fused_ops.py;
                # the factory picks the window-terminated variant when
                # the chain ends in Ffat_Windows_TPU). Every sub-op
                # aliases the fused replica list so edge wiring
                # (first_op/last_op.replicas) stays uniform.
                from ..tpu.fused_ops import make_fused_replica
                fused = [make_fused_replica(s.ops, i)
                         for i in range(s.parallelism)]
                label = s.describe()
                for op in s.ops:
                    op.replicas = fused
                    op._fused_hidden = op is not s.first_op
                s.first_op._fused_stage_label = label
            else:
                for op in s.ops:
                    op.build_replicas()
        # channels (one per consumer replica); the native C++ ring stays
        # OPT-IN (WF_NATIVE_CHANNELS=1): measured 2026-07-29, the Python
        # deque+Condition channel moves ~1.0M msg/s vs ~0.3M for the
        # ctypes ring — per-call ctypes overhead dominates at message
        # granularity, and inter-stage traffic is already batch-granular
        channel_cls = Channel
        if env_flag("WF_NATIVE_CHANNELS"):
            from ..native import NativeChannel, native_available
            if native_available():
                channel_cls = NativeChannel
        for s in self._stages:
            if not s.is_source:
                s.channels = [channel_cls(self.channel_capacity)
                              for _ in range(s.parallelism)]
        # intra-stage chain wiring (fused InlinePort edges); fused device
        # stages have no intra-stage edges at all — the chain is one
        # program inside one replica
        for s in self._stages:
            if s.is_fused_tpu:
                continue
            for a, b in zip(s.ops[:-1], s.ops[1:]):
                for i in range(s.parallelism):
                    em = ForwardEmitter(1, 0, self.execution_mode)
                    em.punct_generation = False
                    em.set_ports([InlinePort(b.replicas[i])])
                    a.replicas[i].set_emitter(em)
        # inter-stage wiring, consumer-driven so that input channel indices
        # follow upstream order (join stream A channels first)
        for c in self._stages:
            for edge in c.upstreams:
                self._wire_edge(edge.stage, edge.branch, c)
        # terminal emitters
        for s in self._stages:
            last = s.last_op
            for r in last.replicas:
                if r.emitter is None:
                    r.set_emitter(NullEmitter())
        # split stages: assemble per-replica splitting emitters
        for s in self._stages:
            if s.is_split:
                for i, r in enumerate(s.last_op.replicas):
                    inner = r._split_inner  # branch -> emitter
                    ems = [inner.get(b) for b in range(len(s.split_branches))]
                    missing = [b for b, e in enumerate(ems) if e is None]
                    if missing:
                        raise WindFlowError(
                            f"split stage {s.describe()}: branches {missing} "
                            f"have no operators")
                    logic = s.split_logic
                    if getattr(s.last_op, "is_tpu", False):
                        from ..tpu.emitters_tpu import TPUSplittingEmitter
                        se: BasicEmitter = TPUSplittingEmitter(
                            logic, ems, self.execution_mode)
                    else:
                        if isinstance(logic, SplitMask):
                            logic = logic.branches
                        elif isinstance(logic, str):
                            field = logic
                            logic = (lambda t, _f=field:
                                     t[_f] if isinstance(t, dict)
                                     else getattr(t, _f))
                        se = SplittingEmitter(logic, ems,
                                              self.execution_mode)
                    r.set_emitter(se)
        # collectors + workers
        for s in self._stages:
            self._make_workers(s)

    def _wire_edge(self, producer: Stage, branch: Optional[int],
                   consumer: Stage) -> None:
        """Create one emitter per producer replica targeting all consumer
        replicas (or one-to-one for same-parallelism FORWARD, reference
        Case 2)."""
        first = consumer.first_op
        routing = first.input_routing
        obs = producer.last_op.output_batch_size
        n_dests = consumer.parallelism
        p_tpu = getattr(producer.last_op, "is_tpu", False)
        c_tpu = getattr(first, "is_tpu", False)
        if c_tpu and not p_tpu and obs <= 0:
            # reference: a GPU operator's predecessor must declare an output
            # batch size (wf/multipipe.hpp:457-460)
            raise WindFlowError(
                f"operator {producer.last_op.name!r} feeds TPU operator "
                f"{first.name!r} but declares no output batch size; call "
                "with_output_batch_size(n) on the producer")
        if c_tpu and not p_tpu:
            first.staged_input = True  # its batches arrive packed
            sides = getattr(first, "staged_sides", None)
            if sides is not None:   # a two-input operator: which input
                sides[0 if producer in consumer.join_a_stages else 1] = True
        one_to_one = (routing is RoutingMode.FORWARD
                      and branch is None
                      and not (c_tpu and not p_tpu)
                      and producer.parallelism == n_dests)
        if routing is RoutingMode.BROADCAST:
            for op in consumer.ops:
                for r in op.replicas:
                    r.copy_on_write = True
        for pi, pr in enumerate(producer.last_op.replicas):
            em = self._create_edge_emitter(first, routing, obs, n_dests,
                                           p_tpu, c_tpu, one_to_one)
            if one_to_one:
                ports = [QueuePort(consumer.channels[pi])]
            else:
                ports = [QueuePort(ch) for ch in consumer.channels]
            em.set_ports(ports)
            if branch is None:
                pr.set_emitter(em)
            else:
                if not hasattr(pr, "_split_inner"):
                    pr._split_inner = {}
                pr._split_inner[branch] = em
                em.stats = pr.stats

    def _create_edge_emitter(self, first: BasicOperator, routing: RoutingMode,
                             obs: int, n_dests: int, p_tpu: bool,
                             c_tpu: bool, one_to_one: bool) -> BasicEmitter:
        """Emitter kind per (device-plane, routing) — the reference's
        create_emitter (``wf/multipipe.hpp:248-362``) plus the GPU-emitter
        template cases (<inputGPU, outputGPU>)."""
        if c_tpu and not p_tpu:  # CPU -> TPU staging boundary
            from ..tpu.emitters_tpu import TPUStageEmitter
            routing_name = ("keyby" if routing is RoutingMode.KEYBY else
                            "broadcast" if routing is RoutingMode.BROADCAST
                            else "forward")
            return TPUStageEmitter(n_dests, obs,
                                   getattr(first, "schema", None),
                                   first.key_extractor,
                                   routing_name, self.execution_mode,
                                   key_field=first.key_field,
                                   key_fields=getattr(first, "key_fields",
                                                      None))
        if p_tpu and c_tpu:  # device -> device
            from ..tpu.emitters_tpu import (TPUBroadcastEmitter,
                                            TPUForwardEmitter,
                                            TPUKeyByEmitter)
            if routing is RoutingMode.KEYBY:
                return TPUKeyByEmitter(first.key_extractor, n_dests,
                                       self.execution_mode,
                                       key_field=first.key_field,
                                       key_fields=getattr(first,
                                                          "key_fields",
                                                          None))
            if routing is RoutingMode.BROADCAST:
                em = TPUBroadcastEmitter(n_dests, 0, self.execution_mode)
            else:
                em = TPUForwardEmitter(1 if one_to_one else n_dests, 0,
                                       self.execution_mode)
            # keyed consumer fed by forward/broadcast: prefetch its key
            # column so a device-computed key never costs a sync D2H
            em.prefetch_field = getattr(first, "key_field", None)
            return em
        if getattr(first, "accepts_columns", False):
            # with_columns sink: whole column batches, no row boxing
            if not p_tpu:
                raise WindFlowError(
                    f"{first.name}: with_columns sink needs a device-plane "
                    "producer (CPU-plane edges deliver rows); drop "
                    "with_columns or move the producer to the device plane")
            if routing in (RoutingMode.KEYBY, RoutingMode.BROADCAST):
                raise WindFlowError(
                    f"{first.name}: with_columns sink supports forward/"
                    "rebalancing routing only (whole batches round-robin; "
                    "keyed distribution would need a device re-shard — "
                    "put the keyed operator before the sink)")
            from ..tpu.emitters_tpu import TPUColumnarExitEmitter
            return TPUColumnarExitEmitter(1 if one_to_one else n_dests,
                                          self.execution_mode)
        if routing is RoutingMode.KEYBY:
            # key_extractor is normalized to a callable by BasicOperator
            em: BasicEmitter = KeyByEmitter(first.key_extractor, n_dests,
                                            obs, self.execution_mode)
        elif routing is RoutingMode.BROADCAST:
            em = BroadcastEmitter(n_dests, obs, self.execution_mode)
        elif one_to_one:
            em = ForwardEmitter(1, obs, self.execution_mode)
        else:  # FORWARD shuffle / REBALANCING
            em = ForwardEmitter(n_dests, obs, self.execution_mode)
        if p_tpu and not c_tpu:  # device -> host exit
            from ..tpu.emitters_tpu import TPUExitEmitter
            return TPUExitEmitter(em)
        return em

    def _make_collector(self, stage: Stage, replica_idx: int):
        first_replica = stage.first_op.replicas[replica_idx]
        n_in = stage.channels[replica_idx].n_inputs
        if getattr(stage.first_op, "collector_override", None) == "id":
            # WLQ/REDUCE window stages sequence per-key result ids in every
            # execution mode (reference wf/multipipe.hpp:221-224)
            return IDSequencerCollector(n_in, first_replica,
                                        stage.first_op.key_extractor)
        separator = None
        if stage.first_op.op_type == OpType.JOIN:
            a_stages = getattr(stage, "join_a_stages", [])
            separator = sum(s.parallelism for s in a_stages)
        mode = self.execution_mode
        if mode is ExecutionMode.DEFAULT:
            from ..basic import JoinMode
            if (separator is not None
                    and getattr(stage.first_op, "join_mode", None)
                    is JoinMode.DP):
                # DP join replicas need an identical total order
                # (reference Join_Collector, wf/multipipe.hpp:216-220)
                return DPJoinCollector(n_in, first_replica, separator)
            if n_in > 1 or separator is not None:
                return WatermarkCollector(n_in, first_replica, separator)
            return None
        if mode is ExecutionMode.DETERMINISTIC:
            if n_in > 1 or separator is not None:
                return OrderingCollector(n_in, first_replica, separator,
                                         by_timestamp=True)
            return None
        # PROBABILISTIC: always reorder (disorder exists within one channel)
        return KSlackCollector(n_in, first_replica, self.dropped, separator)

    def _make_workers(self, stage: Stage) -> None:
        p = stage.parallelism
        rec_events = self._stage_flightrec_events(stage)
        from ..monitoring.flightrec import env_stall_sec
        stall = env_stall_sec()
        for i in range(p):
            chain: List[Any] = []
            channel = None
            if not stage.is_source:
                channel = stage.channels[i]
                # queue-occupancy/backpressure gauges: the consumer's
                # stats record reads its input channel live (Queue_*)
                stats = stage.first_op.replicas[i].stats
                stats.input_channel = channel
                if hasattr(channel, "bind_stats"):
                    # wait:put / wait:get count there (the native ring
                    # waits in C++ and times nothing)
                    channel.bind_stats(stats)
                coll = self._make_collector(stage, i)
                if coll is not None:
                    chain.append(coll)
                    # restore path reaches the collector via its replica
                    stage.first_op.replicas[i]._collector = coll
            if stage.is_fused_tpu:
                # every sub-op aliases the same fused replica: the worker
                # chain holds it once
                chain.append(stage.first_op.replicas[i])
            else:
                chain.extend(op.replicas[i] for op in stage.ops)
            rec = None
            if rec_events > 0:
                from ..monitoring.flightrec import FlightRecorder
                rec = FlightRecorder(
                    rec_events, pid_label=stage.describe(),
                    tid_label=f"{self.name}/{stage.describe()}[{i}]")
                self._recorders.append(rec)
            w = Worker(f"{self.name}/{stage.describe()}[{i}]", chain, channel,
                       coordinator=self._coordinator, flightrec=rec)
            if rec is not None:
                w.on_crash = self._crash_dump
            if self._supervisor is not None:
                # supervised: a dying worker wakes the supervisor instead
                # of draining + forcing EOS (Worker.run error path)
                w.on_failure = self._supervisor.note_failure
            if stall > 0:
                w.force_idle_tick = True  # liveness ticks for the watchdog
            stage.workers.append(w)
            self._workers.append(w)

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def start(self, restore_from: Optional[str] = None) -> None:
        if self._started:
            raise WindFlowError("PipeGraph already started")
        self._validate()
        # supervision (with_supervision / WF_SUPERVISE=1): the supervisor
        # exists BEFORE _build so every worker gets its failure hook, and
        # checkpointing is enabled implicitly — a supervisor without a
        # checkpoint to restore can only resume from in-memory cursors
        if self._supervise_enabled:
            if not self._ckpt_enabled:
                self.with_checkpointing()
            from ..supervision.supervisor import Supervisor
            self._supervisor = Supervisor(self, self._supervise_policy)
            if self._device_probe is None:
                from ..supervision.health import probe_from_env
                self._device_probe = probe_from_env()
        if any(getattr(op, "is_tpu", False) for op in self._ops):
            # persistent compilation cache BEFORE any device program traces
            from ..runtime.compile_cache import setup_compile_cache
            setup_compile_cache(self._compile_cache_dir)
            # initialize the JAX backend on the MAIN thread: lazy first-touch
            # inside a worker thread can deadlock the PJRT client handshake
            import jax
            jax.devices()
        # checkpoint store/coordinator BEFORE _build: workers bind to the
        # coordinator at construction, and sources anchor their barrier
        # cursor to the restored epoch. SLO sampling too: replica
        # histograms allocate at replica construction
        self._ensure_slo_sampling()
        ckpt_dir, manifest = self._setup_checkpointing(restore_from)
        self._build()
        if ckpt_dir is not None:
            self._restore_replicas(ckpt_dir, manifest)
        if self._prewarm_enabled:
            # compile every bucketed chain signature BEFORE any source
            # opens: cold-start pays here, the stream never retraces
            self._prewarm_device_programs()
        if self._coordinator is not None:
            self._coordinator.expected_acks = len(self._workers)
            self._coordinator.worker_names = [w.name for w in self._workers]
            self._coordinator.diagnose = self._worker_diagnostics
            self._coordinator.start()
        self._started = True
        self._t0 = time.monotonic()
        # flight-recorder registry (feeds MonitoringServer's /trace) +
        # the watchdog: its gauge of the process always, its check of the
        # workers under WF_STALL_SEC > 0 (default off)
        from ..monitoring.flightrec import (StallWatchdog, env_stall_sec,
                                            register_graph)
        register_graph(self)
        self._watchdog = StallWatchdog(self, env_stall_sec(),
                                       dump_fn=self._stall_dump)
        if env_flag("WF_TRACING_ENABLED"):
            # reference: one MonitoringThread per PipeGraph when tracing
            # (wf/pipegraph.hpp:671-675)
            from ..monitoring.monitor import MonitoringThread
            self._monitor = MonitoringThread(self)
            self._monitor.start()
        if self._supervisor is not None:
            for w in self._workers:
                w.on_failure = self._supervisor.note_failure
            self._capture_initial_positions()
        for w in self._workers:
            w.start()
        if self._watchdog is not None:
            self._watchdog.start()
        if self._supervisor is not None:
            self._supervisor.start()
        # autoscaler policy thread (with_autoscaler / WF_AUTOSCALE=1)
        if self._autoscale_enabled or env_flag("WF_AUTOSCALE"):
            from ..scaling.autoscaler import Autoscaler
            self._autoscaler = Autoscaler(self, self._autoscale_policy)
            self._autoscaler.start()
        # overload governor (with_slo / WF_SLO_P99_MS): created after the
        # autoscaler so the SCALE rung can read its MAX_PAR and
        # synchronize cooldowns
        self._setup_overload_governor()
        if self._overload_governor is not None:
            self._overload_governor.start()

    def wait_end(self) -> None:
        if not self._started:
            raise WindFlowError("PipeGraph not started")
        if self._ended:
            return
        while True:
            # a live rescale (or supervised restart) REPLACES
            # self._workers mid-run: re-read the list after every join
            # sweep so we wait on the current plane
            workers = self._workers
            try:
                for w in workers:
                    w.join()
            except RuntimeError:
                # mid-rebuild: the new plane is published but its
                # threads are not started yet — come back around
                time.sleep(0.02)
                continue
            if self._workers is not workers:
                continue
            if self._rescaling or self._supervising:
                time.sleep(0.05)  # the new plane is coming
                continue
            sup = self._supervisor
            if sup is not None and sup.active \
                    and any(w.error is not None for w in workers):
                # a worker died but the supervisor has not reacted yet:
                # give it the chance (it restarts or escalates)
                time.sleep(0.02)
                continue
            break
        self._ended = True
        if self._supervisor is not None:
            self._supervisor.stop()
        if self._autoscaler is not None:
            self._autoscaler.stop()
        if self._overload_governor is not None:
            self._overload_governor.stop()
        self.elapsed_sec = time.monotonic() - self._t0
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._coordinator is not None:
            self._coordinator.stop()
        if self._monitor is not None:
            self._monitor.stop()
            self._monitor.join(timeout=3)
        if self._supervisor is not None \
                and self._supervisor.escalated is not None:
            raise self._supervisor.escalated
        errors = [w.error for w in self._workers if w.error is not None]
        if not errors:
            # exactly-once sinks: the run finished cleanly, so every
            # still-pending epoch (the post-final-barrier tail, and any
            # epoch whose finalize landed after its sink worker exited)
            # commits now, in epoch order, on this thread. On the error
            # path they stay pending: restore rolls forward/aborts them.
            for op in self._ops:
                for r in {id(r): r for r in op.replicas}.values():
                    fin = getattr(r, "txn_complete", None)
                    if fin is not None:
                        fin()
        if errors:
            if len(errors) == 1:
                raise errors[0]
            # SEVERAL workers died: naming only errors[0] silently
            # discarded the rest — aggregate, naming every dead worker
            from ..basic import WorkerFailuresError
            raise WorkerFailuresError(
                {w.name: w.error for w in self._workers
                 if w.error is not None}) from errors[0]
        if env_flag("WF_TRACING_ENABLED"):
            self.dump_stats(os.environ.get("WF_LOG_DIR", "log"))

    def run(self, restore_from: Optional[str] = None) -> None:
        """Blocking run (reference ``PipeGraph::run``, L610).

        ``restore_from``: a checkpoint store root (resumes from the
        latest committed checkpoint) or one checkpoint directory. The
        topology must match the checkpointed one (same operator names
        and parallelisms); replayable sources resume from their recorded
        positions."""
        self.start(restore_from)
        self.wait_end()

    def _validate(self) -> None:
        if not self._stages:
            raise WindFlowError("empty PipeGraph: no sources")
        for s in self._stages:
            if s.is_split:
                missing = [b for b, st in enumerate(s.split_branches)
                           if st is None]
                if missing:
                    raise WindFlowError(
                        f"split after {s.describe()}: empty branches {missing}")
            elif s.downstream is None and not s.is_sink:
                raise WindFlowError(
                    f"stage {s.describe()} has no sink downstream")

    # ------------------------------------------------------------------
    # introspection (reference: getNumThreads, getNumDroppedTuples, stats)
    # ------------------------------------------------------------------
    def get_num_threads(self) -> int:
        self._build()
        return len(self._workers)

    def get_num_dropped_tuples(self) -> int:
        return self.dropped.value

    def get_stats(self) -> Dict[str, Any]:
        ops = []
        for op in self._ops:
            if getattr(op, "_fused_hidden", False):
                continue  # reported once under the fused stage's name
            fused_label = getattr(op, "_fused_stage_label", None)
            ops.append({
                "name": fused_label or op.name,
                "kind": ("Fused_TPU_Chain" if fused_label
                         else type(op).__name__),
                "parallelism": op.parallelism,
                "replicas": [r.stats.to_dict() for r in op.replicas],
            })
        # the process's own account (the watchdog's gauge: Process_*,
        # Gc_*) on ONE record, the first source replica's, so a sum over
        # the graph's records counts it once
        if self._watchdog is not None and ops and ops[0]["replicas"]:
            ops[0]["replicas"][0].update(self._watchdog.process_fields())
        # mark-final-then-drop: replicas a scale-down removed appear in
        # exactly ONE report with Final=true, then their series end
        finals, self._final_series = self._final_series, []
        ops.extend(finals)
        st = {
            "PipeGraph_name": self.name,
            "Mode": self.execution_mode.name,
            "Time_policy": self.time_policy.name,
            "Threads": len(self._workers),
            "Dropped_tuples": self.dropped.value,
            "Operators": ops,
        }
        if self._coordinator is not None:
            st["Checkpoints"] = self._coordinator.stats()
        if self._rescale_ctrl is not None:
            st["Rescales"] = self._rescale_ctrl.stats()
        if self._autoscaler is not None:
            st["Autoscaler"] = self._autoscaler.stats()
        if self._supervisor is not None:
            st["Supervision"] = self._supervisor.stats()
        if self._overload_governor is not None:
            st["Overload"] = self._overload_governor.stats()
        if self._prewarm_report is not None:
            st["Prewarm"] = self._prewarm_report
        if self._dlq is not None:
            st["Dead_letters"] = self._dlq.total
        # crash visibility: a worker that died no longer disappears
        # silently — its exception surfaces in the final report (the
        # replica-level Worker_last_error carries the full traceback)
        errs = {w.name: f"{type(w.error).__name__}: {w.error}"
                for w in self._workers if w.error is not None}
        if errs:
            st["Worker_errors"] = errs
        return st

    def dump_stats(self, log_dir: str = "log") -> str:
        """JSON stats + the dataflow diagram. The reference renders a PDF
        at wait_end and an SVG for the dashboard
        (``wf/pipegraph.hpp:525-534,732-734``); here the dot source and an
        SVG are always written (built-in layered renderer when no ``dot``
        binary exists) and a PDF additionally when Graphviz is present."""
        from ..monitoring.diagram import render_graphviz

        os.makedirs(log_dir, exist_ok=True)
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in self.name) or "pipegraph"
        path = os.path.join(log_dir, f"{safe}_stats.json")
        with open(path, "w") as f:
            json.dump(self.get_stats(), f, indent=2)
        dot_src = self.to_dot()
        with open(os.path.join(log_dir, f"{safe}_diagram.dot"), "w") as f:
            f.write(dot_src + "\n")
        svg = render_graphviz(dot_src, "svg")
        with open(os.path.join(log_dir, f"{safe}_diagram.svg"), "wb") as f:
            f.write(svg if svg is not None else self.to_svg().encode())
        pdf = render_graphviz(dot_src, "pdf")
        if pdf is not None:
            with open(os.path.join(log_dir, f"{safe}_diagram.pdf"),
                      "wb") as f:
                f.write(pdf)
        return path

    # -- diagram (reference builds a Graphviz PDF/SVG) ---------------------
    def to_svg(self) -> str:
        """Dependency-free layered SVG of the stage DAG (the dashboard
        diagram; Graphviz output is preferred when a binary exists)."""
        from ..monitoring.diagram import stages_to_svg
        return stages_to_svg(self._stages, self.name)

    def to_dot(self) -> str:
        gname = self.name.replace('"', "'")
        lines = [f'digraph "{gname}" {{', "  rankdir=LR;",
                 "  node [shape=box, style=rounded];"]
        for s in self._stages:
            label = s.describe().replace('"', "'")
            par = "|".join(str(o.parallelism) for o in s.ops)
            extra = ""
            if s.chain_refused:
                # chain() fallback diagnostics: why this stage did not
                # fuse into its predecessor
                reason = s.chain_refused.replace('"', "'")
                extra = f"\\n[unchained: {reason}]"
            lines.append(f'  s{s.id} [label="{label}\\n({par}){extra}"];')
        for s in self._stages:
            for e in s.upstreams:
                style = ""
                if e.branch is not None:
                    style = f' [label="b{e.branch}"]'
                lines.append(f"  s{e.stage.id} -> s{s.id}{style};")
        lines.append("}")
        return "\n".join(lines)

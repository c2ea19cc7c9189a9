"""Per-replica statistics (reference ``wf/stats_record.hpp:49-160``).

Counters: inputs/outputs received/sent, ignored (dropped) tuples, service time
EWMA (``wf/basic_operator.hpp:144-158``), and device-plane traffic (batches
staged to/from the TPU, bytes moved — the analog of the reference's kernels
launched / bytes H2D/D2H). Serialized to JSON by the PipeGraph at wait_end
(``wf/pipegraph.hpp:464-522``).

On top of the reference's counters this record carries the latency-tracing
plane (monitoring/tracing.py): per-replica log2 histograms of service time,
dispatch prep/commit latency and (sinks) end-to-end latency — allocated only
when sampling is enabled, so the default hot path never touches them — plus
queue-occupancy/backpressure gauges read from the replica's input channel
and the emitter-side FIFOs.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

from .tracing import StageCounters

_EWMA_ALPHA = 0.1


def _wm_stall_sec() -> float:
    """Watermark stall threshold (``WF_WM_STALL_SEC``): a replica whose
    watermark has not advanced for this long WHILE inputs keep arriving is
    event-time-stalled (frozen source watermark, wedged punctuation path).
    Quiet replicas (no new inputs either) are ``idle``, never stalled."""
    try:
        return max(0.1, float(os.environ.get("WF_WM_STALL_SEC", "5")))
    except ValueError:
        return 5.0


class StatsRecord(StageCounters):
    # ``op_name``, ``recorder`` and the per-stage cumulative times and
    # counts (``stage_ns`` / ``stage_n``) are StageCounters' slots
    __slots__ = (
        "replica_idx", "start_time",
        "inputs_received", "bytes_received", "outputs_sent", "bytes_sent",
        "inputs_ignored", "punct_received", "punct_sent",
        "service_time_us", "eff_service_time_us",
        "device_batches_in", "device_batches_out",
        "device_bytes_h2d", "device_bytes_d2h", "device_programs_run",
        # window operators (tpu/ffat_tpu.py): windows fired (a row each,
        # empty ones too) and the programs that answered them (a full
        # step with its fire block, or a fire-only program) with their
        # widths summed (the lanes a program runs, live or masked: what a
        # walk by lane costs by); of those,
        # the programs that answered per distinct ring range over every
        # key slot at once, and the ranges summed over them; and the
        # programs the planner closed at a whole round because one more
        # would have passed G_CAP distinct ranges; and the programs of
        # count-based windows that answered by sliding scan (two block
        # scans over every leaf) and not by lane
        "windows_fired", "fire_programs", "fire_lanes",
        "fire_grouped_programs", "fire_groups", "fire_range_cuts",
        "fire_sliding_programs",
        # the rows of the plans the HOST built for those programs (a
        # row a firing key slot, its chunk of consecutive windows, which
        # the program expands into its lanes; both window types since
        # PR 39), the programs whose plan took one window of every chunk
        # (a time-based plan by the plan's width then reckons its ranges
        # from the chunks themselves, no class detection), and the
        # batches whose prep built no batch-sized plane but the rows'
        # slots (count-based windows: the step numbers its own rows)
        "fire_plan_rows", "fire_one_round_plans", "prep_by_key_batches",
        # the level rebuilds of a window operator's forest (a step's
        # in-program rebuild or the standalone one), and of those the
        # steps that rebuilt only the ancestors of the panes written and
        # evicted since the last rebuild (time-based windows)
        "rebuild_programs", "rebuild_partial_programs",
        # key turnover of a time-based window operator: keys given a
        # slot, slots given back (a key none of whose windows holds an
        # event any more), slots in use now (a gauge), doublings of the
        # key capacity (each reallocates the forest and recompiles)
        "keys_admitted", "keys_reclaimed", "key_slots_live",
        "key_capacity_growths",
        # the keyed state plane's grid scans (tpu/ops_tpu.py
        # _KeyedStateScan, standalone or fused): scans run, rows given a
        # grid cell, the grids' cells (keys bucket x depth bucket), their
        # depths and the keys they touched, each summed over the scans;
        # the scans whose new keys were admitted in one operation
        "scan_programs", "scan_rows", "scan_cells", "scan_depth",
        "scan_keys", "scan_batch_admits",
        # the device interval join (tpu/join_tpu.py): rows that probed
        # and rows archived, by side [A, B]; pairs delivered and the
        # batches they left in; rows purged; live rows of both archives
        # after the last step read back (a gauge) and the live rows of
        # the archive a step probed, summed over the steps; doublings of
        # an archive (each recompiles the step); rows that arrived behind
        # their own side's purge line (they probe what is left and are
        # not archived); the rows both rings have room for (a gauge) and
        # the rows of the other ring a step's probe compared with, live
        # or dead, summed over the steps
        "join_probe_rows", "join_archived_rows", "join_pairs",
        "join_output_batches", "join_purged_rows", "join_archive_rows",
        "join_scanned_rows", "join_archive_growths", "join_late_probes",
        "join_batches_held", "join_archive_capacity_rows",
        "join_probed_rows",
        # a device split's deliveries to its branches: the batch whole (every
        # row selected the branch) or a gathered sub-batch
        "split_whole_batches", "split_gathered_batches",
        "staging_pool_hits", "staging_pool_misses",
        "dispatch_stalls", "dispatch_depth_max",
        # finish halves the dispatch queue ran (a compacting commit's
        # readback-and-emit), and those that ran one launch later
        "dispatch_readbacks", "dispatch_readbacks_deferred",
        # megabatch scan loop (runtime/dispatch.py + tpu/fused_ops.py):
        # grouped dispatches (loops), batches committed through them,
        # and the widest group observed — Programs_per_batch in to_dict
        # derives the amortization from device_programs_run
        "megabatch_loops", "megabatch_batches", "megabatch_max",
        # columnar ingest plane (SourceReplica.ship_columns): rows the
        # shipped blocks carried; blocks and host nanoseconds are the
        # ``ingest`` stage's — Ingest_block_ns_per_row in to_dict is the
        # per-row host cost of the block path (the row path has no
        # analog: its cost IS the per-tuple Python this plane removes)
        "ingest_rows",
        # aligned-barrier checkpointing (windflow_tpu.checkpoint):
        # per-replica snapshot count/duration/size + barrier-alignment
        # stall time (multi-input workers buffering behind the barrier)
        "checkpoints_taken", "checkpoint_snapshot_total_us",
        "checkpoint_last_snapshot_us", "checkpoint_bytes_total",
        "checkpoint_align_total_us",
        # barrier CUT pause: how long the worker was actually fenced by
        # the barrier (capture + ack). Equals snapshot time in sync
        # mode; with WF_CKPT_ASYNC it excludes serialization + writes,
        # which run on the coordinator's background uploader
        "checkpoint_cut_total_us", "checkpoint_last_cut_us",
        # exactly-once sinks (windflow_tpu.sinks.transactional): per-epoch
        # two-phase-commit accounting — pre-commits at the barrier,
        # commits on coordinator finalize, aborts on restore/duplicate
        # discard, and fenced (refused) writes from stale zombie replicas
        "txn_precommits", "txn_commits", "txn_aborts", "txn_fenced_writes",
        # per-record error policies + dead-letter queue
        # (windflow_tpu.supervision.errors): quarantined records,
        # policy-skipped records, retry attempts; and Kafka transient-
        # error reconnect/retry events (kafka/connectors.py)
        "dlq_records", "dlq_skipped", "dlq_retries", "kafka_reconnects",
        # overload protection (windflow_tpu.overload): records/bytes shed
        # by admission control at the SOURCE boundary (before barriers
        # and the exactly-once plane — accounted, never silently lost)
        "shed_records", "shed_bytes",
        # mesh execution plane (windflow_tpu.mesh): per-shard visibility
        # for operators whose parallelism is a device mesh — steps run,
        # bytes through the in-program all_to_all shuffle, host-observed
        # step time, and slot occupancy/skew of the block-owner mapping.
        # mesh_devices == 0 marks a non-mesh replica; to_dict then omits
        # the Mesh_* keys so /metrics carries mesh series only where a
        # mesh exists
        "mesh_devices", "mesh_steps", "mesh_shuffle_bytes",
        "mesh_step_total_us", "mesh_shard_occupancy", "mesh_shard_skew",
        # devices this mesh replica is running WITHOUT because the
        # supervision plane excluded them (device-loss failover): > 0
        # means degraded capacity until the probe sees them return
        "mesh_degraded",
        # tiered keyed state (windflow_tpu.state.tiered): hot/cold key
        # gauges, batched promote/demote counters with promote time, and
        # the lookup/miss counters behind Tier_miss_rate. tier_enabled
        # marks a replica whose engine runs with_tiering — to_dict omits
        # the Tier_* keys elsewhere, the Mesh_* discipline
        "tier_enabled", "tier_hot_keys", "tier_cold_keys",
        "tier_promotes", "tier_demotes", "tier_promote_usec_total",
        "tier_lookups", "tier_misses",
        # event-time health plane: watermark progress gauges + unified
        # late-record accounting. ``wm_current``/``wm_advances`` are the
        # only hot-path writes (two stores on ADVANCE only, in
        # BasicReplica._advance_wm); lag/idle/stall derive at poll time
        # (to_dict / worker idle tick) so the per-tuple path stays flat.
        # ``wm_max_source_ts`` is tracked only on explicit event-time
        # source paths (push_with_timestamp / push_columns(ts=...)) —
        # ingress time has wm == ts, so event lag is identically zero
        "wm_current", "wm_advances", "wm_max_source_ts", "wm_stalls",
        "_wm_seen_advances", "_wm_mark_mono", "_wm_inputs_at_mark",
        "_wm_stalled", "_wm_idle", "_wm_stall_usec",
        # unified late-record accounting (every window engine: CPU keyed /
        # persistent / interval join / FFAT CPU / TPU / mesh / fused
        # terminators). late_records counts tuples that arrived behind the
        # watermark (or behind a fired window boundary); late_dropped the
        # subset discarded. Late_admitted derives (records - dropped), so
        # engines whose drop decision is deferred to a device program
        # (mesh FFAT) can count arrivals and drops at different sites and
        # the conservation invariant still holds at the totals
        "late_records", "late_dropped", "hist_lateness",
        "is_terminated", "_last_svc_start",
        # EWMA seeding: value==0.0 is NOT a reliable "unseeded" sentinel
        # (a genuine ~0 first sample would re-seed forever, biasing early
        # readings); explicit flags instead
        "_svc_seeded",
        # latency-tracing plane (None / 0 when sampling is off)
        "sample_every", "_svc_rec",
        "hist_service", "hist_prep", "hist_commit", "hist_e2e",
        # queue / backpressure plane
        "input_channel", "pipe_depth_max", "worker_idle_ticks",
        # exit FIFO depth summed at each add (mean depth over a window is
        # a delta ratio against Exit_fifo_batches) and the Worker whose
        # thread clocks this record reports (first record of its chain)
        "exit_fifo_depth_sum", "worker",
        # device-chain fusion (tpu/fused_ops.py): number of sub-operators
        # fused into this replica's single per-batch program (0 = not a
        # fused replica)
        "fused_ops",
        # XLA compile attribution (monitoring/flightrec.instrumented_jit):
        # (re)traces vs cache hits on the replica's device programs, with
        # elapsed compile time and the triggering abstract signature
        "compile_count", "compile_usec_total", "compile_last_us",
        "compile_last_signature", "compile_cache_hits",
        # worker crash visibility: a replica chain that died records the
        # exception here instead of only dying as a silent daemon thread
        "worker_crashes", "worker_last_error",
    )

    def __init__(self, op_name: str = "", replica_idx: int = 0,
                 sample_every: int = 0) -> None:
        super().__init__(op_name)
        self.replica_idx = replica_idx
        self.start_time = time.monotonic()
        self.inputs_received = 0
        self.bytes_received = 0
        self.outputs_sent = 0
        self.bytes_sent = 0
        self.inputs_ignored = 0
        self.punct_received = 0
        self.punct_sent = 0
        self.service_time_us = 0.0  # EWMA over svc() durations
        self.eff_service_time_us = 0.0
        self.device_batches_in = 0
        self.device_batches_out = 0
        self.device_bytes_h2d = 0
        self.device_bytes_d2h = 0
        self.device_programs_run = 0
        self.windows_fired = 0
        self.fire_programs = 0
        self.fire_lanes = 0
        self.fire_grouped_programs = 0
        self.fire_groups = 0
        self.fire_range_cuts = 0
        self.fire_sliding_programs = 0
        self.fire_plan_rows = 0
        self.fire_one_round_plans = 0
        self.prep_by_key_batches = 0
        self.rebuild_programs = 0
        self.rebuild_partial_programs = 0
        self.keys_admitted = 0
        self.keys_reclaimed = 0
        self.key_slots_live = 0
        self.key_capacity_growths = 0
        self.scan_programs = 0
        self.scan_rows = 0
        self.scan_cells = 0
        self.scan_depth = 0
        self.scan_keys = 0
        self.scan_batch_admits = 0
        self.join_probe_rows = [0, 0]
        self.join_archived_rows = [0, 0]
        self.join_pairs = 0
        self.join_output_batches = 0
        self.join_purged_rows = 0
        self.join_archive_rows = 0
        self.join_scanned_rows = 0
        self.join_archive_growths = 0
        self.join_late_probes = 0
        self.join_batches_held = 0
        self.join_archive_capacity_rows = 0
        self.join_probed_rows = 0
        self.split_whole_batches = 0
        self.split_gathered_batches = 0
        self.staging_pool_hits = 0  # recycled staging buffers (ArrayPool)
        self.staging_pool_misses = 0
        # device-ahead dispatch pipeline (runtime/dispatch.py); the split
        # of a batch's path into host prep and commit is the stage
        # table's (``prep`` / ``commit``: wall and thread-CPU totals)
        self.dispatch_stalls = 0  # forced ordering-point drains
        self.dispatch_depth_max = 0
        self.dispatch_readbacks = 0  # finish halves run
        self.dispatch_readbacks_deferred = 0  # ... one launch later
        self.megabatch_loops = 0
        self.megabatch_batches = 0
        self.megabatch_max = 0
        self.ingest_rows = 0
        self.checkpoints_taken = 0
        self.checkpoint_snapshot_total_us = 0.0
        self.checkpoint_last_snapshot_us = 0.0
        self.checkpoint_bytes_total = 0
        self.checkpoint_align_total_us = 0.0
        self.checkpoint_cut_total_us = 0.0
        self.checkpoint_last_cut_us = 0.0
        self.txn_precommits = 0
        self.txn_commits = 0
        self.txn_aborts = 0
        self.txn_fenced_writes = 0
        self.dlq_records = 0
        self.dlq_skipped = 0
        self.dlq_retries = 0
        self.kafka_reconnects = 0
        self.shed_records = 0
        self.shed_bytes = 0
        self.mesh_devices = 0
        self.mesh_steps = 0
        self.mesh_shuffle_bytes = 0
        self.mesh_step_total_us = 0.0
        self.mesh_shard_occupancy = 0
        self.mesh_shard_skew = 0.0
        self.mesh_degraded = 0
        self.tier_enabled = False
        self.tier_hot_keys = 0
        self.tier_cold_keys = 0
        self.tier_promotes = 0
        self.tier_demotes = 0
        self.tier_promote_usec_total = 0.0
        self.tier_lookups = 0
        self.tier_misses = 0
        # -- event-time health plane ----------------------------------------
        self.wm_current = 0
        self.wm_advances = 0
        self.wm_max_source_ts = 0
        self.wm_stalls = 0
        self._wm_seen_advances = 0
        self._wm_mark_mono = self.start_time
        self._wm_inputs_at_mark = 0
        self._wm_stalled = False
        self._wm_idle = True
        self._wm_stall_usec = _wm_stall_sec() * 1e6
        self.late_records = 0
        self.late_dropped = 0
        self.is_terminated = False
        self._last_svc_start = 0.0
        self._svc_seeded = False
        # -- latency tracing (monitoring/histogram.py) ----------------------
        self.sample_every = max(0, int(sample_every))
        # service-histogram request flag: the replica's traced-message
        # branch sets it; the next end_svc consumes it. Keying service
        # sampling off TRACED messages keeps the end_svc hot path at one
        # bool check regardless of sampling rate (and records a cohort
        # consistent with the e2e samples).
        self._svc_rec = False
        if self.sample_every > 0:
            from .histogram import LatencyHistogram
            self.hist_service: Optional[Any] = LatencyHistogram()
            self.hist_prep: Optional[Any] = LatencyHistogram()
            self.hist_commit: Optional[Any] = LatencyHistogram()
            self.hist_e2e: Optional[Any] = LatencyHistogram()
            self.hist_lateness: Optional[Any] = LatencyHistogram()
        else:
            self.hist_service = None
            self.hist_prep = None
            self.hist_commit = None
            self.hist_e2e = None
            self.hist_lateness = None
        # -- queue / backpressure gauges ------------------------------------
        self.input_channel = None  # wired by PipeGraph._make_workers
        self.pipe_depth_max = 0  # emitter-side FIFO high-water mark
        self.worker_idle_ticks = 0
        self.exit_fifo_depth_sum = 0
        self.worker = None  # wired by the Worker that reports here
        self.fused_ops = 0  # sub-ops fused into this replica's program
        # -- compile attribution / crash visibility / flight recorder -------
        self.compile_count = 0
        self.compile_usec_total = 0.0
        self.compile_last_us = 0.0
        self.compile_last_signature = ""
        self.compile_cache_hits = 0
        self.worker_crashes = 0
        self.worker_last_error = ""

    # -- stage totals older call sites and tests read by these names ---------
    @property
    def dispatch_batches(self) -> int:
        return self.stage_count("prep")

    @property
    def dispatch_host_prep_total_us(self) -> float:
        return self.stage_usec("prep")

    @property
    def dispatch_commit_total_us(self) -> float:
        return self.stage_usec("commit")

    # -- service-time recording (wf/basic_operator.hpp:134-158) -------------
    def start_svc(self) -> None:
        self._last_svc_start = time.perf_counter()

    def end_svc(self, n_tuples: int = 1) -> None:
        dt_us = (time.perf_counter() - self._last_svc_start) * 1e6
        per_tuple = dt_us / max(1, n_tuples)
        if not self._svc_seeded:
            self._svc_seeded = True
            self.service_time_us = per_tuple
        else:
            self.service_time_us += _EWMA_ALPHA * (per_tuple - self.service_time_us)
        self.eff_service_time_us = self.service_time_us
        if self._svc_rec:
            self._svc_rec = False
            if self.hist_service is not None:
                self.hist_service.record(per_tuple)
            # flight-recorder svc span rides the SAME traced-cohort gate
            # (one bool check already paid): no new per-tuple cost. The
            # op name is part of the span name: chained operators share
            # one ring, and an upstream op's svc interval CONTAINS its
            # inline-chained successors' — per-op names keep each
            # operator's own spans sequential and the nesting readable
            if self.recorder is not None:
                self.recorder.event("svc:" + self.op_name, dt_us, n_tuples)

    # -- dispatch-pipeline latencies: under latency sampling the ``prep``
    # and ``commit`` stages record each duration into ``hist_prep`` /
    # ``hist_commit`` (tracing.STAGES ``note``; no hook is bound where
    # sampling is off); totals, counts and ring events are the stage
    # helper's -----------------------------------------------------------------
    def note_megabatch(self, k: int, us: float) -> None:
        """One megabatch scan loop: K same-signature batches committed
        through ONE program dispatch (``FusedTPUReplica._run_megabatch``)."""
        self.megabatch_loops += 1
        self.megabatch_batches += k
        if k > self.megabatch_max:
            self.megabatch_max = k
        if self.recorder is not None:
            self.recorder.event("megabatch:scan", us, k)

    def note_dispatch_depth(self, depth: int) -> None:
        if depth > self.dispatch_depth_max:
            self.dispatch_depth_max = depth

    def note_dispatch_stall(self) -> None:
        self.dispatch_stalls += 1

    def note_dispatch_readback(self, deferred: bool) -> None:
        """One finish half run by the dispatch queue; ``deferred`` where
        a later launch of the same replica had been issued first (a
        drain's or an idle tick's last finish has none)."""
        self.dispatch_readbacks += 1
        if deferred:
            self.dispatch_readbacks_deferred += 1

    # -- checkpointing (windflow_tpu.checkpoint) -----------------------------
    def note_checkpoint(self, snapshot_us: float, nbytes: int,
                        align_us: float,
                        cut_us: Optional[float] = None) -> None:
        """One aligned snapshot of this replica's worker chain:
        state-capture duration, blob bytes written, how long barrier
        alignment stalled the chain (0 for single-input workers), and
        the barrier CUT pause (capture + ack; defaults to the snapshot
        duration for call sites that don't distinguish the two)."""
        if cut_us is None:
            cut_us = snapshot_us
        self.checkpoints_taken += 1
        self.checkpoint_snapshot_total_us += snapshot_us
        self.checkpoint_last_snapshot_us = snapshot_us
        self.checkpoint_bytes_total += nbytes
        self.checkpoint_align_total_us += align_us
        self.checkpoint_cut_total_us += cut_us
        self.checkpoint_last_cut_us = cut_us
        if self.recorder is not None:
            if align_us > 0:
                self.recorder.event("barrier_align", align_us)
            self.recorder.event("ckpt_snapshot", snapshot_us,
                                {"bytes": nbytes})

    # -- compile attribution (monitoring/flightrec.instrumented_jit) ---------
    def note_compile(self, us: float, signature: str = "") -> None:
        """One XLA (re)trace+compile on this replica's device programs:
        elapsed time and the abstract signature that triggered it."""
        self.compile_count += 1
        self.compile_usec_total += us
        self.compile_last_us = us
        self.compile_last_signature = signature

    # -- mesh execution plane (windflow_tpu.mesh) -----------------------------
    def note_mesh_step(self, us: float, shuffle_bytes: int) -> None:
        """One sharded step: host-observed dispatch time + the bytes its
        in-program all_to_all moved (every tuple column crosses the
        shuffle exactly once per step)."""
        self.mesh_steps += 1
        self.mesh_step_total_us += us
        self.mesh_shuffle_bytes += shuffle_bytes
        if self.recorder is not None:
            self.recorder.event("mesh:step", us,
                                {"bytes": shuffle_bytes})

    # -- tiered keyed state (windflow_tpu.state.tiered) -----------------------
    def note_tier_promote(self, n_keys: int, usec: float) -> None:
        """One BATCHED promote (cold rows -> one slot-row scatter):
        ``n_keys`` keys moved hot in ``usec`` host-observed time."""
        self.tier_promotes += n_keys
        self.tier_promote_usec_total += usec
        if self.recorder is not None:
            self.recorder.event("tier:promote", usec, n_keys)

    def note_tier_demote(self, n_keys: int) -> None:
        """One BATCHED demote (slot-row gather -> cold writes)."""
        self.tier_demotes += n_keys
        if self.recorder is not None:
            self.recorder.event("tier:demote", 0.0, n_keys)

    def note_tier_gauges(self, hot: int, cold: int, lookups: int,
                         misses: int) -> None:
        self.tier_enabled = True
        self.tier_hot_keys = hot
        self.tier_cold_keys = cold
        self.tier_lookups = lookups
        self.tier_misses = misses

    # -- overload protection (windflow_tpu.overload) --------------------------
    def note_shed(self, n: int, nbytes: int) -> None:
        """Records shed by source admission control (never emitted, so
        they appear in NO other counter — offered = admitted + shed)."""
        self.shed_records += n
        self.shed_bytes += nbytes

    # -- event-time health plane ---------------------------------------------
    def note_late(self, n_records: int, n_dropped: int = 0,
                  lateness_us: Any = None) -> None:
        """Late-record accounting for one engine decision (or one batched
        block of decisions). ``n_records`` tuples observed behind the
        watermark / a fired boundary; ``n_dropped`` of the replica's late
        tuples discarded. The two may be counted at DIFFERENT call sites
        (device engines learn the drop count from a later readback), so
        pass ``n_records=0`` for drop-only updates of tuples already
        counted late on arrival. ``lateness_us`` — observed (wm - ts),
        scalar or array — feeds the lateness histogram when tracing is on."""
        self.late_records += n_records
        self.late_dropped += n_dropped
        h = self.hist_lateness
        if h is not None and lateness_us is not None:
            if hasattr(lateness_us, "__len__"):
                h.record_many(lateness_us)
            else:
                h.record(lateness_us)
        if self.recorder is not None and n_dropped:
            self.recorder.event("late:drop", 0.0, n_dropped)

    def poll_watermark(self, now: Optional[float] = None) -> float:
        """Derive watermark lag / idle / stall from the advance counter —
        called at observation points (to_dict, worker idle ticks), never
        per tuple. Returns the wall-clock lag in microseconds since the
        watermark last advanced. Stall detection is edge-triggered: a
        replica whose inputs keep arriving while the watermark has been
        frozen past ``WF_WM_STALL_SEC`` bumps ``wm_stalls`` once per
        freeze (and logs a ``wm:stall`` flight-recorder span); a replica
        with no new inputs either is ``idle``, not stalled."""
        if now is None:
            now = time.monotonic()
        adv = self.wm_advances
        if adv != self._wm_seen_advances:
            self._wm_seen_advances = adv
            self._wm_mark_mono = now
            self._wm_inputs_at_mark = self.inputs_received
            self._wm_stalled = False
            self._wm_idle = False
            return 0.0
        lag_us = max(0.0, (now - self._wm_mark_mono) * 1e6)
        self._wm_idle = self.inputs_received == self._wm_inputs_at_mark
        if (not self._wm_idle and not self._wm_stalled
                and lag_us > self._wm_stall_usec):
            self._wm_stalled = True
            self.wm_stalls += 1
            if self.recorder is not None:
                self.recorder.event("wm:stall", lag_us, self.wm_current)
        return lag_us

    # -- latency tracing -----------------------------------------------------
    def note_e2e(self, us: float) -> None:
        """End-to-end latency of one traced tuple (sink side)."""
        if self.hist_e2e is not None:
            self.hist_e2e.record(us)

    def note_pipe_depth(self, depth: int) -> None:
        """Emitter-side FIFO occupancy at one add (_D2HPipeline): the
        high-water mark, and the running sum a reader turns into a mean
        depth over its window."""
        self.exit_fifo_depth_sum += depth
        if depth > self.pipe_depth_max:
            self.pipe_depth_max = depth

    def to_dict(self) -> Dict[str, Any]:
        elapsed = max(time.monotonic() - self.start_time, 1e-9)
        ingest_blocks = self.stage_count("ingest")
        dispatch_batches = self.stage_count("prep")
        d = {
            "Operator_name": self.op_name,
            "Replica_id": self.replica_idx,
            "Inputs_received": self.inputs_received,
            "Bytes_received": self.bytes_received,
            "Outputs_sent": self.outputs_sent,
            "Bytes_sent": self.bytes_sent,
            "Inputs_ignored": self.inputs_ignored,
            "Punctuations_received": self.punct_received,
            "Punctuations_sent": self.punct_sent,
            "Service_time_usec": round(self.service_time_us, 3),
            "Eff_Service_time_usec": round(self.eff_service_time_us, 3),
            "Throughput_tuples_sec": round(self.inputs_received / elapsed, 1),
            "Device_batches_in": self.device_batches_in,
            "Device_batches_out": self.device_batches_out,
            "Device_bytes_H2D": self.device_bytes_h2d,
            "Device_bytes_D2H": self.device_bytes_d2h,
            "Device_programs_run": self.device_programs_run,
            "Windows_fired": self.windows_fired,
            "Fire_programs": self.fire_programs,
            "Fire_lanes": self.fire_lanes,
            "Fire_grouped_programs": self.fire_grouped_programs,
            "Fire_groups": self.fire_groups,
            "Fire_range_cuts": self.fire_range_cuts,
            "Fire_sliding_programs": self.fire_sliding_programs,
            "Fire_plan_rows": self.fire_plan_rows,
            "Fire_one_round_plans": self.fire_one_round_plans,
            "Prep_by_key_batches": self.prep_by_key_batches,
            "Rebuild_programs": self.rebuild_programs,
            "Rebuild_partial_programs": self.rebuild_partial_programs,
            "Keys_admitted": self.keys_admitted,
            "Keys_reclaimed": self.keys_reclaimed,
            "Key_slots_live": self.key_slots_live,
            "Key_capacity_growths": self.key_capacity_growths,
            "Scan_programs": self.scan_programs,
            "Scan_rows": self.scan_rows,
            "Scan_cells": self.scan_cells,
            "Scan_depth": self.scan_depth,
            "Scan_keys": self.scan_keys,
            "Scan_batch_admits": self.scan_batch_admits,
            "Join_probe_rows_a": self.join_probe_rows[0],
            "Join_probe_rows_b": self.join_probe_rows[1],
            "Join_archived_rows_a": self.join_archived_rows[0],
            "Join_archived_rows_b": self.join_archived_rows[1],
            "Join_pairs": self.join_pairs,
            "Join_output_batches": self.join_output_batches,
            "Join_purged_rows": self.join_purged_rows,
            "Join_archive_rows": self.join_archive_rows,
            "Join_scanned_rows": self.join_scanned_rows,
            "Join_archive_growths": self.join_archive_growths,
            "Join_late_probes": self.join_late_probes,
            "Join_batches_held": self.join_batches_held,
            "Join_archive_capacity_rows": self.join_archive_capacity_rows,
            "Join_probed_rows": self.join_probed_rows,
            "Split_whole_batches": self.split_whole_batches,
            "Split_gathered_batches": self.split_gathered_batches,
            "Fused_ops": self.fused_ops,
            "Staging_pool_hits": self.staging_pool_hits,
            "Staging_pool_misses": self.staging_pool_misses,
            "Dispatch_readback_stalls": self.dispatch_stalls,
            "Dispatch_queue_depth_max": self.dispatch_depth_max,
            "Dispatch_readbacks": self.dispatch_readbacks,
            "Dispatch_readbacks_deferred": self.dispatch_readbacks_deferred,
            # megabatch scan loop (0s with WF_MEGABATCH off or on
            # non-fused replicas; Programs_per_batch == 1.0 is the
            # un-amortized fused baseline, < 1.0 means the scan loop is
            # retiring multiple batches per dispatch)
            "Megabatch_loops": self.megabatch_loops,
            "Megabatch_batches_per_loop_avg": round(
                self.megabatch_batches / self.megabatch_loops, 2)
                if self.megabatch_loops else 0.0,
            "Megabatch_max": self.megabatch_max,
            # columnar ingest plane (0s on row-path-only sources)
            "Ingest_rows_per_block_avg": round(
                self.ingest_rows / ingest_blocks, 2)
                if ingest_blocks else 0.0,
            "Ingest_block_ns_per_row": round(
                self.stage_usec("ingest") * 1e3 / self.ingest_rows, 1)
                if self.ingest_rows else 0.0,
            "Programs_per_batch": round(
                self.device_programs_run / dispatch_batches, 3)
                if dispatch_batches else 0.0,
            "Checkpoint_snapshots": self.checkpoints_taken,
            "Checkpoint_snapshot_usec_total": round(
                self.checkpoint_snapshot_total_us, 1),
            "Checkpoint_last_snapshot_usec": round(
                self.checkpoint_last_snapshot_us, 1),
            "Checkpoint_bytes_total": self.checkpoint_bytes_total,
            "Checkpoint_align_stall_usec_total": round(
                self.checkpoint_align_total_us, 1),
            "Checkpoint_cut_pause_usec_total": round(
                self.checkpoint_cut_total_us, 1),
            "Checkpoint_cut_pause_usec": round(
                self.checkpoint_last_cut_us, 1),
            # exactly-once sink 2PC (0s unless with_exactly_once)
            "Sink_txn_precommits": self.txn_precommits,
            "Sink_txn_commits": self.txn_commits,
            "Sink_txn_aborts": self.txn_aborts,
            "Sink_txn_fenced_writes": self.txn_fenced_writes,
            # XLA compile attribution (flightrec.instrumented_jit wraps
            # the device plane's jit entry points; 0/"" on CPU replicas)
            "Compile_count": self.compile_count,
            "Compile_usec_total": round(self.compile_usec_total, 1),
            "Compile_last_usec": round(self.compile_last_us, 1),
            "Compile_last_signature": self.compile_last_signature,
            "Compile_cache_hits": self.compile_cache_hits,
            # per-record error policies / dead-letter quarantine
            # (0s on the default FAIL policy)
            "Dlq_records": self.dlq_records,
            "Dlq_skipped": self.dlq_skipped,
            "Dlq_retries": self.dlq_retries,
            # Kafka transient-error retry/backoff (kafka/connectors.py)
            "Kafka_reconnects": self.kafka_reconnects,
            # overload admission control (0s unless the governor sheds)
            "Shed_records": self.shed_records,
            "Shed_bytes": self.shed_bytes,
            # worker crash visibility (Worker records on its error path)
            "Worker_crashes": self.worker_crashes,
            "Worker_last_error": self.worker_last_error,
            "isTerminated": self.is_terminated,
        }
        # -- event-time health plane (always present: zero lag on a healthy
        # replica is itself the signal the doctor reads) --------------------
        wm_lag_us = self.poll_watermark()
        d["Watermark_current_ts"] = self.wm_current
        d["Watermark_advances"] = self.wm_advances
        d["Watermark_lag_usec"] = round(wm_lag_us, 1)
        d["Watermark_event_lag_usec"] = (
            max(0, self.wm_max_source_ts - self.wm_current)
            if self.wm_max_source_ts > 0 else 0)
        d["Watermark_idle"] = 1 if self._wm_idle else 0
        d["Watermark_stalls"] = self.wm_stalls
        d["Late_records"] = self.late_records
        d["Late_dropped"] = self.late_dropped
        d["Late_admitted"] = max(0, self.late_records - self.late_dropped)
        # -- mesh execution plane (mesh replicas only: a Mesh_* series on
        # every CPU replica would be noise — /metrics renders these only
        # where rep.get(field) exists) ---------------------------------------
        if self.mesh_devices > 0:
            d["Mesh_devices"] = self.mesh_devices
            d["Mesh_steps"] = self.mesh_steps
            d["Mesh_shuffle_bytes"] = self.mesh_shuffle_bytes
            d["Mesh_step_usec_total"] = round(self.mesh_step_total_us, 1)
            d["Mesh_shard_occupancy"] = self.mesh_shard_occupancy
            d["Mesh_shard_skew"] = self.mesh_shard_skew
            d["Mesh_degraded_devices"] = self.mesh_degraded
        # -- tiered keyed state (with_tiering replicas only) ----------------
        if self.tier_enabled:
            d["Tier_hot_keys"] = self.tier_hot_keys
            d["Tier_cold_keys"] = self.tier_cold_keys
            d["Tier_promotes"] = self.tier_promotes
            d["Tier_demotes"] = self.tier_demotes
            d["Tier_promote_usec_total"] = round(
                self.tier_promote_usec_total, 1)
            d["Tier_miss_rate"] = round(
                self.tier_misses / self.tier_lookups, 4) \
                if self.tier_lookups else 0.0
        # -- the stage table's cumulative times and counts (tracing.STAGES:
        # Stage_*, Dispatch_*_total_usec, Dispatch_batches, Device_launch_*,
        # Exit_fifo_*, Sink_*, Ingest_blocks, and the Queue_blocked_* /
        # Queue_puts_blocked waits of this replica's input channel) ---------
        d.update(self.stage_fields())
        d["Exit_fifo_depth_sum"] = self.exit_fifo_depth_sum
        # -- queue / backpressure plane (0s for sources and fused chains) ---
        ch = self.input_channel
        d["Queue_len"] = len(ch) if ch is not None else 0
        d["Queue_capacity"] = getattr(ch, "capacity", 0) if ch is not None \
            else 0
        d["Queue_depth_max"] = getattr(ch, "depth_max", 0) if ch is not None \
            else 0
        d["Queue_emit_fifo_depth_max"] = self.pipe_depth_max
        d["Worker_idle_ticks"] = self.worker_idle_ticks
        # the account of the worker thread that reports here (0 on the
        # other records of its chain, so a sum counts a thread once): its
        # CPU and wall clocks, its own waits as the stage helper summed
        # them on that thread (backpressured: blocked ``put``s; starved:
        # blocked ``get``s, idle ticks too; on the device: the wall less
        # the CPU of readback, d2h and launch), and what is left: a
        # thread off the CPU in no wait the table names (waiting its turn
        # on the interpreter lock, taken off the CPU by the host, or
        # blocked inside a user functor: a sleep, I/O)
        w = self.worker
        cpu_ns, wall_ns = w.thread_clocks() if w is not None else (0, 0)
        put_ns, get_ns, dev_ns = w.thread_waits() if w is not None \
            else (0, 0, 0)
        d["Thread_cpu_usec"] = round(cpu_ns / 1e3, 1)
        d["Thread_wall_usec"] = round(wall_ns / 1e3, 1)
        d["Worker_blocked_put_usec"] = round(put_ns / 1e3, 1)
        d["Worker_blocked_get_usec"] = round(get_ns / 1e3, 1)
        d["Worker_device_wait_usec"] = round(dev_ns / 1e3, 1)
        d["Worker_unaccounted_usec"] = round(max(
            0, wall_ns - cpu_ns - put_ns - get_ns - dev_ns) / 1e3, 1)
        # -- latency-tracing plane ------------------------------------------
        d["Latency_sample_every"] = self.sample_every
        for label, h in (("service", self.hist_service),
                         ("prep", self.hist_prep),
                         ("commit", self.hist_commit),
                         ("e2e", self.hist_e2e),
                         ("lateness", self.hist_lateness)):
            on = h is not None
            d[f"Latency_{label}_p50_usec"] = round(h.p50, 1) if on else 0.0
            d[f"Latency_{label}_p90_usec"] = round(h.p90, 1) if on else 0.0
            d[f"Latency_{label}_p99_usec"] = round(h.p99, 1) if on else 0.0
            d[f"Latency_{label}_max_usec"] = round(h.max_us, 1) if on else 0.0
            d[f"Latency_{label}_samples"] = h.count if on else 0
            if on and h.count:
                # sparse bucket transport: /metrics renders real histogram
                # series and per-operator merges from these
                d[f"Latency_{label}_hist"] = h.to_sparse()
        return d

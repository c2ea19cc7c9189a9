"""TPU builders — siblings of the reference's ``wf/builders_gpu.hpp``
(Filter_GPU/Map_GPU/Reduce_GPU builders with withName/withParallelism/
withKeyBy/withRebalancing), with ``with_schema`` replacing C++ type
deduction (or inferred from the first tuple at the staging boundary).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from ..basic import JoinMode, WindFlowError
from ..builders import BasicBuilder, _RoutableBuilder
from .ops_tpu import Filter_TPU, Map_TPU, Reduce_TPU
from .schema import TupleSchema


class _TPUBuilderMixin:
    def with_schema(self, schema) -> "_TPUBuilderMixin":
        if isinstance(schema, dict):
            schema = TupleSchema(schema)
        self._schema = schema
        return self


class _TieredStateMixin:
    """``with_tiering`` for the keyed-state operators: cap the device
    table at ``hot_capacity`` slots and spill the cold key tail to a
    host sqlite store (``windflow_tpu.state``). Key capacity becomes
    elastic — bounded by host disk, not device memory — while batches
    over the hot set run the unchanged dense path."""

    _tiering = None

    def with_tiering(self, policy: Optional[str] = None,
                     hot_capacity: int = 1024,
                     db_dir: Optional[str] = None):
        """Enable the hot/cold key tiers. ``policy`` picks the eviction
        order ("lru" | "lfu"; default ``WF_TIER_POLICY`` or "lru"),
        ``hot_capacity`` the device-resident slot count — it must exceed
        every batch's distinct-key working set (a single batch touching
        more keys than the hot tier holds raises ``KeyCapacityError``)."""
        from ..state.tiered import TierConfig
        self._tiering = TierConfig(policy=policy, hot_capacity=hot_capacity,
                                   db_dir=db_dir)
        return self

    def _tiering_guard(self, what: str) -> None:
        if self._tiering is not None and self._state_init is None:
            raise WindFlowError(f"{what}: with_tiering requires with_state "
                                "(tiers hold the keyed device state)")


class _KeyCapacityMixin:
    """``with_key_capacity`` for the keyed-state operators."""

    _key_capacity: Optional[int] = None

    def with_key_capacity(self, n: int):
        """Slots of the keyed state table (``with_state``): allocated once
        at that size where it starts at 64 and doubles, and for int keys
        the key directory's direct table laid over as many ids. More
        keys still grow it (``Key_capacity_growths``). A stateless
        operator refuses it at ``build()``."""
        self._key_capacity = int(n)
        return self


class _MeshBuilderMixin:
    """``with_mesh`` for the keyed device operators: shard the operator's
    keyed-state plane over a ``('key','data')`` device mesh
    (``windflow_tpu.mesh``) instead of a single chip."""

    _mesh_cfg: Optional[dict] = None

    def with_mesh(self, n_devices: Optional[int] = None,
                  mesh_shape: Optional[tuple] = None,
                  local_batch: Optional[int] = None,
                  key_capacity: int = 1024):
        """``build()`` returns the mesh-sharded operator (``Map_Mesh`` /
        ``Filter_Mesh`` / ``Reduce_Mesh``): ONE host replica drives every
        device, the KEYBY shuffle runs in-program as a bucket-by-owner +
        ``lax.all_to_all`` collective, and per-key state is block-sharded
        over the devices. ``mesh_shape=(ka, da)`` forces the
        factorization (results are invariant under reshape); default
        uses every visible device. ARBITRARY int64 keys densify to
        ``key_capacity`` slots via a host KeySlotMap (more distinct keys
        raise loudly). Mesh operators refuse ``rescale()`` — parallelism
        is the mesh shape; to change capacity, checkpoint and restore
        with a different ``with_mesh(mesh_shape=...)``."""
        self._mesh_cfg = {"n_devices": n_devices, "mesh_shape": mesh_shape,
                          "local_batch": local_batch,
                          "key_capacity": key_capacity}
        return self

    def _mesh_guard(self, what: str) -> None:
        if self._parallelism != 1:
            raise WindFlowError(
                f"{what}: with_mesh and with_parallelism are exclusive — "
                "the mesh IS the parallelism (one host replica drives "
                "every chip)")
        if self._output_batch_size:
            raise WindFlowError(
                f"{what}: with_output_batch_size does not apply to the "
                "mesh plane (batches pad to the mesh's global batch)")
        if self._key_extractor is None:
            raise WindFlowError(f"{what}: with_mesh requires with_key_by "
                                "(the mesh shards the KEYED plane)")
        if getattr(self, "_key_capacity", None) is not None:
            raise WindFlowError(
                f"{what}: with_key_capacity sizes the single-chip table; "
                "the mesh's is with_mesh(key_capacity=...)")


class Map_TPU_Builder(_RoutableBuilder, _TPUBuilderMixin, _MeshBuilderMixin,
                      _TieredStateMixin, _KeyCapacityMixin):
    _default_name = "map_tpu"

    def __init__(self, func: Callable) -> None:
        super().__init__(func)
        self._schema: Optional[TupleSchema] = None
        self._state_init: Any = None

    def with_state(self, initial_state: Any) -> "Map_TPU_Builder":
        """Per-key device state: switches the functor to
        ``func(row, state) -> (row, state)`` scanned in arrival order."""
        self._state_init = initial_state
        return self

    def build(self) -> Map_TPU:
        if self._state_init is not None and self._key_extractor is None:
            raise WindFlowError("Map_TPU_Builder: with_state requires "
                                "with_key_by")
        self._tiering_guard("Map_TPU_Builder")
        if self._mesh_cfg is not None:
            from ..mesh.ops_mesh import Map_Mesh
            self._mesh_guard("Map_TPU_Builder")
            return self._finish(Map_Mesh(
                self._func, self._state_init, self._key_extractor,
                self._name if self._name != self._default_name
                else "map_mesh", schema=self._schema,
                tiering=self._tiering, **self._mesh_cfg))
        return self._finish(Map_TPU(self._func, self._name, self._parallelism,
                                    self._routing, self._key_extractor,
                                    self._output_batch_size, self._schema,
                                    self._state_init, self._tiering,
                                    self._key_capacity))


class Filter_TPU_Builder(_RoutableBuilder, _TPUBuilderMixin,
                         _MeshBuilderMixin, _TieredStateMixin,
                         _KeyCapacityMixin):
    _default_name = "filter_tpu"

    def __init__(self, pred: Callable) -> None:
        super().__init__(pred)
        self._schema: Optional[TupleSchema] = None
        self._state_init: Any = None

    def with_state(self, initial_state: Any) -> "Filter_TPU_Builder":
        """Per-key device state: switches the predicate to
        ``pred(row, state) -> (keep, state)``."""
        self._state_init = initial_state
        return self

    def build(self) -> Filter_TPU:
        if self._state_init is not None and self._key_extractor is None:
            raise WindFlowError("Filter_TPU_Builder: with_state requires "
                                "with_key_by")
        self._tiering_guard("Filter_TPU_Builder")
        if self._mesh_cfg is not None:
            from ..mesh.ops_mesh import Filter_Mesh
            self._mesh_guard("Filter_TPU_Builder")
            return self._finish(Filter_Mesh(
                self._func, self._state_init, self._key_extractor,
                self._name if self._name != self._default_name
                else "filter_mesh", schema=self._schema,
                tiering=self._tiering, **self._mesh_cfg))
        return self._finish(Filter_TPU(self._func, self._name,
                                       self._parallelism, self._routing,
                                       self._key_extractor,
                                       self._output_batch_size, self._schema,
                                       self._state_init, self._tiering,
                                       self._key_capacity))


class Reduce_TPU_Builder(_RoutableBuilder, _TPUBuilderMixin,
                         _MeshBuilderMixin):
    _default_name = "reduce_tpu"

    def __init__(self, combine: Callable) -> None:
        super().__init__(combine)
        self._schema: Optional[TupleSchema] = None

    def build(self) -> Reduce_TPU:
        from ..basic import RoutingMode
        if self._routing is RoutingMode.BROADCAST:
            # the op derives its routing from the key extractor (keyed
            # shuffle or forward); silently ignoring withBroadcast would
            # mislead (the reference reduce has no broadcast form either)
            raise WindFlowError("Reduce_TPU_Builder: withBroadcast is not "
                                "supported (use withKeyBy or forward)")
        if self._mesh_cfg is not None:
            from ..mesh.ops_mesh import Reduce_Mesh
            self._mesh_guard("Reduce_TPU_Builder")
            return self._finish(Reduce_Mesh(
                self._func, self._key_extractor,
                self._name if self._name != self._default_name
                else "reduce_mesh", schema=self._schema, **self._mesh_cfg))
        # without withKeyBy this is the GLOBAL per-batch reduce
        return self._finish(Reduce_TPU(self._func, self._key_extractor,
                                       self._name, self._parallelism,
                                       self._output_batch_size, self._schema))


class Ffat_Windows_TPU_Builder(_RoutableBuilder, _TPUBuilderMixin):
    """Sibling of the reference ``Ffat_WindowsGPU_Builder``
    (``wf/builders_gpu.hpp:576`` adds withNumWinPerBatch)."""

    _default_name = "ffat_windows_tpu"

    def __init__(self, lift: Callable, combine: Callable) -> None:
        super().__init__(lift)
        self._combine = combine
        self._schema: Optional[TupleSchema] = None
        self._win_len = 0
        self._slide_len = 0
        self._win_type = None
        self._lateness = 0
        self._nwpb = None  # default: auto-sized from key capacity
        self._key_capacity = 16

    def with_key_capacity(self, n: int):
        """Expected distinct-key count per replica (pre-sizes the device
        forest; avoids growth recompiles on streams with many keys)."""
        self._key_capacity = n
        return self

    def with_cb_windows(self, win_len: int, slide_len: int):
        from ..basic import WinType
        self._win_type = WinType.CB
        self._win_len, self._slide_len = win_len, slide_len
        return self

    def with_tb_windows(self, win_usec: int, slide_usec: int):
        from ..basic import WinType
        self._win_type = WinType.TB
        self._win_len, self._slide_len = win_usec, slide_usec
        return self

    def with_lateness(self, lateness_usec: int):
        self._lateness = lateness_usec
        return self

    def with_num_win_per_batch(self, n: int):
        self._nwpb = n
        return self

    def with_mesh(self, n_devices: Optional[int] = None,
                  mesh_shape: Optional[tuple] = None,
                  local_batch: Optional[int] = None,
                  fire_rounds: int = 4, ring_panes: int = 0,
                  late_policy: str = "keep_open"):
        """Shard the FlatFAT forest over a ('key','data') device mesh:
        ``build()`` returns the multi-chip ``Ffat_Windows_Mesh`` operator
        (keyby via ``lax.all_to_all`` over ICI, on-device fire control)
        instead of the single-chip plane. ``mesh_shape=(ka, da)`` forces
        the factorization; default uses every visible device. TB windows
        only (CB needs a serialized per-key arrival counter — see
        PARITY.md); ARBITRARY int64 keys, densified to
        ``key_capacity`` slots by a host KeySlotMap (more distinct keys
        than the capacity raise). ``late_policy``: "keep_open" (default)
        drops a tuple only when every window containing it already fired
        (less lossy than the reference); "ref_fired" reproduces the
        reference's fired-window bound exactly (drops tuples inside the
        last fired window even when open windows still contain them)."""
        self._mesh_cfg = {"n_devices": n_devices, "mesh_shape": mesh_shape,
                          "local_batch": local_batch,
                          "fire_rounds": fire_rounds,
                          "ring_panes": ring_panes,
                          "late_policy": late_policy}
        return self

    def build(self):
        from .ffat_tpu import Ffat_Windows_TPU
        if self._win_type is None:
            raise WindFlowError("Ffat_Windows_TPU_Builder: call "
                                "with_cb_windows() or with_tb_windows()")
        if self._key_extractor is None:
            raise WindFlowError("Ffat_Windows_TPU_Builder: withKeyBy "
                                "is mandatory")
        if getattr(self, "_mesh_cfg", None) is not None:
            from ..mesh.ffat_mesh import Ffat_Windows_Mesh
            if self._parallelism != 1:
                raise WindFlowError(
                    "Ffat_Windows_TPU_Builder: with_mesh and "
                    "with_parallelism are exclusive — the mesh IS the "
                    "parallelism (one host replica drives every chip)")
            if self._nwpb is not None:
                raise WindFlowError(
                    "Ffat_Windows_TPU_Builder: with_num_win_per_batch does "
                    "not apply to the mesh plane; the per-step fire budget "
                    "is with_mesh(fire_rounds=...)")
            if self._output_batch_size:
                raise WindFlowError(
                    "Ffat_Windows_TPU_Builder: with_output_batch_size does "
                    "not apply to the mesh plane (windows emit as rows "
                    "through the exit edge)")
            return self._finish(Ffat_Windows_Mesh(
                self._func, self._combine, self._key_extractor,
                self._win_len, self._slide_len, self._win_type,
                self._lateness, self._name,
                key_capacity=self._key_capacity,
                schema=self._schema, **self._mesh_cfg))
        return self._finish(Ffat_Windows_TPU(
            self._func, self._combine, self._key_extractor, self._win_len,
            self._slide_len, self._win_type, self._lateness, self._nwpb,
            self._name, self._parallelism, self._output_batch_size,
            self._schema, self._key_capacity))


class Interval_Join_TPU_Builder(BasicBuilder):
    """Sibling of ``Interval_Join_Builder`` (``wf/builders.hpp:1480-1538``)
    for the device plane: the same options, the join function over
    columns, the key a field name."""

    _default_name = "interval_join_tpu"

    def __init__(self, join_func: Callable) -> None:
        super().__init__(join_func)
        self._key_extractor = None
        self._lower = self._upper = None
        self._mode = JoinMode.KP
        self._schemas = (None, None)
        self._capacity = (None, None)

    def with_key_by(self, key_field: str):
        self._key_extractor = key_field
        return self

    def with_archive_capacity(self, a_rows=None, b_rows=None):
        """The rows a replica's archive of input A and of input B is
        allocated for, once, at the input's first batch (rounded up to
        whole slots of its batches' width): the stream a deployment holds
        between its purge lines, so the ring never doubles (and
        recompiles its step) inside a run. Past it the ring still
        doubles, counted in ``Join_archive_growths``. None (the default)
        starts the ring at 64 slots."""
        self._capacity = (a_rows, b_rows)
        return self

    def with_boundaries(self, lower_usec: int, upper_usec: int):
        self._lower, self._upper = lower_usec, upper_usec
        return self

    def with_kp_mode(self):
        self._mode = JoinMode.KP
        return self

    def with_dp_mode(self):
        self._mode = JoinMode.DP
        return self

    def with_schemas(self, a, b):
        """Declare both inputs' schemas (else each is taken from the
        side's first batch): the archives are allocated and
        ``PipeGraph.with_prewarm`` compiles both directions of the step
        before the sources open."""
        self._schemas = tuple(TupleSchema(s) if isinstance(s, dict) else s
                              for s in (a, b))
        return self

    def build(self):
        from .join_tpu import Interval_Join_TPU
        if self._key_extractor is None:
            raise WindFlowError("Interval_Join_TPU_Builder: withKeyBy "
                                "mandatory")
        if self._lower is None:
            raise WindFlowError("Interval_Join_TPU_Builder: withBoundaries "
                                "mandatory")
        if self._mode is JoinMode.DP:
            raise WindFlowError(
                "Interval_Join_TPU_Builder: DP mode is not on the device "
                "plane (every replica would archive a share of every key "
                "and probe with every arrival, behind an ordering "
                "collector); use with_kp_mode(), or Interval_Join_Builder "
                "for DP")
        if self._output_batch_size:
            raise WindFlowError(
                "Interval_Join_TPU_Builder: with_output_batch_size does "
                "not apply (pairs leave in batches of the input's "
                "capacity)")
        return self._finish(Interval_Join_TPU(
            self._func, self._key_extractor, self._lower, self._upper,
            self._name, self._parallelism, self._schemas, self._capacity))

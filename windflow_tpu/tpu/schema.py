"""Tuple schemas: the bridge between row-Python payloads and columnar
device batches.

The reference runs arbitrary C++ structs through CUDA kernels; the TPU
plane instead requires a declared (or inferred) mapping tuple -> columns of
fixed dtypes, because XLA programs are compiled per shape/dtype. This is
the "functor surface" decision called out in SURVEY.md §7 step 3a: device
operators are JAX functions over a dict of arrays (struct-of-arrays), and
the schema handles row<->column conversion at the device boundary.

Numeric Python types map to TPU-friendly dtypes: int -> int32,
float -> float32, bool -> bool_. Timestamps stay host-side as int64 numpy
(microseconds can exceed int32; device code that needs event time rebases
to a batch-local int32 offset, see ffat_tpu).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..basic import WindFlowError

_DTYPE_MAP = {
    int: np.int32,
    float: np.float32,
    bool: np.bool_,
}


class TupleSchema:
    """Ordered field name -> numpy dtype, plus a row constructor."""

    def __init__(self, fields: Dict[str, Any],
                 constructor: Optional[Callable] = None) -> None:
        self.fields: Dict[str, np.dtype] = {
            name: np.dtype(dt) for name, dt in fields.items()}
        self.constructor = constructor  # None => rows come back as dicts
        self._names = list(self.fields)
        self._native_ok: Optional[bool] = None  # encode path memo

    # ------------------------------------------------------------------
    @staticmethod
    def infer(payload: Any) -> "TupleSchema":
        """Infer from a sample tuple: dataclass instances or dicts with
        numeric scalar fields."""
        if dataclasses.is_dataclass(payload):
            flds = {}
            for f in dataclasses.fields(payload):
                v = getattr(payload, f.name)
                dt = _DTYPE_MAP.get(type(v))
                if dt is None:
                    dt = np.asarray(v).dtype
                flds[f.name] = dt
            return TupleSchema(flds, type(payload))
        if isinstance(payload, dict):
            flds = {}
            for k, v in payload.items():
                dt = _DTYPE_MAP.get(type(v))
                if dt is None:
                    dt = np.asarray(v).dtype
                flds[k] = dt
            return TupleSchema(flds, None)
        raise WindFlowError(
            f"cannot infer a device schema from {type(payload).__name__}; "
            "use dataclass/dict tuples or pass an explicit TupleSchema")

    # ------------------------------------------------------------------
    def to_columns(self, rows: Sequence[Tuple[Any, int]], capacity: int
                   ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        """Rows [(payload, ts)] -> padded columnar arrays + int64 ts."""
        cols = {name: np.zeros(capacity, dtype=dt)
                for name, dt in self.fields.items()}
        ts = np.zeros(capacity, dtype=np.int64)
        n = len(rows)
        if n and self._try_native(rows, cols, ts, n):
            return cols, ts
        # access mode follows the PAYLOADS (an explicit dict schema may be
        # used with dataclass tuples and vice versa)
        by_item = bool(rows) and isinstance(rows[0][0], dict)
        for i, (p, t) in enumerate(rows):
            ts[i] = t
            if by_item:
                for name in self._names:
                    cols[name][i] = p[name]
            else:
                for name in self._names:
                    cols[name][i] = getattr(p, name)
        return cols, ts

    def _try_native(self, rows, cols, ts, n) -> bool:
        """One C pass per column instead of a Python loop per row*field
        (windflow_tpu.native staging encoders). The first failure disables
        the path for this schema — retrying a doomed C pass per batch would
        double staging cost forever."""
        if self._native_ok is False:
            return False
        from ..native import ENCODABLE_DTYPES, encode_column, native_available
        if self._native_ok is None:
            if not native_available() or any(
                    str(dt) not in ENCODABLE_DTYPES
                    for dt in self.fields.values()):
                self._native_ok = False
                return False
        payloads = [r[0] for r in rows]
        try:
            for name in self._names:
                encode_column(payloads, name, cols[name][:n])
            ts[:n] = [r[1] for r in rows]
            self._native_ok = True
            return True
        except Exception:
            self._native_ok = False
            return False

    def from_columns(self, cols: Dict[str, np.ndarray], ts: np.ndarray,
                     n: int) -> List[Tuple[Any, int]]:
        """Columnar arrays -> rows [(payload, ts)] for the CPU plane.
        One ``tolist()`` C pass per column (2.4x the per-element ``.item``
        loop this replaces) — the D2H exit is a hot boundary."""
        names = self._names
        ctor = self.constructor
        ts_list = np.asarray(ts[:n], dtype=np.int64).tolist()
        if len(ts_list) != n:
            raise WindFlowError(f"from_columns: ts holds {len(ts_list)} "
                                f"rows, batch claims {n}")
        if not names:  # ts-only tuples: zip(*[]) would silently drop rows
            return [({}, t) for t in ts_list]
        lists = []
        for name in names:
            col = np.asarray(cols[name])[:n].tolist()
            if len(col) != n:  # zip would TRUNCATE silently
                raise WindFlowError(
                    f"from_columns: column {name!r} holds {len(col)} rows, "
                    f"batch claims {n}")
            lists.append(col)
        if ctor is not None:
            # kwargs: an explicit schema's field order may not match the
            # constructor's positional order
            return [(ctor(**dict(zip(names, vals))), t)
                    for vals, t in zip(zip(*lists), ts_list)]
        return [(dict(zip(names, vals)), t)
                for vals, t in zip(zip(*lists), ts_list)]

    def signature(self) -> Tuple:
        """Hashable key for the compile cache."""
        return tuple((name, str(dt)) for name, dt in self.fields.items())

    def __repr__(self) -> str:  # pragma: no cover
        return f"TupleSchema({self.fields})"


def broadcast_scalar_fields(vals: Any, n_rows: int) -> Any:
    """Broadcast per-tuple CONSTANT lift fields (e.g. a count seed
    ``{"n": 1.0}`` — per-row semantics in the reference's lift functor,
    ``wf/ffat_windows.hpp``) to the batch column shape. Shared by the
    single-chip FFAT step and the sharded-forest step so the lift-shape
    rule cannot diverge between them."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a: (jnp.broadcast_to(jnp.asarray(a), (n_rows,))
                   if jnp.ndim(a) == 0 else a), vals)

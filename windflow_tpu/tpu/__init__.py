"""Device plane: JAX/XLA siblings of the reference's CUDA operators.

Loaded lazily — ``import windflow_tpu`` never imports jax; importing
``windflow_tpu.tpu`` does.
"""

from .schema import TupleSchema
from .batch import BatchTPU
from .ops_tpu import Filter_TPU, Map_TPU, Reduce_TPU
from .ffat_tpu import Ffat_Windows_TPU
from .ffat_mesh import Ffat_Windows_Mesh
from .join_tpu import Interval_Join_TPU
from .builders_tpu import (Ffat_Windows_TPU_Builder, Filter_TPU_Builder,
                           Interval_Join_TPU_Builder, Map_TPU_Builder,
                           Reduce_TPU_Builder)

__all__ = [
    "TupleSchema", "BatchTPU",
    "Map_TPU", "Filter_TPU", "Reduce_TPU", "Ffat_Windows_TPU",
    "Ffat_Windows_Mesh", "Interval_Join_TPU",
    "Map_TPU_Builder", "Filter_TPU_Builder", "Reduce_TPU_Builder",
    "Ffat_Windows_TPU_Builder", "Interval_Join_TPU_Builder",
]

"""Mesh execution plane: the keyed-state plane sharded across a device
mesh (``jax.sharding.Mesh``), as a first-class subsystem.

What lives here (absorbing the old ``parallel/mesh.py`` bolt-on):

- ``core``: the collective primitives — ``('key','data')`` mesh
  construction, the in-program bucket-by-owner + ``lax.all_to_all``
  KEYBY shuffle, the sharded FlatFAT forest, the flat-owner grid-scan
  and keyed-reduce step builders, and the ``shard_map`` seam
  (``wf_shard_map``/``pvary_fn``);
- ``ffat_mesh``: ``Ffat_Windows_Mesh`` — keyed sliding windows sharded
  over the mesh, with sharded snapshot/restore;
- ``ops_mesh``: ``Map_Mesh`` / ``Filter_Mesh`` / ``Reduce_Mesh`` — the
  mesh-sharded stateful Map/Filter (grid-scan key tables block-sharded
  along the slot axis) and keyed Reduce, built via ``.with_mesh(...)``
  on the TPU builders.

Every mesh operator runs ONE host replica driving every device: the
topology edge into it stays single-destination (the host KEYBY emitter
degenerates to staging), and the per-key routing happens inside the
jitted step as a device collective. Parallelism is the mesh shape, not
the replica count — ``rescale()`` refuses mesh ops; to change capacity,
checkpoint and restore with a different ``with_mesh(mesh_shape=...)``
(sharded restore relayouts the key axis, arXiv:2112.01075's
redistribution decomposition at slot-row granularity).

Import layering: ``import windflow_tpu.mesh`` stays jax-free; device
code imports lazily inside functions like the rest of the device plane.
"""

from __future__ import annotations

import os
import sys

DEFAULT_VIRTUAL_DEVICES = 8


def ensure_virtual_devices(n: int = DEFAULT_VIRTUAL_DEVICES) -> bool:
    """Force a virtual ``n``-device CPU platform so mesh programs compile
    and run without TPU hardware — the XLA_FLAGS dance every mesh script
    and test used to hand-roll, in one place. Must run BEFORE jax
    initializes (env flags are read at backend creation); returns False
    when jax is already imported (the caller should then check
    ``len(jax.devices())`` and skip if short)."""
    if "jax" in sys.modules:
        return False
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    return True


from .core import (MESH_AXES, default_ring_panes, make_key_mesh,  # noqa: E402
                   make_mesh_table, make_sharded_state, mesh_shard_count,
                   pvary_fn, ring_pane_window_query, sharded_ffat_forest,
                   sharded_grid_scan, sharded_keyby_window_step,
                   sharded_keyed_reduce, wf_shard_map)
from .ffat_mesh import Ffat_Windows_Mesh  # noqa: E402
from .ops_mesh import Filter_Mesh, Map_Mesh, Reduce_Mesh  # noqa: E402

__all__ = [
    "ensure_virtual_devices", "DEFAULT_VIRTUAL_DEVICES",
    "MESH_AXES", "default_ring_panes", "make_key_mesh", "make_mesh_table",
    "make_sharded_state", "mesh_shard_count", "pvary_fn",
    "ring_pane_window_query", "sharded_ffat_forest", "sharded_grid_scan",
    "sharded_keyby_window_step", "sharded_keyed_reduce", "wf_shard_map",
    "Ffat_Windows_Mesh", "Map_Mesh", "Filter_Mesh", "Reduce_Mesh",
]

"""StateRepartitioner: split/merge checkpointed keyed state N -> M.

A committed checkpoint (windflow_tpu.checkpoint) already serializes every
replica's keyed state into per-replica blobs. Rescaling an operator from N
to M replicas is then exactly the redistribution problem of
"Memory-efficient array redistribution through portable collective
communication" (arXiv:2112.01075): re-bucket every key's state by the SAME
routing function the KEYBY emitters use, so that after restore each new
replica owns precisely the keys the emitters will route to it. Host-dict
states (Reduce key_state, WindowEngine key_map, FlatFAT forests, interval
-join archives) re-bucket per key; array-shaped device states (grid-scan
tables, FFAT TPU forests) re-bucket by slot-row gather along the key axis
(the DrJAX-style array-native keyed plane, arXiv:2403.07128 — state moves
as array transfers, never through a per-tuple serializer).

Routing consistency is the correctness contract: CPU KEYBY routes
``hash(key) % M``; the device plane routes via ``_dest_of_key`` (identity
for non-negative ints, FNV for str/bytes/composite — consistent with the
vectorized columnar paths). Both agree for int keys. Because ``hash`` of
str/bytes is randomized per process (PYTHONHASHSEED), CPU-plane
repartitioning of such keys is only valid within one process — which live
rescale always is; cross-process restore keeps the checkpoint's original
parallelism.

Non-repartitionable state fails LOUDLY (``WindFlowError``), never
silently dropped: global (unkeyed) reduce accumulators, BROADCAST-
distributed window operators (window ids are arithmetic over the replica
count), DP-mode interval joins (round-robin storage is bound to the old
replica set), sqlite-backed persistent operators (the DB image belongs to
one replica), and sources (replay cursors are not keyed state).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

from ..basic import OpType, RoutingMode, WindFlowError

# blob keys that need no repartitioning (merged, not split)
_BENIGN_KEYS = {"cur_wm", "shipped", "__emitter__", "__collector__"}


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------
def dest_fn_for(op, new_n: int) -> Callable[[Any], int]:
    """The destination function of the KEYBY emitters that feed ``op`` at
    parallelism ``new_n`` — repartitioned state MUST land where the
    emitters will route the keys."""
    if getattr(op, "is_tpu", False):
        from ..tpu.emitters_tpu import _dest_of_key
        return lambda k: _dest_of_key(k, new_n)
    return lambda k: hash(k) % new_n


# ---------------------------------------------------------------------------
# legality
# ---------------------------------------------------------------------------
def repartition_refusal(op) -> Optional[str]:
    """Why ``op``'s state cannot be repartitioned across a different
    replica count — None when rescaling is legal. Mirrors the chain
    legality diagnostics: the reason string is what the loud error
    carries."""
    if op.op_type == OpType.SOURCE:
        return ("source replicas are independent generators; their replay "
                "cursors are positions, not keyed state")
    if getattr(op, "is_mesh", False):
        return ("mesh-sharded operators parallelize over the device mesh, "
                "not the replica count — one host replica drives every "
                "chip; to change capacity, checkpoint and restore with a "
                "different with_mesh(mesh_shape=...) (sharded restore "
                "relayouts the key axis across the new factorization)")
    if getattr(op, "is_device_join", False):
        return ("Interval_Join_TPU keeps its archives as device arrays in "
                "arrival order, rows of every key interleaved; there is no "
                "per-key blob to re-bucket. Checkpoint and rebuild the "
                "graph at the new parallelism (the sources replay)")
    if getattr(op, "exactly_once", False):
        return ("exactly-once sinks own per-replica transaction logs "
                "(staged epoch segments / transactional producer ids); "
                "changing the replica count would orphan staged epochs "
                "and break the commit fencing")
    mod = type(op).__module__
    if ".persistent." in mod:
        return ("persistent (sqlite-backed) state is a per-replica DB "
                "image bound to one replica; keyed rows cannot be split "
                "out of it")
    if ".kafka" in mod:
        return ("Kafka connectors own partition assignments managed by "
                "the group protocol, not by WindFlow routing")
    if op.input_routing is RoutingMode.BROADCAST:
        return ("BROADCAST-distributed operators assign work by replica "
                "arithmetic (global window ids mod parallelism); their "
                "state is bound to the replica count, not to keys")
    if getattr(op, "join_mode", None) is not None:
        from ..basic import JoinMode
        if op.join_mode is JoinMode.DP:
            return ("DP-mode interval join stores a round-robin share of "
                    "a replica-count-dependent shared sequence")
    # keyed state without KEYBY routing = global accumulator (e.g. the
    # global Reduce_TPU): one stream-wide value has no keyed partition
    if getattr(op, "fusion_role", None) == "terminator" \
            and op.key_extractor is None:
        return ("global (unkeyed) reduce folds one stream-wide "
                "accumulator; there is no keyed partition to split")
    if op.op_type in (OpType.WIN, OpType.WIN_TPU) \
            and op.input_routing is not RoutingMode.KEYBY:
        return (f"{op.input_routing.name}-routed window operators "
                "distribute windows, not keys, across replicas")
    return None


# ---------------------------------------------------------------------------
# generic splitters
# ---------------------------------------------------------------------------
def _split_keyed_dict(olds: List[Dict[Any, Any]], new_n: int,
                      dest: Callable[[Any], int]) -> List[Dict[Any, Any]]:
    outs: List[Dict[Any, Any]] = [{} for _ in range(new_n)]
    for d in olds:
        for k, v in d.items():
            outs[dest(k)][k] = v
    return outs


def _merged_wm(states: List[dict]) -> int:
    return max((st.get("cur_wm", 0) for st in states), default=0)


def _split_scan(scans: List[Optional[dict]], new_n: int,
                dest: Callable[[Any], int], op_name: str) -> List[dict]:
    """Grid-scan keyed state tables: ``{"slot_of_key", "table_capacity",
    "table"}`` with table a pytree of host arrays whose axis 0 is the
    slot. Re-bucket keys, then gather each new replica's rows.

    Tiered blobs (a ``"tier"`` sub-dict per source) split across BOTH
    tiers: cold rows re-bucket by the same dest function, and a
    destination whose re-bucketed hot set overflows its (unchanged)
    ``hot_capacity`` spills its coldest keys — ranked by the
    checkpointed eviction order — into its own cold tier."""
    import numpy as np

    tiers = [st.get("tier") if st else None for st in scans]
    tiered = any(t is not None for t in tiers)
    proto_tier = next((t for t in tiers if t is not None), None)
    rank: Dict[Tuple[int, Any], int] = {}
    cold_per_dest: List[list] = [[] for _ in range(new_n)]
    if tiered:
        from ..state.tiered import cold_items_from_image
        for si, t in enumerate(tiers):
            if not t:
                continue
            for pos, k in enumerate(t.get("order", [])):
                rank[(si, k)] = pos  # higher = hotter (evicted later)
            for key, row in cold_items_from_image(t["cold_image"]):
                cold_per_dest[dest(key)].append((key, row))

    # (key, source index, source slot) in deterministic order
    per_dest: List[List[Tuple[Any, int, int]]] = [[] for _ in range(new_n)]
    for si, st in enumerate(scans):
        if not st:
            continue
        for key, slot in st["slot_of_key"].items():
            per_dest[dest(key)].append((key, si, slot))
    outs = []
    for j in range(new_n):
        sel = per_dest[j]
        spill: List[Tuple[Any, int, int]] = []
        if tiered:
            cap = int(proto_tier["hot_capacity"])
            # coldest-first; the kept tail is the destination's hot set
            sel = sorted(sel, key=lambda e: rank.get((e[1], e[0]), -1))
            n_spill = max(0, len(sel) - cap)
            spill, sel = sel[:n_spill], sel[n_spill:]
        else:
            cap = 64
            while cap < len(sel):
                cap *= 2
        slot_of_key = {key: i for i, (key, _, _) in enumerate(sel)}
        table = None
        src = next((st for st in scans if st and st.get("table") is not None),
                   None)
        if src is not None:
            import jax

            leaves, treedef = jax.tree_util.tree_flatten(src["table"])
            src_leaves = []
            for st in scans:
                src_leaves.append(
                    None if not st or st.get("table") is None
                    else jax.tree_util.tree_leaves(st["table"]))

            def _src_row(li, si, slot):
                if src_leaves[si] is None:
                    raise WindFlowError(
                        f"repartition: {op_name!r} replica {si} "
                        "registered keys but checkpointed no state "
                        "table")
                return np.asarray(src_leaves[si][li])[slot]

            new_leaves = []
            for li, proto in enumerate(leaves):
                proto = np.asarray(proto)
                out = np.zeros((cap,) + proto.shape[1:], dtype=proto.dtype)
                for i, (_, si, slot) in enumerate(sel):
                    out[i] = _src_row(li, si, slot)
                new_leaves.append(out)
            table = jax.tree_util.tree_unflatten(treedef, new_leaves)
            for key, si, slot in spill:  # overflow hot rows -> dest cold
                cold_per_dest[j].append((key, tuple(
                    _src_row(li, si, slot) for li in range(len(leaves)))))
        elif spill:
            raise WindFlowError(
                f"repartition: {op_name!r} holds tiered keys but "
                "checkpointed no state table to spill rows from")
        blob = {"slot_of_key": slot_of_key, "table_capacity": cap,
                "table": table}
        if tiered:
            from ..state.tiered import build_tier_blob, hot_table_digest
            blob["tier"] = build_tier_blob(
                proto_tier["policy"], cap,
                free_slots=range(cap - 1, len(sel) - 1, -1),
                order=[key for key, _, _ in sel],  # coldest-first kept
                cold_items=cold_per_dest[j],
                hot_digest=hot_table_digest(table))
        outs.append(blob)
    return outs


def _split_ffat_tpu(ffats: List[dict], new_n: int,
                    dest: Callable[[Any], int], op_name: str) -> List[dict]:
    """FFAT TPU forests: per-slot host arrays (K_cap,) + device trees
    (K_cap, 2F) re-bucket by slot-row gather. All contributing sources
    must share the ring depth F — tree node layout is F-dependent, and
    relayouting a segment-tree ring across depths is not implemented;
    the caller surfaces this as a loud error."""
    import numpy as np

    fs = {d["F"] for d in ffats if d["slot_of_key"]}
    if len(fs) > 1:
        raise WindFlowError(
            f"repartition: {op_name!r} replicas checkpointed FFAT forests "
            f"with different ring depths F={sorted(fs)}; merging rings of "
            "different depth is not supported — checkpoint at a quieter "
            "moment (F converges) or rescale before backlog builds up")
    per_dest: List[List[Tuple[Any, int, int]]] = [[] for _ in range(new_n)]
    for si, d in enumerate(ffats):
        for key, slot in d["slot_of_key"].items():
            per_dest[dest(key)].append((key, si, slot))
    proto = ffats[0]
    F = next(iter(fs), proto["F"])
    outs = []
    for j in range(new_n):
        sel = per_dest[j]
        k_cap = 4
        while k_cap < max(1, len(sel)):
            k_cap *= 2
        out = {
            "slot_of_key": {key: i for i, (key, _, _) in enumerate(sel)},
            "out_keys_by_slot": [key for key, _, _ in sel],
            "K_cap": k_cap, "F": F,
            "keys_all_int": all(d["keys_all_int"] for d in ffats),
            "key_dtype": proto["key_dtype"],
            "saw_new_key": True,  # force key-table refresh on first batch
            "leaf_frontier": max(d["leaf_frontier"] for d in ffats),
            "rebuild_dirty": True,  # level caches are stale by definition
            "ignored": sum(d["ignored"] for d in ffats) if j == 0 else 0,
            # the live keys are packed from slot 0: no free slot below
            # them; a key a source had forgotten stays forgotten, and a
            # new key's first window keeps the sources' highest floor
            "free_slots": [],
            "reclaimed_wid": max(d.get("reclaimed_wid", 0) for d in ffats),
        }
        for field in ("next_fire", "fired", "max_leaf", "count", "keys_np"):
            protos = np.asarray(proto[field])
            arr = np.zeros((k_cap,) + protos.shape[1:], dtype=protos.dtype)
            if field == "max_leaf":
                arr[:] = -1
            for i, (_, si, slot) in enumerate(sel):
                arr[i] = np.asarray(ffats[si][field])[slot]
            out[field] = arr
        # device trees: gather slot rows (axis 0); valid mask likewise
        src_tree = next((d for d in ffats
                         if d.get("trees") is not None and d["slot_of_key"]),
                        None)
        if src_tree is None or not sel:
            out["trees"] = None
            out["tvalid"] = None
        else:
            import jax

            leaves, treedef = jax.tree_util.tree_flatten(src_tree["trees"])
            tleaves = [None if d.get("trees") is None
                       else jax.tree_util.tree_leaves(d["trees"])
                       for d in ffats]
            new_leaves = []
            for li, pl in enumerate(leaves):
                pl = np.asarray(pl)
                buf = np.zeros((k_cap,) + pl.shape[1:], dtype=pl.dtype)
                for i, (_, si, slot) in enumerate(sel):
                    if tleaves[si] is None:
                        raise WindFlowError(
                            f"repartition: {op_name!r} replica {si} "
                            "registered keys but checkpointed no forest")
                    buf[i] = np.asarray(tleaves[si][li])[slot]
                new_leaves.append(buf)
            out["trees"] = jax.tree_util.tree_unflatten(treedef, new_leaves)
            tv = np.zeros((k_cap, 2 * F), dtype=bool)
            for i, (_, si, slot) in enumerate(sel):
                src_tv = ffats[si].get("tvalid")
                if src_tv is not None:
                    tv[i] = np.asarray(src_tv)[slot]
            out["tvalid"] = tv
        outs.append(out)
    return outs


# ---------------------------------------------------------------------------
# collector state
# ---------------------------------------------------------------------------
def _msg_sort_key(msg) -> Tuple[int, int]:
    from ..message import Batch
    if isinstance(msg, Batch):
        ts = msg.rows[0][1] if msg.rows else 0
    else:
        ts = msg.ts
    return (ts, msg.id)


def _filter_msg(msg, keep: Callable[[Any], bool]):
    """The sub-message of ``msg`` whose payloads satisfy ``keep`` (None
    when nothing survives). Batches split row-wise; id/wm/tag are
    preserved so (ts, id) merge order stays stable."""
    from ..message import Batch
    if isinstance(msg, Batch):
        rows = [(p, ts) for p, ts in msg.rows if keep(p)]
        if not rows:
            return None
        if len(rows) == len(msg.rows):
            return msg
        nb = Batch(rows, msg.wm, msg.is_punct, msg.stream_tag)
        nb.id = msg.id
        return nb
    return msg if keep(msg.payload) else None


def split_collector_states(colls: List[Optional[dict]], new_n: int,
                           key_fn: Callable[[Any], Any],
                           dest: Callable[[Any], int],
                           op_name: str) -> List[Optional[dict]]:
    """Split the RESCALED operator's own collector states (ordering /
    K-slack buffers, id sequencers hold PRE-BARRIER input the replica has
    not consumed yet — dropping them would lose data). Messages re-bucket
    by key; per-channel buffers keep their channel identity (the upstream
    producer set is unchanged)."""
    olds = [c for c in colls if c]
    if not olds:
        return [None] * new_n
    outs: List[Optional[dict]] = []
    n_ch = max(len(c.get("bufs", c.get("ch_wm", []))) for c in olds)
    for j in range(new_n):
        def keep(p, _j=j):
            return dest(key_fn(p)) == _j
        st: dict = {}
        if any("ch_wm" in c for c in olds):
            st["ch_wm"] = [
                min((c["ch_wm"][ch] for c in olds if "ch_wm" in c
                     and ch < len(c["ch_wm"])), default=0)
                for ch in range(n_ch)]
        if any("bufs" in c for c in olds):  # OrderingCollector
            bufs: List[list] = [[] for _ in range(n_ch)]
            for c in olds:
                for ch, buf in enumerate(c.get("bufs", [])):
                    for m in buf:
                        sub = _filter_msg(m, keep)
                        if sub is not None:
                            bufs[ch].append(sub)
            st["bufs"] = [sorted(b, key=_msg_sort_key) for b in bufs]
        if any("next" in c for c in olds):  # IDSequencerCollector
            st["next"] = {}
            st["pending"] = {}
            for c in olds:
                for k, v in c.get("next", {}).items():
                    if dest(k) == j:
                        st["next"][k] = max(v, st["next"].get(k, 0))
                for k, pend in c.get("pending", {}).items():
                    if dest(k) == j:
                        st["pending"].setdefault(k, {}).update(pend)
        if any("heap" in c and "K" in c for c in olds):  # KSlack
            heap = []
            for c in olds:
                for ts, seq, m in c.get("heap", []):
                    sub = _filter_msg(m, keep)
                    if sub is not None:
                        heap.append((ts, seq, sub))
            st["heap"] = sorted(heap)
            st["K"] = max(c.get("K", 0) for c in olds)
            st["max_ts"] = max(c.get("max_ts", 0) for c in olds)
            st["frontier"] = min(c.get("frontier", -1) for c in olds)
            st["seq"] = max(c.get("seq", 0) for c in olds)
        if any("heap" in c and "ch_wm" in c and "K" not in c
               for c in olds):
            raise WindFlowError(
                f"rescale: {op_name!r} sits behind a DP-join collector; "
                "DP interval joins are not repartitionable")
        outs.append(st or None)
    return outs


def remap_neighbor_collector(st: dict, old_inputs: List[Tuple[int, int]],
                             new_inputs: List[Tuple[int, int]],
                             changed_edges: set) -> dict:
    """Re-index a NEIGHBOR stage's collector state when the rescaled
    stage changed its input-channel layout (its parallelism is part of
    the channel numbering). Matched ``(edge, producer)`` entries keep
    their data; buffered messages from the rescaled edge's vanished
    channels merge (sorted) into that edge's first new channel; fresh
    channels seed conservatively (min watermark — late, never wrong)."""
    pos_new = {key: i for i, key in enumerate(new_inputs)}
    first_of_edge = {}
    for i, (e, _) in enumerate(new_inputs):
        first_of_edge.setdefault(e, i)
    out = dict(st)
    if "ch_wm" in st:
        per_edge_min: Dict[int, int] = {}
        for (e, pi), v in zip(old_inputs, st["ch_wm"]):
            per_edge_min[e] = min(per_edge_min.get(e, v), v)
        wm = []
        for i, (e, pi) in enumerate(new_inputs):
            try:
                oi = old_inputs.index((e, pi))
                keep = (e not in changed_edges)
            except ValueError:
                oi, keep = -1, False
            wm.append(st["ch_wm"][oi] if keep and oi < len(st["ch_wm"])
                      else per_edge_min.get(e, 0))
        out["ch_wm"] = wm
    if "bufs" in st:
        bufs: List[list] = [[] for _ in range(len(new_inputs))]
        spill: Dict[int, list] = {}
        for (e, pi), buf in zip(old_inputs, st["bufs"]):
            tgt = pos_new.get((e, pi)) if e not in changed_edges else None
            if tgt is not None:
                bufs[tgt].extend(buf)
            else:
                spill.setdefault(e, []).extend(buf)
        for e, msgs in spill.items():
            tgt = first_of_edge.get(e)
            if tgt is None:
                if msgs:
                    raise WindFlowError(
                        "rescale: buffered collector messages from a "
                        "removed edge have no destination channel")
                continue
            bufs[tgt] = sorted(bufs[tgt] + msgs, key=_msg_sort_key)
        out["bufs"] = bufs
    if "heap" in st and "ch_wm" in st and "K" not in st:  # DPJoin heap
        heap = []
        for ts, ch, mid, m in st["heap"]:
            e, pi = old_inputs[ch] if ch < len(old_inputs) else (0, 0)
            tgt = pos_new.get((e, pi))
            if tgt is None or e in changed_edges:
                tgt = first_of_edge.get(e, 0)
            heap.append((ts, tgt, mid, m))
        out["heap"] = sorted(heap)
    return out


# ---------------------------------------------------------------------------
# emitter state
# ---------------------------------------------------------------------------
def stretch_emitter_state(st: Optional[dict], new_len: int) -> dict:
    """Synthesize a routing-counter state for an emitter whose
    destination count changed: every per-destination id starts at the
    GLOBAL max of the old counters, so ids stay monotone per channel and
    (ts, id) ties order checkpoint-buffered messages before post-rescale
    ones."""
    st = st or {}
    if "inner" in st:  # SplittingEmitter: stretch every branch
        return {"inner": [stretch_emitter_state(s, new_len)
                          for s in st["inner"]]}
    mx = max(st.get("next_ids", []) or [0])
    return {"next_ids": [mx] * new_len,
            "emit_count": st.get("emit_count", 0)}


def merge_emitter_states(sts: List[Optional[dict]], new_len: int) -> dict:
    """Per-destination counters for the RESCALED op's new emitters: the
    max over every old replica and destination (safe for any old/new
    dest-count combination)."""
    mx = 0
    for st in sts:
        if not st:
            continue
        inner = st.get("inner")
        if inner:
            for s in inner:
                mx = max(mx, max(s.get("next_ids", []) or [0]))
        mx = max(mx, max(st.get("next_ids", []) or [0]))
    return {"next_ids": [mx] * new_len, "emit_count": 0}


# ---------------------------------------------------------------------------
# per-operator state split
# ---------------------------------------------------------------------------
def split_operator_states(op, olds: List[dict], new_n: int) -> List[dict]:
    """Split one operator's N replica state blobs into M. ``olds`` must
    not contain ``__emitter__`` / ``__collector__`` (handled by the
    caller, which knows the wiring)."""
    refusal = repartition_refusal(op)
    if refusal is not None:
        raise WindFlowError(
            f"rescale: operator {op.name!r} is not repartitionable — "
            f"{refusal}")
    dest = dest_fn_for(op, new_n)
    wm = _merged_wm(olds)
    news: List[dict] = [{"cur_wm": wm} for _ in range(new_n)]
    handled = set(_BENIGN_KEYS)

    if any("key_state" in st for st in olds):  # CPU Reduce
        for j, d in enumerate(_split_keyed_dict(
                [st.get("key_state", {}) for st in olds], new_n, dest)):
            news[j]["key_state"] = d
        handled.add("key_state")
    if any("engine" in st for st in olds):  # WindowEngine (SEQ role only)
        engines = [st.get("engine", {}) for st in olds]
        kms = _split_keyed_dict([e.get("key_map", {}) for e in engines],
                                new_n, dest)
        for j in range(new_n):
            news[j]["engine"] = {
                "key_map": kms[j],
                "ignored_tuples": (sum(e.get("ignored_tuples", 0)
                                       for e in engines) if j == 0 else 0),
                "cur_wm": max((e.get("cur_wm", 0) for e in engines),
                              default=0)}
        handled.add("engine")
    if any("keys" in st for st in olds):  # FlatFAT CPU / KP interval join
        for j, d in enumerate(_split_keyed_dict(
                [st.get("keys", {}) for st in olds], new_n, dest)):
            news[j]["keys"] = d
        if any("ignored" in st for st in olds):
            news[0]["ignored"] = sum(st.get("ignored", 0) for st in olds)
            for j in range(1, new_n):
                news[j]["ignored"] = 0
            handled.add("ignored")
        handled.add("keys")
    if any("scan" in st for st in olds):  # grid-scan stateful map/filter
        for j, d in enumerate(_split_scan([st.get("scan") for st in olds],
                                          new_n, dest, op.name)):
            news[j]["scan"] = d
        handled.add("scan")
    if any("ffat" in st for st in olds):  # FFAT TPU forest
        for j, d in enumerate(_split_ffat_tpu(
                [st.get("ffat", {}) for st in olds], new_n, dest, op.name)):
            news[j]["ffat"] = d
        handled.add("ffat")
    if any("__fused__" in st for st in olds):  # fused device chain
        sig = next(st["__fused__"] for st in olds if "__fused__" in st)
        subs = [st.get("fused_sub_states", []) for st in olds]
        n_sub = max((len(s) for s in subs), default=0)
        split_subs: List[List[Optional[dict]]] = [[] for _ in range(new_n)]
        for si in range(n_sub):
            col = [s[si] if si < len(s) else None for s in subs]
            if all(c is None for c in col):
                for j in range(new_n):
                    split_subs[j].append(None)
            else:
                for j, d in enumerate(_split_scan(col, new_n, dest,
                                                  op.name)):
                    split_subs[j].append(d)
        for j in range(new_n):
            news[j]["__fused__"] = sig
            news[j]["fused_sub_states"] = split_subs[j]
        handled.update(("__fused__", "fused_sub_states"))

    unknown = {k for st in olds for k in st} - handled
    if unknown:
        raise WindFlowError(
            f"rescale: operator {op.name!r} checkpointed state this "
            f"version cannot repartition: {sorted(unknown)} — refusing "
            "loudly rather than dropping it")
    return news

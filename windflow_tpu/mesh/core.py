"""Mesh execution plane, collective core: key-sharded streaming state
over a device mesh (promoted from ``parallel/mesh.py`` into the
``windflow_tpu.mesh`` subsystem).

The single-node reference has no distributed backend (SURVEY.md §5: FastFlow
shared-memory queues only). This module is the new surface: the keyby
shuffle — the core repartitioning primitive of the whole framework
(``wf/keyby_emitter*.hpp``) — expressed as XLA collectives over a
``jax.sharding.Mesh`` so keyed window state scales across chips:

- mesh axes ``('key', 'data')``: ingestion is data-parallel along ``data``
  (every chip stages its own micro-batches), keyed state is block-sharded
  along ``key`` (shard ``s`` owns keys ``[s*k_local, (s+1)*k_local)``, so
  global state row ``k`` is key ``k``);
- one jitted step per global batch, written with ``shard_map``:
  bucket-by-owner (local sort) -> ``lax.all_to_all`` along ``key`` (the
  ICI shuffle replacing the reference's lock-free queues) -> masked
  segment-sum into the local per-key pane accumulators -> ``psum`` along
  ``data`` to merge the data-parallel contributions -> global metrics via
  ``psum`` over both axes;
- collectives ride ICI: the all_to_all moves only tuple payloads, state
  never leaves its owner shard.

This is the dry-run surface validated on a virtual CPU mesh; the same
program runs unchanged on a real multi-chip TPU slice.
"""

from __future__ import annotations

import math
import threading

import numpy as np

from ..tpu.schema import broadcast_scalar_fields

# named scope of the in-program KEYBY shuffle in a device profile
SCOPE_ALL_TO_ALL = "keyby_all_to_all"

# -- device-health exclusion registry ---------------------------------------
# Device ids the supervision plane has marked lost (health probe,
# supervision/health.py). Every mesh built through make_key_mesh avoids
# them, so a supervised rebuild after device loss lands the sharded state
# on the surviving devices. Process-global on purpose: a lost chip is
# lost for every graph in the process.
_EXCLUDED_DEVICE_IDS: frozenset = frozenset()
_EXCLUDE_LOCK = threading.Lock()


def set_excluded_devices(device_ids) -> None:
    """Replace the excluded-device set (ids as in ``device.id``). The
    supervisor calls this from the health probe before every rebuild;
    an empty set restores full capacity."""
    global _EXCLUDED_DEVICE_IDS
    with _EXCLUDE_LOCK:
        _EXCLUDED_DEVICE_IDS = frozenset(int(d) for d in device_ids)


def excluded_device_ids() -> frozenset:
    return _EXCLUDED_DEVICE_IDS


def healthy_devices():
    """``jax.devices()`` minus the excluded set. Falls back to ALL
    devices when the exclusion set would leave nothing — a probe gone
    mad must degrade to the pre-probe behavior, not to a zero-device
    mesh."""
    import jax

    devs = jax.devices()
    excl = _EXCLUDED_DEVICE_IDS
    if not excl:
        return list(devs)
    alive = [d for d in devs if d.id not in excl]
    return alive if alive else list(devs)


def wf_shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with this repo's argument order — the one seam
    every mesh program builds through."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def pvary_fn(axes):
    """Cast to varying over ``axes`` (shard_map's varying-axis types)."""
    from jax import lax

    return lambda a: lax.pcast(a, axes, to="varying")


def default_ring_panes(win_panes: int, slide_panes: int,
                       fire_rounds: int) -> int:
    """Default leaf-ring size: the smallest power of two holding the
    window PLUS the worst-case unfired backlog one step can leave
    (fire_rounds windows of slide panes each) — the single definition
    shared by the forest and the topology operator, so an all-defaults
    config always satisfies the forest's validation."""
    return 1 << max(3, math.ceil(
        math.log2(win_panes + max(fire_rounds * slide_panes, 16))))


def make_key_mesh(n_devices: int, shape=None):
    """Largest 2D ('key', 'data') mesh for n devices (data axis >= 1).
    ``shape=(ka, da)`` forces an explicit factorization (result invariance
    under mesh reshape is a correctness property — tests exercise 8x1 /
    4x2 / 2x4 over the same stream). Asking for more devices than exist
    raises; only health exclusions (degraded recovery) shrink a mesh
    below what was asked."""
    import jax
    from jax.sharding import Mesh

    alive = healthy_devices()
    if shape is not None:
        ka, da = shape
        if ka * da > len(jax.devices()):
            raise ValueError(f"mesh shape {shape} needs {ka * da} devices, "
                             f"have {len(jax.devices())}")
        if ka * da > len(alive):
            # the forced factorization no longer fits the surviving
            # devices (health exclusions): degrade to the auto path over
            # what is healthy rather than refusing to recover
            return make_key_mesh(len(alive))
        arr = np.array(alive[:ka * da]).reshape(ka, da)
        return Mesh(arr, ("key", "data"))
    n_devices = max(1, int(n_devices))
    if n_devices > len(alive):
        if not _EXCLUDED_DEVICE_IDS:
            raise ValueError(f"mesh needs {n_devices} devices, "
                             f"have {len(alive)}")
        # degraded recovery: rebuild over the survivors
        n_devices = len(alive)
    devs = alive[:n_devices]
    ka = n_devices
    da = 1
    # prefer a 2D mesh when the device count allows it
    for cand in (2, 4):
        if n_devices % cand == 0 and n_devices // cand >= 2:
            da = cand
            ka = n_devices // cand
            break
    arr = np.array(devs).reshape(ka, da)
    return Mesh(arr, ("key", "data"))


def make_sharded_state(mesh, n_keys: int, n_panes: int):
    """Per-key pane accumulators sharded along the 'key' axis (replicated
    along 'data'); zeros-initialized."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    ka = mesh.shape["key"]
    n_keys_padded = math.ceil(n_keys / ka) * ka
    state = jnp.zeros((n_keys_padded, n_panes), jnp.float32)
    counts = jnp.zeros((n_keys_padded, n_panes), jnp.int32)
    sharding = NamedSharding(mesh, P("key", None))
    return (jax.device_put(state, sharding),
            jax.device_put(counts, sharding))


def _route_to_owners(ka: int, k_local: int, C: int, keys, panes, vals):
    """The ICI keyby shuffle shared by the sharded steps: bucket local
    tuples by owner shard (stable sort + run positions, capacity-masked),
    ``lax.all_to_all`` along 'key', and recover (keys, panes, vals pytree,
    valid mask, local key index) on the owner. Runs inside shard_map."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    tmap = jax.tree_util.tree_map
    B = keys.shape[0]
    # key < 0 marks a PADDING lane (partial input batches): route it to
    # shard 0 — it arrives with key -1, fails the ``valid`` mask, and is
    # dropped. clip (not minimum) so the negative key cannot produce a
    # negative destination (negative scatter indices would WRAP, not drop)
    dest = jnp.clip(keys // k_local, 0, ka - 1).astype(jnp.int32)
    order = jnp.argsort(dest, stable=True)
    dsort, ksort, psort = dest[order], keys[order], panes[order]
    vsort = tmap(lambda a: a[order], vals)
    # position of each tuple within its destination run
    start_of_dest = jnp.searchsorted(dsort, jnp.arange(ka))
    within = jnp.arange(B) - start_of_dest[dsort]
    ok = within < C
    flat = dsort * C + jnp.minimum(within, C - 1)

    def bucketize(col, fill):
        buf = jnp.full((ka * C,), fill, dtype=col.dtype)
        return buf.at[flat].set(
            jnp.where(ok, col, fill), mode="drop").reshape(ka, C)

    # the ICI shuffle: block i of every chip goes to key-shard i
    a2a = lambda b: lax.all_to_all(b, "key", 0, 0, tiled=True).reshape(-1)
    with jax.named_scope(SCOPE_ALL_TO_ALL):
        rk = a2a(bucketize(ksort, -1))
        rp = a2a(bucketize(psort, 0))
        rv = tmap(lambda a: a2a(bucketize(a, np.zeros((), a.dtype)[()])),
                  vsort)
    valid = rk >= 0
    shard = lax.axis_index("key")
    local_key = jnp.where(valid, rk - shard * k_local, 0).astype(jnp.int32)
    return rk, rp, rv, valid, local_key


def sharded_keyby_window_step(mesh, n_keys: int, n_panes: int,
                              local_batch: int):
    """Builds the jitted global step: (state, counts, keys, values, panes)
    -> (state', counts', global_tuple_count).

    ``keys``/``values``/``panes`` are global arrays of shape
    (ka*da*local_batch,) sharded over both mesh axes; the step re-shards
    tuples to their key-owner chips with all_to_all and folds them into the
    owner's pane accumulators.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ka = mesh.shape["key"]
    da = mesh.shape["data"]
    n_keys_padded = math.ceil(n_keys / ka) * ka
    k_local = n_keys_padded // ka
    # per-destination bucket capacity: worst case all local tuples go to one
    # owner; pad to local_batch (masked)
    C = local_batch

    def local_step(state, counts, keys, values, panes):
        # state/counts: (k_local, n_panes); keys/values/panes: (B,)
        # BLOCK key ownership: shard s owns global keys
        # [s*k_local, (s+1)*k_local), so returned global row k IS key k
        rk, rp, rv, valid, local_key = _route_to_owners(
            ka, k_local, C, keys, panes, {"v": values})
        rv = rv["v"]
        pane_idx = jnp.where(valid, rp % n_panes, 0).astype(jnp.int32)
        flat_idx = jnp.where(valid, local_key * n_panes + pane_idx,
                             k_local * n_panes)
        # accumulate the DELTA only, then merge deltas across the
        # data-parallel replicas — psum of state+delta would multiply the
        # pre-existing accumulators by the data-axis size every step
        delta = jnp.zeros(k_local * n_panes, state.dtype).at[flat_idx].add(
            jnp.where(valid, rv, 0), mode="drop").reshape(k_local, n_panes)
        dcount = jnp.zeros(k_local * n_panes, counts.dtype).at[flat_idx].add(
            jnp.where(valid, 1, 0), mode="drop").reshape(k_local, n_panes)
        state = state + lax.psum(delta, "data")
        counts = counts + lax.psum(dcount, "data")
        n_tuples = lax.psum(jnp.sum(valid), ("key", "data"))
        return state, counts, n_tuples

    stepped = wf_shard_map(
        local_step, mesh=mesh,
        in_specs=(P("key", None), P("key", None),
                  P(("key", "data")), P(("key", "data")), P(("key", "data"))),
        out_specs=(P("key", None), P("key", None), P()),
    )
    return jax.jit(stepped), n_keys_padded, ka * da * local_batch


def sharded_ffat_forest(mesh, lift, combine, n_keys: int, win_panes: int,
                        slide_panes: int, local_batch: int,
                        fire_rounds: int = 2, ring_panes: int = 0,
                        late_policy: str = "keep_open"):
    """The FLAGSHIP operator sharded over the mesh: a FlatFAT forest whose
    key axis is block-sharded along ``'key'`` (shard s owns keys
    [s*k_local, (s+1)*k_local)), with ingestion data-parallel along
    ``'data'``.

    Multi-chip redesign of ``tpu/ffat_tpu.py`` (single-chip keeps its
    host-metadata control plane; here the per-key control state —
    next_fire/max_leaf — lives ON DEVICE in the shard that owns the key,
    so firing needs no host round-trip and no cross-chip metadata):

      bucket-by-owner -> ``lax.all_to_all`` along 'key' (tuple payloads
      ride ICI; forest state never moves) -> per-shard segmented scan +
      leaf scatter-combine -> per-shard level rebuild -> ``fire_rounds``
      device-side fire rounds (every owned key fires its next window when
      the frontier passed it; queries are the same <=2 log F ring walks,
      vmapped over the shard's keys) -> per-round leaf eviction.

    Returns ``(init_fn, step_fn, meta)``:
    - ``init_fn(sample_vals) -> state`` — 5-tuple (trees, tvalid,
      next_fire, max_leaf, fired), properly sharded; ``sample_vals`` is a
      pytree of (1,)-arrays carrying the RAW tuple column dtypes
      (pre-lift);
    - ``step_fn(*state, keys, values, panes, frontier)`` (state is
      SPLATTED) -> flat 10-tuple ``(trees, tvalid, next_fire, max_leaf,
      fired, results, res_valid, res_wid, n_tuples, n_late)``; results
      have shape (K_pad, fire_rounds) per lift field — window aggregates
      for each owned key, up to ``fire_rounds`` windows per step;
      ``n_late`` counts tuples dropped by the per-key lateness rule —
      under ``late_policy="keep_open"`` (default) a pane is late iff
      EVERY window containing it has fired (pane < next_fire[key]); under
      ``late_policy="ref_fired"`` it is the reference's exact bound
      (``wf/window_replica.hpp:257-258``): late iff it falls anywhere
      inside the key's last FIRED window (pane < next_fire + win - slide
      once a window fired), i.e. the reference also drops tuples that
      still belong to OPEN windows;
    - ``meta = (K_pad, k_local, global_batch)``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ka = mesh.shape["key"]
    da = mesh.shape["data"]
    if da & (da - 1):
        raise ValueError(f"sharded_ffat_forest: the 'data' axis must be a "
                         f"power of two for the delta-merge butterfly "
                         f"(got {da})")
    K_pad = math.ceil(n_keys / ka) * ka
    k_local = K_pad // ka
    F = ring_panes or default_ring_panes(win_panes, slide_panes,
                                         fire_rounds)
    if F & (F - 1) or F < win_panes + fire_rounds * slide_panes:
        raise ValueError(
            f"sharded_ffat_forest: ring_panes must be a power of two >= "
            f"win_panes + fire_rounds*slide_panes (got F={F}, "
            f"win={win_panes}, rounds={fire_rounds}, slide={slide_panes})")
    # int32 index-plane guard: the scatter uses flat indices up to
    # k_local*2F (lkey*2F + F + leaf); ring GROWTH doubles F through this
    # same construction path, so a large key_capacity times a grown ring
    # must refuse loudly here rather than wrap int32 silently
    if k_local * 2 * F > np.iinfo(np.int32).max:
        raise ValueError(
            f"sharded_ffat_forest: k_local*2*ring_panes = {k_local * 2 * F}"
            f" overflows the int32 index plane (k_local={k_local}, "
            f"ring_panes={F}); shard over more 'key' devices or lower "
            f"key_capacity/ring_panes")
    if late_policy not in ("keep_open", "ref_fired"):
        raise ValueError(
            f"sharded_ffat_forest: late_policy must be 'keep_open' or "
            f"'ref_fired' (got {late_policy!r})")
    # static late-bound offset: 0 keeps tuples that still belong to open
    # windows; win-slide reproduces the reference's fired-window bound
    # (gated below on next_fire > 0 == "at least one window fired/skipped",
    # matching the reference's last_lwid >= 0 gate). Dropping MORE tuples
    # is always ring-safe (fewer leaf touches); the offset must never go
    # NEGATIVE (hopping windows, slide > win: a bound below next_fire
    # would admit tuples whose leaf slot is already evicted). Clamping to
    # 0 loses nothing there — panes in [nf+win-slide, nf) fall in the
    # gaps BETWEEN hopping windows and contribute to no window at all,
    # so the two policies coincide for hopping windows.
    LATE_OFF = max(0, win_panes - slide_panes) \
        if late_policy == "ref_fired" else 0
    NNODES = 2 * F
    LOGQ = NNODES.bit_length()
    C = local_batch  # per-destination bucket capacity (masked)
    tmap = jax.tree_util.tree_map

    def comb_valid(va, a, vb, b):
        both = va & vb
        merged = combine(a, b)
        out = tmap(lambda m, x, y: jnp.where(both, m, jnp.where(va, x, y)),
                   merged, a, b)
        return va | vb, out

    def range_query(tree_row, vrow, lo, length):
        # loop-carry scalars must carry the shard_map varying axes
        pv = pvary_fn(("key", "data"))
        zero = tmap(lambda a: pv(jnp.zeros((), a.dtype)), tree_row)

        def body(_, st):
            l, r, lv, la, rv, ra = st
            take_l = ((l & 1) == 1) & (l < r)
            il = jnp.clip(l, 0, NNODES - 1)
            node_l = tmap(lambda a: a[il], tree_row)
            lv, la = comb_valid(lv, la, vrow[il] & take_l, node_l)
            l = jnp.where(take_l, l + 1, l)
            take_r = ((r & 1) == 1) & (l < r)
            ir = jnp.clip(r - 1, 0, NNODES - 1)
            node_r = tmap(lambda a: a[ir], tree_row)
            rv, ra = comb_valid(vrow[ir] & take_r, node_r, rv, ra)
            r = jnp.where(take_r, r - 1, r)
            return (l >> 1, r >> 1, lv, la, rv, ra)

        init = (lo + F, lo + length + F,
                pv(jnp.zeros((), bool)), zero, pv(jnp.zeros((), bool)), zero)
        st = lax.fori_loop(0, LOGQ, body, init)
        return comb_valid(st[2], st[3], st[4], st[5])

    def window_query(tree_row, vrow, start_phys, length):
        len1 = jnp.minimum(length, F - start_phys)
        v1, r1 = range_query(tree_row, vrow, start_phys, len1)
        v2, r2 = range_query(tree_row, vrow, jnp.zeros_like(start_phys),
                             length - len1)
        return comb_valid(v1, r1, v2, r2)

    def local_step(trees, tvalid, next_fire, max_leaf, fired,
                   keys, raw_vals, panes, frontier):
        # ---- fast-forward DRAINED keys past the frontier ----------------
        # A key with max_leaf < next_fire holds no live leaves (everything
        # below next_fire is evicted) and its pending windows are provably
        # empty — but while it sits idle the frontier keeps moving, and on
        # resume a new pane p >= next_fire + F would alias the ring slots
        # its stalled windows still read: they would fire valid=True with
        # the NEW tuple's value, and the per-round eviction would destroy
        # the new leaf before its real window fires. Jump next_fire to the
        # first slide-aligned start that is not yet fireable (skipping
        # only empty windows); ``fired`` tracks next_fire//slide (origin
        # numbering) and jumps with it. This makes the host's ring-headroom
        # floor a real invariant for idle-resume keys.
        first_unfireable = jnp.maximum(
            jnp.int32(0),
            ((frontier - win_panes) // slide_panes + 1) * slide_panes
        ).astype(jnp.int32)
        ff = (max_leaf < next_fire) & (next_fire < first_unfireable)
        next_fire = jnp.where(ff, first_unfireable, next_fire)
        fired = jnp.where(ff, first_unfireable // slide_panes, fired)

        # ---- route tuples to their key-owner shard (ICI all_to_all) ----
        recv_k, recv_p, recv_v, valid, lkey = _route_to_owners(
            ka, k_local, C, keys, panes, raw_vals)
        # per-key lateness rule. Default ("keep_open", LATE_OFF=0): a pane
        # is late iff EVERY window containing it has fired (p < next_fire)
        # — a deliberate LESS-LOSSY divergence from the reference, which
        # also drops tuples inside the last fired window even when they
        # still belong to open windows (``wf/window_replica.hpp:257-258``:
        # index < win + last_lwid*slide, gated on last_lwid >= 0).
        # "ref_fired" reproduces that bound exactly: next_fire > 0 means
        # at least one window fired (or was skipped provably-empty, which
        # the reference fires too), i.e. the last fired window ends at
        # next_fire + win - slide. Late panes must also not touch the
        # forest — their leaf slot may alias an evicted ring position.
        # Counted and returned so the host can account drops.
        nf_t = next_fire[lkey]
        late_bound = nf_t
        if LATE_OFF:
            late_bound = nf_t + jnp.where(nf_t > 0, jnp.int32(LATE_OFF), 0)
        late = valid & (recv_p < late_bound)
        valid = valid & ~late
        n_late = lax.psum(jnp.sum(late), ("key", "data"))

        # ---- segmented scan by (key, pane) + leaf scatter-combine ------
        vals = broadcast_scalar_fields(lift(recv_v), recv_k.shape[0])
        leaf = jnp.where(valid, recv_p % F, 0).astype(jnp.int32)
        big = jnp.int32(k_local * F)
        composite = jnp.where(valid, lkey * F + leaf, big)
        order2 = jnp.argsort(composite, stable=True)
        sc = composite[order2]
        same_prev = jnp.concatenate([jnp.zeros((1,), bool), sc[1:] == sc[:-1]])
        is_end = jnp.concatenate(
            [sc[1:] != sc[:-1], jnp.ones((1,), bool)]) & (sc < big)
        svals = tmap(lambda a: a[order2], vals)

        def seg_op(a, b):
            fa, sa = a
            fb, same_b = b
            merged = combine(fa, fb)
            out = tmap(lambda m, y: jnp.where(same_b, m, y), merged, fb)
            return out, sa & same_b

        scanned, _ = lax.associative_scan(seg_op, (svals, same_prev))
        flat_idx = (lkey[order2] * NNODES + F + leaf[order2])
        OOB = k_local * NNODES
        safe_idx = jnp.where(is_end, flat_idx, OOB)
        # scatter segment tails into a DELTA forest first: the state is
        # replicated along 'data' while each data replica received a
        # DISJOINT tuple subset, so deltas must merge across 'data'
        # (butterfly ppermute with the user combine — a generic-combine
        # all_reduce; cross-replica combine order is arbitrary, the same
        # guarantee DEFAULT mode gives multi-replica CPU ingestion)
        dleaf = tmap(lambda sv: jnp.zeros(
            (k_local * NNODES,), sv.dtype).at[safe_idx].set(
            sv, mode="drop"), scanned)
        dvalid = jnp.zeros((k_local * NNODES,), bool).at[safe_idx].set(
            is_end, mode="drop")
        shift = 1
        while shift < da:
            perm = [(i, i ^ shift) for i in range(da)]
            p_leaf = tmap(lambda a: lax.ppermute(a, "data", perm), dleaf)
            p_valid = lax.ppermute(dvalid, "data", perm)
            dvalid, dleaf = comb_valid(dvalid, dleaf, p_valid, p_leaf)
            shift <<= 1
        # combine the merged delta into the state leaves
        leaf_valid = tvalid.reshape(-1) & dvalid
        merged_all = combine(tmap(lambda t: t.reshape(-1), trees), dleaf)
        trees = tmap(lambda t, m, dl: jnp.where(
            dvalid, jnp.where(leaf_valid, m, dl), t.reshape(-1)
        ).reshape(t.shape), trees, merged_all, dleaf)
        tvalid = (tvalid.reshape(-1) | dvalid).reshape(tvalid.shape)
        # per-key max pane (control state stays on the owner shard),
        # merged across the data replicas
        max_leaf = max_leaf.at[lkey].max(
            jnp.where(valid, recv_p, -1).astype(max_leaf.dtype))
        max_leaf = lax.pmax(max_leaf, "data")

        # ---- level rebuild across the shard's forest -------------------
        # SKIPPED (lax.cond) when no owned key can fire this step: the
        # mesh rebuilds from leaves in-step, so internal nodes are only
        # ever read by this step's own fire rounds — a non-firing step
        # leaves them stale with no reader, and the next firing step's
        # cond takes the rebuild branch. The rebuild is O(keys × ring)
        # regardless of batch size: the dominant per-step term under
        # periodic (sparse) watermarks.
        def _rebuild(carry):
            trees, tvalid = carry
            lvl = F >> 1
            while lvl >= 1:
                lc = tmap(lambda t: t[:, 2 * lvl:4 * lvl:2], trees)
                rc = tmap(lambda t: t[:, 2 * lvl + 1:4 * lvl:2], trees)
                vlc = tvalid[:, 2 * lvl:4 * lvl:2]
                vrc = tvalid[:, 2 * lvl + 1:4 * lvl:2]
                merged = combine(lc, rc)
                node = tmap(lambda m, a, b: jnp.where(
                    vlc & vrc, m, jnp.where(vlc, a, b)), merged, lc, rc)
                trees = tmap(lambda t, nd: t.at[:, lvl:2 * lvl].set(nd),
                             trees, node)
                tvalid = tvalid.at[:, lvl:2 * lvl].set(vlc | vrc)
                lvl >>= 1
            return trees, tvalid

        any_elig = jnp.any((next_fire + win_panes <= frontier)
                           & (max_leaf >= next_fire))
        trees, tvalid = lax.cond(any_elig, _rebuild, lambda c: c,
                                 (trees, tvalid))

        # ---- device-side fire rounds -----------------------------------
        pv = pvary_fn(("key", "data"))
        res = tmap(lambda a: pv(jnp.zeros((k_local, fire_rounds), a.dtype)),
                   vals)
        res_valid = pv(jnp.zeros((k_local, fire_rounds), bool))
        res_wid = pv(jnp.zeros((k_local, fire_rounds), jnp.int32))

        def round_body(r, st):
            trees, tvalid, next_fire, max_leaf, fired, res, rvalid, rwid = st
            eligible = ((next_fire + win_panes <= frontier)
                        & (max_leaf >= next_fire))
            start = next_fire
            length = jnp.where(
                eligible,
                jnp.minimum(win_panes, max_leaf + 1 - start), 0
            ).astype(jnp.int32)
            qv, qr = jax.vmap(window_query)(
                trees, tvalid, (start % F).astype(jnp.int32), length)
            qv = qv & eligible
            res = tmap(lambda acc, q: acc.at[:, r].set(
                jnp.where(qv, q, acc[:, r])), res, qr)
            rvalid = rvalid.at[:, r].set(qv)
            rwid = rwid.at[:, r].set(
                jnp.where(eligible, fired, -1).astype(jnp.int32))
            # evict the panes sliding out of every fired key
            ev = start[:, None] + jnp.arange(slide_panes)[None, :]
            ev_ok = eligible[:, None] & (ev <= max_leaf[:, None])
            rows = jnp.broadcast_to(
                jnp.arange(k_local)[:, None], ev.shape)
            eflat = jnp.where(ev_ok, rows * NNODES + F + ev % F,
                              k_local * NNODES)
            tvalid = tvalid.reshape(-1).at[eflat.reshape(-1)].set(
                False, mode="drop").reshape(tvalid.shape)
            next_fire = jnp.where(eligible, next_fire + slide_panes,
                                  next_fire)
            fired = jnp.where(eligible, fired + 1, fired)
            return (trees, tvalid, next_fire, max_leaf, fired,
                    res, rvalid, rwid)

        (trees, tvalid, next_fire, max_leaf, fired, res, res_valid,
         res_wid) = lax.fori_loop(
            0, fire_rounds, round_body,
            (trees, tvalid, next_fire, max_leaf, fired, res, res_valid,
             res_wid))
        n_tuples = lax.psum(jnp.sum(valid), ("key", "data"))
        return (trees, tvalid, next_fire, max_leaf, fired,
                res, res_valid, res_wid, n_tuples, n_late)

    def init_fn(sample_vals):
        """sample_vals: pytree of (1,) arrays with the RAW tuple column
        dtypes (pre-lift); returns the sharded state pytree."""
        shapes = jax.eval_shape(
            lambda v: broadcast_scalar_fields(lift(v), 1), sample_vals)
        sh_keys = NamedSharding(mesh, P("key", None))
        sh_key1 = NamedSharding(mesh, P("key"))
        trees = {name: jax.device_put(jnp.zeros((K_pad, NNODES), s.dtype),
                                      sh_keys)
                 for name, s in shapes.items()}
        tvalid = jax.device_put(jnp.zeros((K_pad, NNODES), bool), sh_keys)
        next_fire = jax.device_put(jnp.zeros((K_pad,), jnp.int32), sh_key1)
        max_leaf = jax.device_put(jnp.full((K_pad,), -1, jnp.int32), sh_key1)
        fired = jax.device_put(jnp.zeros((K_pad,), jnp.int32), sh_key1)
        return trees, tvalid, next_fire, max_leaf, fired

    stepped = wf_shard_map(
        local_step, mesh=mesh,
        in_specs=(P("key", None), P("key", None), P("key"), P("key"),
                  P("key"),
                  P(("key", "data")), P(("key", "data")), P(("key", "data")),
                  P()),
        out_specs=(P("key", None), P("key", None), P("key"), P("key"),
                   P("key"),
                   P("key", None), P("key", None), P("key", None), P(),
                   P()),
        # the butterfly delta-merge makes state/results equal across the
        # 'data' axis, but the varying-axis type system cannot infer that
        # replication through a generic-combine reduction
        check_vma=False,
    )
    return init_fn, jax.jit(stepped), (K_pad, k_local, ka * da * local_batch)


def ring_pane_window_query(mesh, n_panes_global: int, win_panes: int,
                           slide_panes: int):
    """Sliding-window combines over a PANE-SHARDED timeline — the
    long-context analog: when one chip cannot hold a window's pane state
    (SURVEY.md §5: pane decomposition / window partitioning is how the
    reference scales window length), the pane axis itself is sharded over
    the mesh's 'key' axis; a shard owns the windows STARTING in its slice,
    which extend up to win-1 panes into the RIGHT neighbor, so each shard
    receives the head of its right neighbor via a RING exchange
    (``lax.ppermute`` over ICI), not a full all_gather.

    Builds a jitted fn: (pane_partials[P_global]) -> window_sums[W_global]
    where window w = sum of panes [w*slide, w*slide+win). Collectives move
    exactly the overlap, O(win) per link, independent of timeline length.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape["key"]
    if n_panes_global % n_shards:
        raise ValueError("n_panes_global must divide the key axis")
    p_local = n_panes_global // n_shards
    halo = win_panes - 1
    if halo > p_local:
        raise ValueError("window span exceeds one shard + halo; increase "
                         "panes per shard")
    n_windows = (n_panes_global - win_panes) // slide_panes + 1

    def local(panes):
        # panes: (p_local,) this shard's slice of the timeline. A shard
        # owns the windows STARTING in its slice; those extend up to
        # win-1 panes into the RIGHT neighbor, so the halo is the right
        # neighbor's head (ring ppermute: shard i sends its head to i-1).
        perm = [(i, (i - 1) % n_shards) for i in range(n_shards)]
        right_head = lax.ppermute(panes[:halo], "key", perm) \
            if halo > 0 else jnp.zeros((0,), panes.dtype)
        shard = lax.axis_index("key")
        ext = jnp.concatenate([panes, right_head])  # (p_local + halo,)
        start0_global = shard * p_local
        first_w = (start0_global + slide_panes - 1) // slide_panes
        max_w_here = p_local // slide_panes + 1
        w_ids = first_w + jnp.arange(max_w_here)
        starts_local = w_ids * slide_panes - start0_global
        valid = (w_ids < n_windows) & (starts_local < p_local)
        idx = jnp.clip(starts_local[:, None]
                       + jnp.arange(win_panes)[None, :],
                       0, p_local + halo - 1)
        sums = jnp.where(valid[:, None], ext[idx], 0).sum(axis=1)
        # each window is produced by exactly one shard; psum assembles the
        # dense global window vector
        out = jnp.zeros((n_windows,), panes.dtype)
        out = out.at[jnp.clip(w_ids, 0, n_windows - 1)].add(
            jnp.where(valid, sums, 0))
        return lax.psum(out, "key")

    stepped = wf_shard_map(local, mesh=mesh,
                           in_specs=(P("key"),), out_specs=P())
    return jax.jit(stepped), n_windows


# ---------------------------------------------------------------------------
# flat-owner routing: the keyed-plane shuffle for the sharded operators
# ---------------------------------------------------------------------------
# The FFAT plane block-shards keys along the 'key' axis only and merges the
# data-parallel contributions with an associative butterfly. A grid-scan
# state transition is SEQUENTIAL per key (func(row, state) is arbitrary),
# so no cross-replica merge exists: every tuple of a key must land on ONE
# device. The sharded Map/Filter/Reduce therefore block-shard the slot
# space over the FLATTENED ('key', 'data') device order (the same
# slot // k_local owner formula, ns = ka*da shards), and the all_to_all
# runs over the axis tuple — the mesh shape stays a pure layout choice,
# which is exactly what makes 8x1 / 4x2 / 2x4 results identical.

MESH_AXES = ("key", "data")


def _route_flat(ns: int, k_local: int, C: int, slots, aux, vals):
    """Bucket-by-owner + ``lax.all_to_all`` over the flattened mesh: the
    in-program KEYBY shuffle of the sharded operators. ``slots`` are
    dense key slots (< 0 = padding lane, routed to shard 0 and dropped by
    the ``valid`` mask); ``aux`` is one extra int column that rides the
    shuffle (global arrival position for scans, unused for reduce);
    ``vals`` a pytree of 1-D columns. Returns
    ``(recv_slots, recv_aux, recv_vals, valid, local_key, order, flat,
    ok)`` — the last three are the source-side routing map
    ``_route_back`` needs to return per-row results to arrival order."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    tmap = jax.tree_util.tree_map
    B = slots.shape[0]
    dest = jnp.clip(slots // k_local, 0, ns - 1).astype(jnp.int32)
    order = jnp.argsort(dest, stable=True)
    dsort, ssort, asort = dest[order], slots[order], aux[order]
    vsort = tmap(lambda a: a[order], vals)
    start_of_dest = jnp.searchsorted(dsort, jnp.arange(ns))
    within = jnp.arange(B) - start_of_dest[dsort]
    ok = within < C
    flat = dsort * C + jnp.minimum(within, C - 1)

    def bucketize(col, fill):
        buf = jnp.full((ns * C,), fill, dtype=col.dtype)
        return buf.at[flat].set(
            jnp.where(ok, col, fill), mode="drop").reshape(ns, C)

    a2a = lambda b: lax.all_to_all(b, MESH_AXES, 0, 0, tiled=True).reshape(-1)
    with jax.named_scope(SCOPE_ALL_TO_ALL):
        rs = a2a(bucketize(ssort, jnp.asarray(-1, ssort.dtype)))
        ra = a2a(bucketize(asort, jnp.zeros((), asort.dtype)))
        rv = tmap(lambda a: a2a(bucketize(a, jnp.zeros((), a.dtype))),
                  vsort)
    valid = rs >= 0
    shard = lax.axis_index(MESH_AXES)
    local_key = jnp.where(valid, rs - shard * k_local, 0).astype(jnp.int32)
    return rs, ra, rv, valid, local_key, order, flat, ok


def _route_back(ns: int, C: int, routed, order, flat, ok, fill=0):
    """Inverse shuffle: per-received-row results (the owner's outputs, in
    the recv layout ``j*C + c``) return to their source shard — tiled
    all_to_all with equal split/concat axes is an involution — and
    un-permute to the original arrival positions."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    with jax.named_scope(SCOPE_ALL_TO_ALL):
        ret = lax.all_to_all(routed.reshape(ns, C), MESH_AXES, 0, 0,
                             tiled=True).reshape(-1)
    picked = ret[flat]
    out = jnp.full((order.shape[0],), fill, dtype=routed.dtype)
    return out.at[order].set(
        jnp.where(ok, picked, jnp.asarray(fill, routed.dtype)))


def mesh_shard_count(mesh) -> int:
    """Shards of the flat-owner plane: every device of the mesh."""
    return mesh.shape["key"] * mesh.shape["data"]


def make_mesh_table(mesh, state_init, K_pad: int):
    """Per-key state table block-sharded over the flattened mesh: a
    pytree of (K_pad, ...) arrays filled with ``state_init`` leaves (the
    grid-scan table the single-chip ``_KeyedStateScan`` keeps on one
    chip, spread over every device's HBM)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(mesh, P(MESH_AXES))
    return jax.tree_util.tree_map(
        lambda v: jax.device_put(
            jnp.full((K_pad,) + jnp.asarray(v).shape, v,
                     dtype=jnp.asarray(v).dtype), sh), state_init)


def sharded_grid_scan(mesh, func, filter_mode: bool, key_capacity: int,
                      M: int, local_batch: int):
    """Mesh-sharded keyed grid scan: the device core of the sharded
    stateful Map/Filter. One jitted ``shard_map`` step per batch:

      bucket-by-owner -> all_to_all over the flat ('key','data') order
      (tuple payloads ride ICI; the state table never moves) -> per-key
      arrival ranking (sort by owner-local slot, stable in global
      position) -> (k_local x M) grid scan: ``lax.scan`` walks the
      per-key position axis while ``vmap`` covers the shard's slots ->
      outputs return to their source shard via the inverse all_to_all,
      so the emitted batch keeps arrival order.

    ``M`` is the max per-key tuple count of the batch (host-computed,
    power of two — the program signature, cached per M like the
    single-chip plane caches per (M, KB)). Returns ``(step, meta)``:
    ``step(table, slots, gpos, vals) -> (table2, out, n_tuples)`` where
    ``out`` is the per-row output columns (map) or keep mask (filter) in
    arrival order, and ``meta = (K_pad, k_local, GB)``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..tpu.ops_tpu import _grid_scan_core

    ns = mesh_shard_count(mesh)
    K_pad = math.ceil(key_capacity / ns) * ns
    k_local = K_pad // ns
    C = local_batch
    GB = ns * local_batch
    tmap = jax.tree_util.tree_map
    core = _grid_scan_core(func, filter_mode, M, k_local)

    def local_step(table, slots, gpos, vals):
        rs, rg, rv, valid, lkey, order, flat, ok = _route_flat(
            ns, k_local, C, slots, gpos, vals)
        B2 = rs.shape[0]
        # per-key arrival rank: routed recv layout (source shard asc,
        # source slot asc) IS global arrival order, so a stable sort by
        # owner-local slot preserves each key's relative order
        lk = jnp.where(valid, lkey, k_local)
        sort2 = jnp.argsort(lk, stable=True)
        sl = lk[sort2]
        start_of = jnp.searchsorted(sl, jnp.arange(k_local + 1))
        within_sorted = (jnp.arange(B2)
                         - start_of[jnp.clip(sl, 0, k_local)])
        within = jnp.zeros(B2, jnp.int32).at[sort2].set(
            within_sorted.astype(jnp.int32))
        grid_idx = jnp.where(valid,
                             lkey * M + jnp.minimum(within, M - 1),
                             k_local * M).astype(jnp.int32)
        touched = jnp.arange(k_local, dtype=jnp.int32)
        tmask = jnp.ones(k_local, bool)
        # the mesh plane tracks touched slots host-side (_ckpt_dirty);
        # the device bitmap is dropped and DCE'd out of the program
        out, table2, _dirty2 = core(rv, valid, grid_idx, touched, tmask,
                                    table, jnp.zeros((k_local,), bool))
        if filter_mode:
            keep = _route_back(ns, C, out.astype(jnp.int8), order, flat,
                               ok).astype(bool)
            ret = keep
        else:
            ret = tmap(lambda o: _route_back(ns, C, o, order, flat, ok),
                       out)
        n = lax.psum(jnp.sum(valid), MESH_AXES)
        return table2, ret, n

    stepped = wf_shard_map(
        local_step, mesh=mesh,
        in_specs=(P(MESH_AXES), P(MESH_AXES), P(MESH_AXES), P(MESH_AXES)),
        out_specs=(P(MESH_AXES), P(MESH_AXES), P()),
        # the flat-owner shuffle + route-back keep every array varying
        # over both axes; older jax rep-checking cannot type psum over an
        # axis tuple here, and the forest already runs unchecked
        check_vma=False,
    )
    return jax.jit(stepped), (K_pad, k_local, GB)


def sharded_keyed_reduce(mesh, combine, key_capacity: int,
                         local_batch: int):
    """Mesh-sharded keyed Reduce: per-batch ``reduce_by_key`` with the
    KEYBY shuffle lowered to the flat-owner all_to_all and the combine
    running as a segmented associative scan on each key's owner shard —
    the single-chip ``Reduce_TPU`` semantics (one output per distinct
    key per batch, reference ``reduce_gpu.hpp:239-272``) at mesh scale.
    Fields the combine does not return pass through unchanged.

    Returns ``(step, meta)``: ``step(slots, vals) -> (res, touched,
    n_tuples)`` where ``res`` maps each field to a (K_pad,) array of
    per-slot combine results and ``touched`` is the (K_pad,) bool mask
    of slots this batch touched; ``meta = (K_pad, k_local, GB)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    ns = mesh_shard_count(mesh)
    K_pad = math.ceil(key_capacity / ns) * ns
    k_local = K_pad // ns
    C = local_batch
    GB = ns * local_batch
    tmap = jax.tree_util.tree_map

    def local_step(slots, vals):
        rs, _, rv, valid, lkey, _, _, _ = _route_flat(
            ns, k_local, C, slots, slots, vals)
        B2 = rs.shape[0]
        lk = jnp.where(valid, lkey, k_local)
        order = jnp.argsort(lk, stable=True)  # arrival order within key
        sl = lk[order]
        sv = tmap(lambda a: a[order], rv)

        def seg_op(a, b):
            fa, sa = a
            fb, sb = b
            same = sa == sb
            merged = combine(fa, fb)
            out = {k: jnp.where(same, merged.get(k, fb[k]), fb[k])
                   for k in fb}
            return out, sb

        scanned, _ = lax.associative_scan(seg_op, (sv, sl))
        is_end = jnp.concatenate(
            [sl[1:] != sl[:-1], jnp.ones((1,), bool)]) & (sl < k_local)
        safe = jnp.where(is_end, sl, k_local)
        res = {f: jnp.zeros((k_local,), v.dtype).at[safe].set(
                   jnp.where(is_end, v, jnp.zeros((), v.dtype)),
                   mode="drop")
               for f, v in scanned.items()}
        touched = jnp.zeros((k_local,), bool).at[safe].set(
            is_end, mode="drop")
        n = lax.psum(jnp.sum(valid), MESH_AXES)
        return res, touched, n

    stepped = wf_shard_map(
        local_step, mesh=mesh,
        in_specs=(P(MESH_AXES), P(MESH_AXES)),
        out_specs=(P(MESH_AXES), P(MESH_AXES), P()),
        check_vma=False,
    )
    return jax.jit(stepped), (K_pad, k_local, GB)


def mesh_occupancy(n_slots: int, k_local: int, ns: int):
    """(max per-shard slot occupancy, skew) for ``n_slots`` dense
    first-seen slots block-owned ``slot // k_local`` over ``ns`` shards.
    Skew is max/mean — 1.0 when keys fill the shards evenly, ns when a
    single shard owns everything (dense slot assignment fills shard 0
    first, so early-stream skew is expected and decays as keys arrive)."""
    if n_slots <= 0 or ns <= 0 or k_local <= 0:
        return 0, 0.0
    occ_max = k_local if n_slots >= k_local else n_slots
    mean = n_slots / ns
    return occ_max, round(occ_max / mean, 3) if mean > 0 else 0.0

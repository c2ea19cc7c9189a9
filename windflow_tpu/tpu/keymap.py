"""Key -> dense slot mapping shared by keyed device operators.

Every keyed device operator (FFAT forest, stateful map/filter scans,
keyed reduce metadata) needs the same hot operation: map a batch of keys
to dense slot ids, creating slots for unseen keys. The generic path is a
dict; the hot path for small non-negative int keys is a direct numpy
lookup table — O(n) with no per-tuple Python and no sort (the reference
keeps per-batch key maps rebuilt with device sort/unique kernels,
``keyby_emitter_gpu.hpp:518-583``; here keys are host metadata)."""

from __future__ import annotations

from contextlib import nullcontext
from typing import Any, Callable, Dict, List, Optional

import numpy as np


def structured_unique(keys_arr: np.ndarray, n: int):
    """``(uniq, inverse)`` for a structured (composite-key) column, or
    None when a field numpy cannot sort (object dtype) — callers then
    walk the rows as ``.tolist()`` tuples. The SINGLE definition of the
    structured dedup used by every slot-mapping path: slot identity must
    never diverge between them for the same stream."""
    try:
        return np.unique(keys_arr[:n], return_inverse=True)
    except TypeError:
        return None


def distinct_batch_keys(keys, keys_arr: np.ndarray, n: int):
    """The batch's DISTINCT keys in the same canonical hashed form each
    ``slots_of`` path registers them (python ints for int columns, tuples
    for structured rows) — the tiered store plans promotions against
    these before the vectorized slot resolution runs, so every form
    mismatch would split one stream key into two slots."""
    if not n:
        return []
    if keys_arr.ndim == 1:
        if keys_arr.dtype.kind in "iu":
            return [int(k) for k in np.unique(keys_arr[:n])]
        if keys_arr.dtype.kind == "V" and keys_arr.dtype.names:
            uu = structured_unique(keys_arr, n)
            if uu is not None:
                return [u.item() for u in uu[0]]
            return list(dict.fromkeys(keys_arr[:n].tolist()))
    it = iter(keys)
    return list(dict.fromkeys(next(it) for _ in range(n)))


class KeySlotMap:
    """``slot_of_key`` (a dict) is the directory; the int paths look a
    batch up through an index built from it: a direct table over
    ``[base, base + len)`` where the LIVE keys span at most ``LUT_MAX``
    (``base`` 0 while they fit from 0: the fixed-key fast path; a base
    that moves on with a churning key space, refitted from the live keys
    when a batch leaves the table), else a sorted array of the live keys
    and ``np.searchsorted``. Either costs by the live keys, not by the
    largest id seen.

    Slots given back (``release``) join ``free`` and are handed out again
    before a new one: every slot below ``n_slots`` is live or free. A
    caller that never releases gets the insertion-order slots it always
    got. A batch's new int keys are admitted in one operation (counted
    in ``batch_admits``), with one ``on_new_many(keys, slots)`` call
    where the owner gave it; an owner with only ``on_new`` is called a
    key. Either may refuse (capacity) and then nothing is registered.
    ``admit_span()`` is opened around a batch's admission (the owner's
    stage span). ``span``, where given, is the ids the owner expects live
    at once (its key capacity): the direct table is never smaller than
    that and may pass ``LUT_MAX`` up to twice it, so a directory that
    never releases a key is not refitted from every live key as its ids
    climb."""

    LUT_MAX = 1 << 22  # 16 MiB int32 ceiling for the direct table
    DENSE_MAX = 1 << 16  # ids below this are looked up from 0 (base 0)

    def __init__(self, on_new: Optional[Callable[[Any, int], None]] = None,
                 on_new_many: Optional[Callable] = None,
                 admit_span: Optional[Callable] = None,
                 span: int = 0) -> None:
        self.slot_of_key: Dict[Any, int] = {}
        self._on_new = on_new  # called as on_new(key, slot) for each new key
        self._on_new_many = on_new_many  # (int keys array, slots array)
        # a context around a batch's admission (the owner's stage span)
        self._admit_span = admit_span or nullcontext
        self.free: List[int] = []   # slots given back, reused last-in first
        self.batch_admits = 0       # admissions made in one operation
        self._lut = None
        self._base = 0
        self._sorted = None         # (keys, slots) sorted by key, or None
        # the direct table's least size and its ceiling
        self._lut_min = 1 << (span - 1).bit_length() if span > 1 else 0
        self._lut_max = max(self.LUT_MAX, 2 * self._lut_min)

    def __len__(self) -> int:
        return len(self.slot_of_key)

    @property
    def n_slots(self) -> int:
        """The high-water mark: slots below it are live or free."""
        return len(self.slot_of_key) + len(self.free)

    def reset_index(self) -> None:
        """Drop the lookup index (the directory was replaced under it,
        e.g. by a restore); the next batch rebuilds it."""
        self._lut = None
        self._base = 0
        self._sorted = None

    def slot(self, key) -> int:
        s = self.slot_of_key.get(key)
        if s is None:
            s = self.free[-1] if self.free else len(self.slot_of_key)
            if self._on_new is not None:
                # on_new may refuse the key (capacity); it must run BEFORE
                # registration so a raise leaves no stale entry that a
                # caught-and-retried batch would silently reuse with an
                # out-of-range slot
                self._on_new(key, s)
            if self.free:
                self.free.pop()
            self.assign(key, s)
        return s

    def _admit(self, new: np.ndarray) -> np.ndarray:
        """Slots for the distinct unseen int keys ``new``: free ones
        first (last in, first out), then past the high-water mark, in one
        operation with one callback, or none where the owner gave none;
        key for key the slots ``slot`` would give. An owner with only a
        per-key ``on_new`` is called once a key."""
        m = len(new)
        with self._admit_span():
            if self._on_new is not None and self._on_new_many is None:
                return np.fromiter((self.slot(int(k)) for k in new),
                                   dtype=np.int64, count=m)
            n_free = min(m, len(self.free))
            reused = self.free[len(self.free) - n_free:]
            top = self.n_slots
            slots = np.concatenate([
                np.asarray(reused[::-1], dtype=np.int64),
                np.arange(top, top + m - n_free, dtype=np.int64)])
            if self._on_new_many is not None:
                # may refuse: nothing mutated yet
                self._on_new_many(new, slots)
            if n_free:
                del self.free[len(self.free) - n_free:]
            self.slot_of_key.update(zip(new.tolist(), slots.tolist()))
            self._sorted = None
            self.batch_admits += 1
            return slots

    def release(self, keys) -> None:
        """Forget ``keys`` (an int array or a list) and give their slots
        back; a key that returns is a new key."""
        ks = keys.tolist() if isinstance(keys, np.ndarray) else list(keys)
        self.free.extend(map(self.slot_of_key.pop, ks))
        if not (isinstance(keys, np.ndarray) and keys.dtype.kind in "iu"):
            self._lut = self._sorted = None
            return
        ka = keys.astype(np.int64)
        if self._lut is not None:
            rel = ka - self._base
            self._lut[rel[(rel >= 0) & (rel < len(self._lut))]] = -1
        if self._sorted is not None:
            sk, ss = self._sorted
            keep = np.ones(len(sk), bool)
            keep[np.searchsorted(sk, ka)] = False
            self._sorted = (sk[keep], ss[keep])

    # -- tiered-store slot reuse (windflow_tpu.state.tiered) ---------------
    # The tiered key store recycles slots of demoted keys, so slot ids are
    # assigned by the TIER plan, not by insertion order; these two keep the
    # dict and the int LUT consistent under out-of-order assignment.
    def assign(self, key, slot: int) -> None:
        """Register ``key`` at an explicit ``slot`` (tier promote)."""
        self.slot_of_key[key] = slot
        self._sorted = None
        lut = self._lut
        if lut is not None and isinstance(key, (int, np.integer)) \
                and 0 <= key - self._base < len(lut):
            lut[key - self._base] = slot

    def evict(self, key) -> None:
        """Forget ``key`` (tier demote); its slot is the caller's to
        recycle. The LUT entry must clear too — a stale hit would route
        the key to a slot now owned by someone else."""
        self.slot_of_key.pop(key, None)
        self._sorted = None
        lut = self._lut
        if lut is not None and isinstance(key, (int, np.integer)) \
                and 0 <= key - self._base < len(lut):
            lut[key - self._base] = -1

    def _live_int_keys(self):
        """``(keys, slots)`` int64 arrays of the directory's int keys."""
        d = self.slot_of_key
        try:
            return (np.fromiter(d.keys(), dtype=np.int64, count=len(d)),
                    np.fromiter(d.values(), dtype=np.int64, count=len(d)))
        except (TypeError, ValueError, OverflowError):
            pass    # a mixed directory: its int64 keys only
        ks = [k for k in d if isinstance(k, (int, np.integer))
              and -2**63 <= k < 2**63]
        return (np.asarray(ks, dtype=np.int64),
                np.asarray([d[k] for k in ks], dtype=np.int64))

    def _fit_lut(self, kmin: int, kmax: int) -> bool:
        """Lay the direct table over the live keys and ``[kmin, kmax]``;
        False where they span more than its ceiling (``LUT_MAX``, or
        twice the owner's ``span``)."""
        top = self._lut_max
        if self._sorted is not None and len(self._sorted[0]):
            # looked up by search so far: the live range without a walk
            kmin = min(kmin, int(self._sorted[0][0]))
            kmax = max(kmax, int(self._sorted[0][-1]))
        if kmax - kmin >= top:
            self._lut = None
            return False
        keys, slots = self._live_int_keys()
        if len(keys):
            kmin, kmax = min(kmin, int(keys.min())), max(kmax,
                                                         int(keys.max()))
        if kmax - kmin >= top:
            self._lut = None
            return False
        if 0 <= kmin and kmax < max(self.DENSE_MAX, self._lut_min):
            base, span = 0, kmax + 1     # small ids: the table from 0
        else:
            base, span = kmin, kmax - kmin + 1
        size = min(top, max(self._lut_min,
                            1 << max(10, (2 * span - 1).bit_length())))
        lut = np.full(size, -1, dtype=np.int32)
        lut[keys - base] = slots
        self._lut, self._base = lut, base
        return True

    def _slots_by_table(self, keys_arr: np.ndarray) -> np.ndarray:
        lut, base = self._lut, self._base
        rel = keys_arr if base == 0 else keys_arr - base
        slots = lut[rel]
        miss = slots < 0
        if miss.any():
            new = np.unique(keys_arr[miss])
            lut[new - base] = self._admit(new)
            slots = lut[rel]
        return slots

    def _slots_by_search(self, keys_arr: np.ndarray) -> np.ndarray:
        if self._sorted is None:
            keys, slots = self._live_int_keys()
            order = np.argsort(keys, kind="stable")
            self._sorted = (keys[order], slots[order])
        sk, ss = self._sorted
        ka = keys_arr.astype(np.int64)
        pos = np.minimum(np.searchsorted(sk, ka), max(len(sk) - 1, 0))
        hit = sk[pos] == ka if len(sk) else np.zeros(len(ka), bool)
        if hit.all():
            return ss[pos]
        new = np.unique(ka[~hit])
        got = self._admit(new)
        at = np.searchsorted(sk, new)
        sk, ss = np.insert(sk, at, new), np.insert(ss, at, got)
        self._sorted = (sk, ss)
        return ss[np.searchsorted(sk, ka)]

    def slots_of(self, keys, keys_arr: np.ndarray, n: int) -> np.ndarray:
        """Vectorized mapping of a whole batch; int result of length n
        (int32 on the LUT fast path — valid for indexing and promoted by
        numpy in mixed arithmetic; avoids a 16k-copy per batch). The int
        fast paths require a 1-D int array — tuple-of-int keys become a
        2-D array and must take the generic per-key path."""
        if keys_arr.ndim != 1:
            return np.fromiter((self.slot(k) for k in keys),
                               dtype=np.int64, count=n)
        if keys_arr.dtype.kind in "iu" and n:
            kmin = int(keys_arr.min())
            kmax = int(keys_arr.max())
            if kmax < 2**63:
                lut = self._lut
                if (lut is not None and kmin >= self._base
                        and kmax - self._base < len(lut)) \
                        or self._fit_lut(kmin, kmax):
                    return self._slots_by_table(keys_arr)
                return self._slots_by_search(keys_arr)
        if keys_arr.dtype.kind in "iu":
            uniq, inverse = np.unique(keys_arr, return_inverse=True)
            slot_map = np.fromiter((self.slot(int(k)) for k in uniq),
                                   dtype=np.int64, count=len(uniq))
            return slot_map[inverse]
        if keys_arr.dtype.kind == "V" and keys_arr.dtype.names:
            # structured (composite-key) columns: O(n log n) C sort +
            # one Python slot() per DISTINCT key. Registered as plain
            # tuples (np.void rows are unhashable and must equal the
            # tuples the per-row path extracts for the same key).
            uu = structured_unique(keys_arr, n)
            if uu is None:  # an object field: per-row over tuples
                return np.fromiter(
                    (self.slot(k) for k in keys_arr[:n].tolist()),
                    dtype=np.int64, count=n)
            uniq, inverse = uu
            slot_map = np.fromiter((self.slot(u.item()) for u in uniq),
                                   dtype=np.int64, count=len(uniq))
            return slot_map[inverse]
        return np.fromiter((self.slot(k) for k in keys),
                           dtype=np.int64, count=n)


def stable_group_argsort(vals: np.ndarray, n_groups: int) -> np.ndarray:
    """Stable argsort of small non-negative group ids. numpy's stable
    sort takes a RADIX path for <=16-bit ints only (~12x the comparison
    sort; int32/int64 both fall back to timsort, measured), so the cast
    pays off exactly when the ids fit int16."""
    if n_groups < 2**15 - 1:
        return np.argsort(vals.astype(np.int16), kind="stable")
    return np.argsort(vals, kind="stable")


def group_positions(slots: np.ndarray, n_groups: int):
    """(order, within): stable group-sort order of ``slots`` and each
    element's arrival rank WITHIN its group (the run-length grouping idiom
    shared by the grid scan and CB leaf numbering)."""
    n = len(slots)
    order = stable_group_argsort(slots, n_groups)
    ss = slots[order]
    seg_start = np.r_[True, ss[1:] != ss[:-1]] if n else np.zeros(0, bool)
    first_of = np.nonzero(seg_start)[0]
    grp = np.cumsum(seg_start) - 1
    within = np.empty(n, dtype=np.int64)
    within[order] = np.arange(n) - first_of[grp]
    return order, within

"""The row lifecycle's bookkeeping at the cut (PR 41, half 1):
`_ArenaBase.end_interval` runs over the rows ever handed out (`hw`),
not over the capacity, and asks the object-array name test only of the
rows old enough to die — and frees exactly the rows, in exactly the
order, that the pass over the whole capacity freed.

The reference is the parent's `end_interval`, kept verbatim below and
bound to a second arena of the same family that is driven through the
same random sequence of registrations, touches, cuts, evictions, growth
and checkpoint -> restore."""

import random
import types

import numpy as np
import pytest

from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.arena import IDLE_GC_INTERVALS
from veneur_tpu.samplers.metric_key import MetricKey, MetricScope


def _reference_end_interval(self) -> None:
    """`_ArenaBase.end_interval` as it stood before PR 41."""
    self.idle[self.touched] = 0
    self.idle[~self.touched] += 1
    dead = np.nonzero((self.idle >= IDLE_GC_INTERVALS)
                      & (self.name_col != None))[0]  # noqa: E711
    for row in dead:
        m = self.meta[row]
        self.meta[row] = None
        self.name_col[row] = None
        self.tags_col[row] = None
        self.name_hash_col[row] = 0
        if self.kind_col is not None:
            self.kind_col[row] = None
        self.scope_col[row] = 0
        self.idle[row] = 0
        del self.kdict[(m.key, m.scope)]
        self._fold_key_fingerprints(m.key, m.scope, int(row))
        self._free.append(int(row))
    self.touched[:] = False


_FAMILIES = {
    "counters": (arena_mod.CounterArena, "counter", {}),
    "gauges": (arena_mod.GaugeArena, "gauge", {}),
    "status": (arena_mod.StatusArena, "status", {}),
    "sets": (arena_mod.SetArena, "set", {"precision": 8}),
    "digests": (arena_mod.DigestArena, "timer", {}),
    "moments": (arena_mod.MomentsArena, "timer", {}),
    "compactors": (arena_mod.CompactorArena, "timer", {}),
}


def _make(family: str, capacity: int, reference: bool = False):
    cls, _, kw = _FAMILIES[family]
    ar = cls(capacity=capacity, **kw)
    if reference:
        ar.end_interval = types.MethodType(_reference_end_interval, ar)
    return ar


def _key(family: str, i: int):
    mtype = _FAMILIES[family][1]
    scope = MetricScope.GLOBAL_ONLY if i % 3 == 0 else MetricScope.MIXED
    tags = [f"k:{i}", "env:t"]
    return MetricKey(f"m.{i}", mtype, ",".join(sorted(tags))), scope, tags


def _assert_same(ar, ref, where: str) -> None:
    assert list(ar.kdict.items()) == list(ref.kdict.items()), where
    assert ar._free == ref._free, where
    assert ar.meta == ref.meta, where
    assert ar.name_col.tolist() == ref.name_col.tolist(), where
    assert ar.tags_col.tolist() == ref.tags_col.tolist(), where
    assert np.array_equal(ar.touched, ref.touched), where
    assert np.array_equal(ar.scope_col, ref.scope_col), where
    assert np.array_equal(ar.name_hash_col, ref.name_hash_col), where
    hw = ar.hw
    assert np.array_equal(ar.idle[:hw], ref.idle[:hw]), where
    assert (ar.key_checksum, ar.keyset_checksum) == (
        ref.key_checksum, ref.keyset_checksum), where
    assert ar.capacity == ref.capacity, where
    # what lies at or beyond hw has never held a key, in either arena
    assert not ref.touched[hw:].any(), where
    assert all(n is None for n in ref.name_col[hw:]), where
    assert all(row < hw for row in ar.kdict.values()), where
    assert not ar.idle[hw:].any(), where


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("family", list(_FAMILIES))
def test_end_interval_equals_the_pass_over_the_capacity(family, seed):
    rng = random.Random(f"{family}/{seed}")
    ar, ref = _make(family, 8), _make(family, 8, reference=True)
    pool = 48                      # past 8 and 16 and 32: three _grow()s
    freed = freed_ref_rows = 0
    reused = False
    # keys come and go in phases, so that the idle GC fires (a key
    # silent for IDLE_GC_INTERVALS cuts) and freed rows are handed out
    # again while others are still counting up
    for interval in range(40):
        phase = interval // 13
        active = [i for i in range(pool)
                  if (i + phase) % 3 != 0 and rng.random() < 0.8]
        if interval < 3:
            active = [i for i in active if i < 6 + 10 * interval]
        for i in active:
            key, scope, tags = _key(family, i)
            new, hw_before = (key, scope) not in ar.kdict, ar.hw
            rows = [a.row_for(key, scope, list(tags)) for a in (ar, ref)]
            assert rows[0] == rows[1]
            # a new key on a row under the high-water mark sits on a
            # row some other key was freed from
            reused |= new and rows[0] < hw_before
        _assert_same(ar, ref, f"interval {interval}: after row_for")
        if interval % 7 == 5 and ar.kdict:
            # the eager form of the GC (cardinality / cube eviction)
            dks = rng.sample(list(ar.kdict), min(3, len(ar.kdict)))
            dks.append((_key(family, 10_000)[0], MetricScope.MIXED))
            assert ar.release_keys(list(dks)) == ref.release_keys(
                list(dks)) == len(dks) - 1
            freed += len(dks) - 1
            _assert_same(ar, ref, f"interval {interval}: after release")
        n_ref = len(ref.kdict)
        got = ar.end_interval()
        assert ref.end_interval() is None
        assert got == n_ref - len(ref.kdict)
        freed += got
        freed_ref_rows += got
        assert ar.recycled == freed
        _assert_same(ar, ref, f"interval {interval}: after end_interval")
        if interval in (9, 27):
            restored = []
            for a, is_ref in ((ar, False), (ref, True)):
                a.sync()
                meta, arrays = a.checkpoint_state()
                fresh = _make(family, 8, reference=is_ref)
                fresh.restore_state(meta, arrays)
                restored.append(fresh)
            ar, ref = restored
            freed = 0              # a fresh arena has recycled nothing
            assert ar.hw == max(ar.kdict.values()) + 1
            _assert_same(ar, ref, f"interval {interval}: after restore")
    assert ar.capacity >= 32 and freed_ref_rows > 0 and reused


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_a_presized_arena_pays_for_the_rows_it_handed_out(family):
    ar = _make(family, 131_072)
    for i in range(3):
        key, scope, tags = _key(family, i)
        assert ar.row_for(key, scope, tags) == i
    assert ar.hw == 3
    for _ in range(IDLE_GC_INTERVALS):
        assert ar.end_interval() == 0
    # the pass never reached beyond hw: those rows did not count up
    assert ar.idle[:3].tolist() == [IDLE_GC_INTERVALS - 1] * 3
    assert not ar.idle[3:].any()
    key, scope, tags = _key(family, 1)
    ar.row_for(key, scope, tags)
    assert ar.end_interval() == 2 and ar.recycled == 2
    assert sorted(ar.kdict.values()) == [1] and ar.hw == 3
    # freed rows are handed out again first, and start at idle 0
    key, scope, tags = _key(family, 7)
    assert ar.row_for(key, scope, tags) == 2
    assert ar.idle[2] == 0 and ar.hw == 3

"""The row lifecycle's bookkeeping at the cut (PR 41, half 1):
`_ArenaBase.end_interval` runs over the rows ever handed out (`hw`),
not over the capacity, and asks the object-array name test only of the
rows old enough to die — and frees exactly the rows, in exactly the
order, that the pass over the whole capacity freed.

The reference is the parent's `end_interval`, kept verbatim below and
bound to a second arena of the same family that is driven through the
same random sequence of registrations, touches, cuts, evictions, growth
and checkpoint -> restore.

PR 45: a row's two key fingerprints are hashed once, at its birth, kept
in `fp_col` and read back by one batched free (`_recycle`).  The
reference's deaths (the idle GC and `release_keys`, both as they stood
before PR 45) still hash each dying key with the plain `fnv1a_64`, and
after every step both checksums are held to a from-scratch fold of
`kdict`."""

import random
import types

import numpy as np
import pytest

from veneur_tpu.core import arena as arena_mod
from veneur_tpu.core.arena import IDLE_GC_INTERVALS
from veneur_tpu.samplers import metric_key
from veneur_tpu.samplers.metric_key import (MetricKey, MetricScope,
                                            fnv1a_64, identity_string)


def _plain_fingerprints(key, scope, row) -> tuple[int, int]:
    """(keyset, key) fingerprints of one mapping as the parent hashed
    them: two whole passes of the plain reference."""
    base = identity_string(key, scope)
    return fnv1a_64(base), fnv1a_64(f"{base}\x00{row}")


def _scratch_fold(kdict) -> tuple[int, int]:
    """(key_checksum, keyset_checksum) folded from nothing."""
    key_ck = keyset_ck = 0
    for (key, scope), row in kdict.items():
        keys_fp, rows_fp = _plain_fingerprints(key, scope, row)
        keyset_ck ^= keys_fp
        key_ck ^= rows_fp
    return key_ck, keyset_ck


def _reference_fold(self, key, scope, row) -> None:
    """`_ArenaBase._fold_key_fingerprints` as it stood before PR 45."""
    keys_fp, rows_fp = _plain_fingerprints(key, scope, row)
    self.keyset_checksum ^= keys_fp
    self.key_checksum ^= rows_fp


def _reference_release_keys(self, dks: list) -> int:
    """`_ArenaBase.release_keys` as it stood before PR 45."""
    rows: list[int] = []
    for dk in dks:
        row = self.kdict.pop(dk, None)
        if row is None:
            continue
        m = self.meta[row]
        self.meta[row] = None
        self.name_col[row] = None
        self.tags_col[row] = None
        self.name_hash_col[row] = 0
        if self.kind_col is not None:
            self.kind_col[row] = None
        self.scope_col[row] = 0
        self.idle[row] = 0
        self.touched[row] = False
        self._fold_key_fingerprints(m.key, m.scope, int(row))
        self._free.append(int(row))
        rows.append(int(row))
    if rows:
        self.reset_rows(np.asarray(rows, np.int64))
        self.recycled += len(rows)
    return len(rows)


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
        for name, fn in (("end_interval", _reference_end_interval),
                         ("release_keys", _reference_release_keys),
                         ("_fold_key_fingerprints", _reference_fold)):
            setattr(ar, name, types.MethodType(fn, ar))
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
    # both checksums are what folding kdict from nothing with the plain
    # fnv1a_64 gives: no birth, batched free, growth or restore drifted
    assert (ar.key_checksum, ar.keyset_checksum) == _scratch_fold(
        ar.kdict), where
    # a live row keeps the pair its birth hashed, a free row keeps
    # nothing — so each checksum is the XOR of its whole column
    for (key, scope), row in ar.kdict.items():
        assert tuple(ar.fp_col[row].tolist()) == _plain_fingerprints(
            key, scope, row), where
    assert len(ar.fp_col) == ar.capacity, where
    fold = np.bitwise_xor.reduce(ar.fp_col, axis=0)
    assert (int(fold[1]), int(fold[0])) == (
        ar.key_checksum, ar.keyset_checksum), where
    live = set(ar.kdict.values())
    assert live.isdisjoint(ar._free), where
    assert len(ar._free) == len(set(ar._free)) == ar.capacity - len(
        live), where
    for row in ar._free:
        assert ar.meta[row] is None, where
        assert ar.name_col[row] is None and ar.tags_col[row] is None, where
        assert ar.kind_col is None or ar.kind_col[row] is None, where
        assert ar.name_hash_col[row] == 0 and ar.scope_col[row] == 0, where
        assert not ar.touched[row] and not ar.fp_col[row].any(), where


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("family", list(_FAMILIES))
def test_end_interval_equals_the_pass_over_the_capacity(family, seed):
    rng = random.Random(f"{family}/{seed}")
    ar, ref = _make(family, 8), _make(family, 8, reference=True)
    pool = 48                      # past 8 and 16 and 32: three _grow()s
    freed = freed_ref_rows = born = 0
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
            born += new
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
        assert ar.births == ref.births == born
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
            freed = born = 0       # a fresh arena: none recycled or born
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


# -- PR 45: the fingerprints are paid once, at the row's birth ----------

_ROWS = [0, 9, 10, 65_535, 100_000, 123_456_789]
_IDENTITIES = [
    ("plain.name", ["env:prod", "host:web-01"]),
    ("no.tags", []),
    ("näme.ü.日本語", ["k:v"]),                  # UTF-8: 2 and 3 bytes
    ("tag.values", ["région:zürich", "😀:1"]),  # and 4
    ("", []),
]


@pytest.mark.parametrize("row", _ROWS)
def test_a_births_hashes_equal_the_plain_reference(row):
    """Value parity of the birth's hash with two whole passes of
    `fnv1a_64`, on each path: over the UTF-8 bytes, through the
    continuation over "\\0<row>" where the row has 1, 2, 5 and more
    digits."""
    for name, tags in _IDENTITIES:
        for mtype in ("counter", "timer"):
            key = MetricKey(name, mtype, ",".join(sorted(tags)))
            for scope in MetricScope:
                base = identity_string(key, scope)
                assert metric_key.key_fingerprints(base, row) == (
                    fnv1a_64(base), fnv1a_64(f"{base}\x00{row}")), (
                    key, scope, row)


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_row_for_keeps_the_plain_hashes_of_the_row_it_gave(family):
    """The same parity through the arena: the row bound into the second
    hash is the row the free list handed out, here rows of 1, 2, 5 and 6
    digits of a pre-sized arena."""
    ar = _make(family, 131_072)
    ar._free = [r for r in reversed(_ROWS) if r < ar.capacity]
    given = []
    for i, (name, tags) in enumerate(_IDENTITIES):
        key = MetricKey(name, _FAMILIES[family][1], ",".join(sorted(tags)))
        scope = list(MetricScope)[i % 3]
        given.append(ar.row_for(key, scope, list(tags)))
        assert tuple(ar.fp_col[given[-1]].tolist()) == _plain_fingerprints(
            key, scope, given[-1])
    assert given == _ROWS[:5] and ar.births == 5
    assert (ar.key_checksum, ar.keyset_checksum) == _scratch_fold(ar.kdict)
    # the column holds Python-int-sized values whole (no float detour)
    assert ar.fp_col.dtype == np.uint64


class _Calls:
    """Counts the calls of everything that hashes or spells a key's
    identity, with no clock: the plain `fnv1a_64` (and the characters
    it was handed), `identity_string`, and the birth's own hasher."""

    def __init__(self, monkeypatch):
        self.n = dict.fromkeys(
            ("fnv1a_64", "hashed_chars", "identity_string", "hasher"), 0)
        for label, name in (("fnv1a_64", "fnv1a_64"),
                            ("identity_string", "identity_string"),
                            ("hasher", "key_fingerprints")):
            monkeypatch.setattr(
                metric_key, name,
                self._counted(label, getattr(metric_key, name)))

    def _counted(self, name, fn):
        def counted(*a, **kw):
            self.n[name] += 1
            if name == "fnv1a_64":
                self.n["hashed_chars"] += len(a[0])
            return fn(*a, **kw)
        return counted

    def take(self) -> dict:
        n, self.n = self.n, dict.fromkeys(self.n, 0)
        return n


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_a_death_hashes_nothing_and_a_birth_hashes_once(family,
                                                        monkeypatch):
    ar = _make(family, 64)
    n = 40
    keys = [_key(family, i) for i in range(n)]
    # rows are handed out in order: key i is born on row i
    once = sum(len(identity_string(k, s)) + len(f"\x00{i}")
               for i, (k, s, _) in enumerate(keys))
    calls = _Calls(monkeypatch)
    nothing = dict.fromkeys(calls.n, 0)
    for key, scope, tags in keys:
        ar.row_for(key, scope, tags)
    # a birth spells the identity once and hashes it once: the second
    # fnv1a_64 call continues the first over "\0<row>" alone
    assert calls.take() == {"fnv1a_64": 2 * n, "hashed_chars": once,
                            "identity_string": n, "hasher": n}
    for key, scope, tags in keys:          # a live key's upsert: nothing
        ar.row_for(key, scope, tags)
    assert calls.take() == nothing
    # D = 12 keys evicted, then the other 28 idle out in one cut
    assert ar.release_keys([(k, s) for k, s, _ in keys[:12]]) == 12
    for _ in range(IDLE_GC_INTERVALS):     # the first cut restarts them
        assert ar.end_interval() == 0
    assert ar.end_interval() == n - 12
    assert ar.recycled == n and not ar.kdict
    assert calls.take() == nothing
    assert (ar.key_checksum, ar.keyset_checksum) == (0, 0)
    assert not ar.fp_col.any()

"""Metric identity types: MetricKey, UDPMetric, scopes, fnv1a sharding digest.

Mirrors `samplers/parser.go:25-104`: a metric's identity is (name, type,
deterministically-joined tags); its 32-bit fnv1a digest picks the worker
shard (`server.go:997-1011`) and, in the TPU design, the arena row hash.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any

from veneur_tpu.util import tagging


class MetricScope(enum.IntEnum):
    """Where the metric is aggregated (`samplers/parser.go:64-97`)."""
    MIXED = 0
    LOCAL_ONLY = 1
    GLOBAL_ONLY = 2


_FNV1A_INIT32 = 0x811C9DC5
_FNV1A_PRIME32 = 0x01000193
_MASK32 = 0xFFFFFFFF


def fnv1a_32(data: bytes, h: int = _FNV1A_INIT32) -> int:
    """Incremental 32-bit FNV-1a (segmentio/fasthash-equivalent)."""
    for b in data:
        h = ((h ^ b) * _FNV1A_PRIME32) & _MASK32
    return h


def metric_digest(name: str, mtype: str, joined_tags: str) -> int:
    """The worker-sharding digest: fnv1a over name, type, joined tags
    (`samplers/parser.go:54-60`)."""
    h = fnv1a_32(name.encode())
    h = fnv1a_32(mtype.encode(), h)
    h = fnv1a_32(joined_tags.encode(), h)
    return h


_FNV1A_INIT64 = 0xCBF29CE484222325
_FNV1A_PRIME64 = 0x100000001B3
_MASK64 = 0xFFFFFFFFFFFFFFFF


def fnv1a_64(s: str, seed: int = 0) -> int:
    """64-bit FNV-1a of a string, with an optional seed XOR-folded into
    the offset basis (seeded deterministic tie-breaks)."""
    h = _FNV1A_INIT64 ^ (seed & _MASK64)
    for b in s.encode():
        h = ((h ^ b) * _FNV1A_PRIME64) & _MASK64
    return h


def key_fingerprints(base: str, row: int) -> tuple[int, int]:
    """An arena row's two key fingerprints (core/arena.py):
    (fnv1a_64(base), fnv1a_64(f"{base}\x00{row}")), of the key's identity
    alone and of the identity bound to its row.  FNV-1a is a running
    hash, so the second is the first one's state continued over
    "\x00<row>" and `base` is hashed once: a seed of INIT ^ state makes
    fnv1a_64 start from `state`."""
    keys = fnv1a_64(base)
    return keys, fnv1a_64(f"\x00{row}", _FNV1A_INIT64 ^ keys)


def identity_string(key: "MetricKey", scope: "MetricScope") -> str:
    """THE canonical (key, scope) identity encoding — shared by the
    arena key-dictionary fingerprints (core/arena.py) and the
    cardinality guard's seeded eviction ranking (core/cardinality.py),
    so the two can never silently diverge."""
    return (f"{key.name}\x00{key.type}\x00{key.joined_tags}"
            f"\x00{int(scope)}")


@dataclass(frozen=True)
class MetricKey:
    """Comparable/hashable sampler-map key (`samplers/parser.go:100-104`)."""
    name: str
    type: str
    joined_tags: str


@dataclass
class UDPMetric:
    """One parsed client sample (`samplers/parser.go:25-35`)."""
    name: str = ""
    type: str = ""
    joined_tags: str = ""
    digest: int = 0
    value: Any = None
    sample_rate: float = 1.0
    tags: list[str] = field(default_factory=list)
    scope: MetricScope = MetricScope.MIXED
    timestamp: int = 0
    message: str = ""
    hostname: str = ""

    @property
    def key(self) -> MetricKey:
        return MetricKey(self.name, self.type, self.joined_tags)

    def update_tags(self, tags: list[str],
                    extend_tags: tagging.ExtendTags | None) -> None:
        """Sort+join tags, apply implicit tags, recompute digest
        (`samplers/parser.go:40-61`)."""
        et = extend_tags if extend_tags is not None else tagging.EMPTY
        self.tags = et.extend(tags)
        self.joined_tags = ",".join(self.tags)
        self.digest = metric_digest(self.name, self.type, self.joined_tags)

"""Differential parser fuzz: the C++ ingest engine vs the Python parser.

Round-2 verdict #7: the reference pins DogStatsD behavior with a 1149-line
malformation table (`parser_test.go:855-1020`); those vectors are ported in
tests/test_parser.py and tests/test_native_ingest.py.  This file adds the
property-based layer: hypothesis generates both structured near-valid
packets and arbitrary byte soup, and the two parsers must agree — same
accept/reject decision, same staged (name, type, tags, scope) identities,
same values/weights — for every input.  The Python parser is the semantic
reference (itself matching `samplers/parser.go:349-503` error-for-error).
"""

import math

import pytest

# property-based layer only where hypothesis exists: without the guard,
# the tier-1 run reports a collection ERROR on images that don't bake
# the package in (the table-driven vectors in test_parser.py /
# test_native_ingest.py still run everywhere)
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from veneur_tpu import ingest as ingest_mod
from tests.test_native_ingest import native_parse, python_reference_parse



@pytest.fixture(scope="module", autouse=True)
def _native_engine():
    """Build/load inside a fixture: an engine that does not build is a
    FAILED test here, not a collection error (and never a skip)."""
    return ingest_mod.load_library()


FUZZ_SETTINGS = settings(max_examples=250, deadline=None,
                         derandomize=True)

# name/tag alphabets: printable-ish plus the structural characters the
# parser must treat specially
_NAME = st.text(
    alphabet="abcXYZ019._-/ |#@:,\t{}", min_size=0, max_size=12)
_TYPE = st.sampled_from(["c", "g", "h", "ms", "d", "s", "", "cc", "x",
                         "C", "G", "seconds"])
_VALUE = st.one_of(
    st.integers(-10**6, 10**6).map(str),
    st.floats(allow_nan=False, allow_infinity=False,
              width=32).map(lambda f: f"{f:.6g}"),
    st.sampled_from(["nan", "NaN", "-inf", "+inf", "inf", "1e3", "1E-2",
                     "0x10", "1_0", "", " 1", "1 ", "+5", "-0", "007",
                     "1.", ".5", "--1", "1e", "1e+", "ە1"]))
_RATE = st.one_of(
    st.just(None),
    st.sampled_from(["0.1", "1", "0", "-0.1", "1.1", "0.5", "", "abc",
                     "0.25"]))
_TAG = st.text(alphabet="abckey:val019.-_,#|@", min_size=0, max_size=10)


@st.composite
def structured_packet(draw):
    name = draw(_NAME)
    values = draw(st.lists(_VALUE, min_size=1, max_size=3))
    mtype = draw(_TYPE)
    parts = [f"{name}:{':'.join(values)}", mtype]
    rate = draw(_RATE)
    if rate is not None:
        parts.append(f"@{rate}")
    tags = draw(st.lists(
        st.one_of(_TAG, st.sampled_from(
            ["veneurlocalonly", "veneurglobalonly", "a:1", "b"])),
        min_size=0, max_size=3))
    if draw(st.booleans()) or tags:
        parts.append("#" + ",".join(tags))
    if draw(st.booleans()):
        # duplicate/malformed trailing sections
        parts.append(draw(st.sampled_from(
            ["@0.2", "#x:y", "", "junk", "@", "#"])))
    return "|".join(parts).encode()


def _assert_agree(line: bytes):
    ref = python_reference_parse([line])
    batch = native_parse([line])
    got = {}
    eng_keys = {nk.id: nk for nk in batch.new_keys}
    for ids, vals, extra in (
            (batch.c_ids, batch.c_vals, None),
            (batch.g_ids, batch.g_vals, None),
            (batch.h_ids, batch.h_vals, batch.h_wts)):
        for i, uid in enumerate(ids):
            nk = eng_keys[uid]
            key = (nk.name, nk.mtype, nk.joined_tags, nk.scope)
            got.setdefault(key, []).append(
                (float(vals[i]),
                 float(extra[i]) if extra is not None else None))
    for i, uid in enumerate(batch.s_ids):
        nk = eng_keys[uid]
        got.setdefault((nk.name, nk.mtype, nk.joined_tags, nk.scope),
                       []).append(("<member>", None))

    ref_norm = {}
    for (name, mtype, joined, scope), samples in ref.items():
        for value, rate in samples:
            if mtype == "set":
                ref_norm.setdefault((name, mtype, joined, scope),
                                    []).append(("<member>", None))
            elif mtype in ("histogram", "timer"):
                ref_norm.setdefault((name, mtype, joined, scope),
                                    []).append(
                    (float(value), 1.0 / rate))
            else:
                v = float(value)
                if mtype == "counter":
                    v = float(int(v / rate))
                ref_norm.setdefault((name, mtype, joined, scope),
                                    []).append((v, None))

    assert set(got) == set(ref_norm), (
        f"{line!r}: staged identities diverge\n"
        f"  native={sorted(got)}\n  python={sorted(ref_norm)}")
    for key in ref_norm:
        a, b = sorted(got[key], key=str), sorted(ref_norm[key], key=str)
        assert len(a) == len(b), (line, key, a, b)
        for (va, wa), (vb, wb) in zip(a, b):
            if isinstance(va, str):
                assert va == vb, (line, key)
                continue
            assert math.isclose(va, vb, rel_tol=1e-5, abs_tol=1e-6), (
                line, key, a, b)
            if wa is not None or wb is not None:
                assert math.isclose(wa, wb, rel_tol=1e-5), (line, key)


@FUZZ_SETTINGS
@given(structured_packet())
def test_structured_packets_agree(line):
    _assert_agree(line)


@FUZZ_SETTINGS
@given(st.binary(min_size=0, max_size=40).filter(
    lambda b: b"\n" not in b
    and not b.startswith(b"_e{") and not b.startswith(b"_sc")))
def test_byte_soup_agrees(line):
    _assert_agree(line)


@FUZZ_SETTINGS
@given(st.binary(min_size=0, max_size=30).filter(lambda b: b"\n" not in b))
def test_events_and_checks_punt_to_python(prefix):
    """_e{/_sc lines are not metrics: the engine must punt them verbatim
    to the Python slow path (batch.other), never stage them."""
    for lead in (b"_e{", b"_sc"):
        line = lead + prefix
        batch = native_parse([line])
        assert list(batch.other) == [line]
        assert not len(batch.c_ids) and not len(batch.g_ids)
        assert not len(batch.h_ids) and not len(batch.s_ids)

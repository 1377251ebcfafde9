"""Key format tests (§4.1): order preservation per type, round trips,
multi-column lexicographic semantics, varchar terminator behaviour."""

import struct

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import keyformat as KF


@given(st.integers(-(2**31), 2**31 - 1), st.integers(-(2**31), 2**31 - 1))
def test_int32_order(a, b):
    assert (KF.encode_int32(a) < KF.encode_int32(b)) == (a < b)
    assert KF.decode_int32(KF.encode_int32(a)) == a


@given(st.integers(-(2**63), 2**63 - 1), st.integers(-(2**63), 2**63 - 1))
def test_int64_order(a, b):
    assert (KF.encode_int64(a) < KF.encode_int64(b)) == (a < b)
    assert KF.decode_int64(KF.encode_int64(a)) == a


_floats = st.floats(allow_nan=False, width=32)


@given(_floats, _floats)
def test_float32_order(a, b):
    af = struct.unpack(">f", struct.pack(">f", a))[0]
    bf = struct.unpack(">f", struct.pack(">f", b))[0]
    ka, kb = KF.encode_float32(af), KF.encode_float32(bf)
    if af == bf:  # +0.0 / -0.0 keys may differ; order among equals is free
        return
    assert (ka < kb) == (af < bf)
    assert KF.decode_float32(ka) == af


@given(st.floats(allow_nan=False), st.floats(allow_nan=False))
def test_float64_order(a, b):
    if a == b:
        return
    assert (KF.encode_float64(a) < KF.encode_float64(b)) == (a < b)
    assert KF.decode_float64(KF.encode_float64(a)) == a


@given(st.integers(-(10**9), 10**9), st.integers(-(10**9), 10**9))
def test_decimal_order(a, b):
    ka, kb = KF.encode_decimal(a, 5), KF.encode_decimal(b, 5)
    assert (ka < kb) == (a < b)
    assert KF.decode_decimal(ka, 5) == a


def test_decimal_paper_figure4():
    """Exact byte patterns from Figure 4 (2-byte decimal(2,0))."""
    assert KF.encode_decimal(99, 1) == bytes([0b00000011, 0b01100011])
    assert KF.encode_decimal(1, 1) == bytes([0b00000011, 0b00000001])
    assert KF.encode_decimal(0, 1) == bytes([0b00000011, 0b00000000])
    assert KF.encode_decimal(-1, 1) == bytes([0b00000010, 0b11111110])
    assert KF.encode_decimal(-99, 1) == bytes([0b00000010, 0b10011100])
    assert KF.encode_decimal(None, 1) < KF.encode_decimal(-99, 1)  # null lowest


_varchar = st.text(
    alphabet=st.characters(min_codepoint=1, max_codepoint=127), max_size=20
)


@given(_varchar, _varchar)
def test_varchar_order(a, b):
    ka, kb = KF.encode_varchar(a, 32), KF.encode_varchar(b, 32)
    assert (ka < kb) == (a.encode() < b.encode())


def test_varchar_prefix_case():
    """AB∅ < ABA∅: the distinction bit lands in the terminator (§4.1.C)."""
    ka, kb = KF.encode_varchar("AB", 30), KF.encode_varchar("ABA", 30)
    assert ka < kb
    with pytest.raises(ValueError):
        KF.encode_varchar("A\x00B", 30)


@given(
    st.lists(
        st.tuples(st.integers(-100, 100), _varchar, st.integers(-100, 100)),
        min_size=2,
        max_size=20,
    )
)
@settings(max_examples=50)
def test_multicolumn_lexicographic(rows):
    """Tuple order == encoded byte order (Figure 5 semantics), including
    padded packed-word comparisons."""
    enc = [
        KF.encode_multicolumn(
            [KF.encode_int32(a), KF.encode_varchar(s, 24), KF.encode_int32(b)]
        )
        for (a, s, b) in rows
    ]
    want = sorted(range(len(rows)), key=lambda i: (rows[i][0], rows[i][1].encode(), rows[i][2]))
    got = sorted(range(len(rows)), key=lambda i: enc[i])
    # equal keys may permute freely: compare by tuple values not index
    assert [rows[i] for i in got] == [rows[i] for i in want]
    # packed words preserve order too (zero padding, §4.1)
    ks = KF.keys_to_words(enc)
    order = sorted(range(len(rows)), key=lambda i: tuple(ks.words[i]) + (rows[i],))
    by_words = [rows[i] for i in sorted(range(len(rows)), key=lambda i: tuple(int(w) for w in ks.words[i]))]
    by_bytes = [rows[i] for i in got]
    assert by_words == by_bytes


def test_keys_to_words_roundtrip():
    keys = [b"hello", b"a", b"longer-key-material!"]
    ks = KF.keys_to_words(keys)
    for i, k in enumerate(keys):
        assert KF.words_to_bytes(ks.words[i], int(ks.lengths[i])) == k


def _fixed_record_loop(rng, n, width):
    """Row-at-a-time reference for the bulk fixed-record generator."""
    out = np.full((n, width), ord("0"), np.uint8)
    doc = rng.integers(0, 10000, n)
    item = rng.integers(0, 100, n)
    for i in range(n):
        s = f"{2024:04d}{int(doc[i]):08d}{int(item[i]):04d}{i:010d}".encode()
        out[i, : len(s)] = np.frombuffer(s[:width], np.uint8)
    keys = sorted(set(bytes(r) for r in out))
    rng.shuffle(keys)
    return KF.keys_to_words(keys)


@pytest.mark.parametrize("name,n", [("INDBTAB", 1), ("INDBTAB", 2000), ("Part", 777)])
def test_fixed_record_dataset_matches_loop(name, n):
    """The bulk INDBTAB/Part generator is byte-identical to the row loop."""
    from dataclasses import replace

    from repro.configs.paper_index import DATASETS
    from repro.data.synthetic import dataset_keys

    cfg = replace(DATASETS[name], n_keys=n)
    got = dataset_keys(cfg, seed=3)
    want = _fixed_record_loop(np.random.default_rng(3), n, cfg.key_bytes)
    np.testing.assert_array_equal(got.words, want.words)
    np.testing.assert_array_equal(got.lengths, want.lengths)
    np.testing.assert_array_equal(got.rids, want.rids)

"""The code of a sparse payload's positions on the party-global link
(``compression.entries.CODED``): strictly ascending positions as the
gaps between them, LEB128, padded to whole words. The native form
(``native/kernels.cc`` ``gxk_idx_encode`` / ``gxk_idx_decode``) against
the numpy form against the plain list, byte for byte, and every
refusal of the decoder raising instead of returning a shorter list."""

import numpy as np
import pytest

from geomx_tpu import kernels_native
from geomx_tpu.compression import Entries, Pairs, _generic_decompress
from geomx_tpu.compression import entries as coding
from geomx_tpu.compression.entries import (CODED, decode_positions,
                                           decode_positions_numpy,
                                           encode_positions,
                                           encode_positions_numpy,
                                           plain_positions)

INT32_MAX = (1 << 31) - 1


def _native(monkeypatch):
    if kernels_native.lib() is None:
        pytest.skip("no native kernels on this machine")
    # the public pair takes the library where there is one
    return encode_positions, decode_positions


def _numpy(monkeypatch):
    return encode_positions_numpy, decode_positions_numpy


def _fallback(monkeypatch):
    """The public pair on a machine whose library cannot be built."""
    monkeypatch.setattr(kernels_native, "lib", lambda: None)
    return encode_positions, decode_positions


FORMS = {"native": _native, "numpy": _numpy, "fallback": _fallback}


@pytest.fixture(params=list(FORMS))
def form(request, monkeypatch):
    return FORMS[request.param](monkeypatch)


def _by_hand(positions):
    """The code of a plain list, one python int at a time."""
    out, prev = bytearray(), None
    for at in positions:
        gap = at if prev is None else at - prev
        prev = at
        while gap >= 0x80:
            out.append(gap & 0x7f | 0x80)
            gap >>= 7
        out.append(gap)
    out.extend(bytes(-len(out) % 4))
    return bytes(out)


def _first_gaps(*gaps):
    return list(np.cumsum(gaps, dtype=np.int64))


# name -> (positions, the range's size)
LISTS = {
    "empty": ([], 100),
    "one_entry": ([41], 100),
    "position_0_first": ([0, 1, 5], 6),
    "position_0_alone": ([0], 1),
    "gaps_of_1": (list(range(10, 75)), 75),
    "gap_127": (_first_gaps(3, 127, 127), 1000),
    "gap_128": (_first_gaps(3, 128, 128), 1000),
    "gap_16383": (_first_gaps(0, 16_383, 16_383, 1), 40_000),
    "gap_16384": (_first_gaps(1, 16_384, 16_384), 40_000),
    "gap_2_21": (_first_gaps(5, 1 << 21, 1, 1 << 21), 1 << 23),
    "gap_2_28": (_first_gaps(127, 1 << 28, 1 << 28, 2), 1 << 30),
    "last_2_31_minus_1": ([0, 77, INT32_MAX - 1, INT32_MAX], 1 << 31),
    "first_2_31_minus_1": ([INT32_MAX], 1 << 31),
    "int64_past_2_31": ([5, 1 << 31, (1 << 31) + 1, 1 << 40,
                         (1 << 62) + 3], (1 << 62) + 4),
    "padding_0": ([1, 2, 3, 4], 10),
    "padding_1": ([1, 2, 3], 10),
    "padding_2": ([1, 2], 10),
    "padding_3": ([1], 10),
    "padding_0_of_long_gaps": ([200, 400], 500),
}


@pytest.mark.parametrize("name", list(LISTS))
def test_a_list_goes_through_the_code_and_comes_back(form, name):
    encode, decode = form
    positions, size = LISTS[name]
    itype = np.int32 if size <= INT32_MAX else np.int64
    if name == "last_2_31_minus_1" or name == "first_2_31_minus_1":
        itype = np.int32            # the largest an int32 list can hold
    idx = np.asarray(positions, dtype=itype)
    coded = encode(idx)
    assert coded.dtype == CODED and coded.size % 4 == 0
    assert coded.tobytes() == _by_hand(positions)
    if name.startswith("padding_"):
        used = len(positions) if "long" not in name else 4
        assert coded.size - used == int(name.split("_")[1])
    back = decode(coded, len(positions), size)
    assert back.dtype == (np.int32 if size <= INT32_MAX else np.int64)
    assert back.tolist() == positions
    # a frame's part is read-only and need not start a word
    frame = np.frombuffer(b"\x01" + coded.tobytes(), dtype=CODED)[1:]
    assert not frame.flags.writeable
    assert decode(frame, len(positions), size).tolist() == positions


@pytest.mark.parametrize("wide", [False, True], ids=["int32", "int64"])
def test_both_widths_of_positions_code_alike(form, wide):
    encode, _decode = form
    positions = _first_gaps(9, 1, 130, 70_000, 3)
    a = encode(np.asarray(positions, dtype=np.int64 if wide else np.int32))
    assert a.tobytes() == _by_hand(positions)


@pytest.mark.parametrize("density", [0.01, 0.02])
def test_a_random_share_of_a_large_key(density):
    """1% and 2% of 38.6M elements (the largest key of the GPT-2 cells
    and two parties' union of it): the two forms give the same bytes and
    the same list, at the bytes a position the issue reckons with."""
    if kernels_native.lib() is None:
        pytest.skip("no native kernels on this machine")
    n = 38_597_376
    rng = np.random.default_rng(int(density * 1000))
    idx = np.flatnonzero(rng.random(n) < density).astype(np.int32)
    coded = encode_positions(idx)
    assert coded.tobytes() == encode_positions_numpy(idx).tobytes()
    assert coded.size / idx.size < (1.30 if density == 0.01 else 1.10)
    for decode in (decode_positions, decode_positions_numpy):
        back = decode(coded, idx.size, n)
        assert back.dtype == np.int32
        np.testing.assert_array_equal(back, idx)


NOT_ASCENDING = {
    "a_repeat": [3, 9, 9, 12],
    "a_step_back": [3, 9, 8, 12],
    "by_magnitude": [700, 2, 55, 31],
    "negative_first": [-1, 4],
    "large_then_small": [1 << 30, 5],
}


@pytest.mark.parametrize("name", list(NOT_ASCENDING))
def test_positions_that_do_not_ascend_are_not_coded(form, name):
    encode, _decode = form
    assert encode(np.asarray(NOT_ASCENDING[name], dtype=np.int32)) is None
    assert encode(np.asarray(NOT_ASCENDING[name], dtype=np.int64)) is None


def _pad(raw: bytes) -> np.ndarray:
    return np.frombuffer(raw + bytes(-len(raw) % 4), dtype=CODED)


# name -> (bytes, positions expected, size)
REFUSED = {
    "truncated_buffer": (_by_hand([5, 300, 70_000])[:3], 3, 100_000),
    "truncated_inside_a_gap": (b"\x05\x80", 2, 100_000),
    "nothing_where_one_is_expected": (b"", 1, 10),
    "varint_over_ten_bytes": (b"\x80" * 10 + b"\x01", 1, 1 << 62),
    "varint_over_64_bits": (b"\xff" * 9 + b"\x02", 1, 1 << 62),
    "gap_of_0_after_the_first": (b"\x05\x00\x03", 3, 100),
    "last_position_is_size": (_by_hand([5, 99, 100]), 3, 100),
    "first_position_is_size": (_by_hand([100]), 1, 100),
    "last_position_far_over": (_by_hand([5, 1 << 40]), 2, 1 << 20),
    "a_size_of_0": (_by_hand([0]), 1, 0),
    "fewer_positions_than_values": (_by_hand([5, 6, 7]), 4, 100),
    "more_positions_than_values": (_by_hand([5, 6, 7, 8, 9, 10, 11, 12]),
                                   4, 100),
    "one_more_position_than_values": (_by_hand([5, 6, 7]), 2, 100),
    "bytes_where_no_position_is": (b"\x00\x00\x00\x00", 0, 100),
    "a_word_of_padding_too_many": (_by_hand([5]) + bytes(4), 1, 100),
}


@pytest.mark.parametrize("name", list(REFUSED))
def test_the_decoder_refuses_and_returns_no_short_list(form, name):
    _encode, decode = form
    raw, count, size = REFUSED[name]
    with pytest.raises(ValueError, match="coded positions"):
        decode(_pad(raw), count, size)


def test_a_gap_that_would_wrap_64_bits_is_out_of_range(form):
    _encode, decode = form
    top = b"\xff" * 9 + b"\x01"          # 2**64 - 1
    with pytest.raises(ValueError, match="outside the range"):
        decode(_pad(b"\x05" + top), 2, (1 << 63) - 1)


@pytest.mark.parametrize("cls", [Pairs, Entries])
@pytest.mark.parametrize("vtype", [np.float32, np.float16])
def test_from_wire_takes_the_coded_part_as_entries(cls, vtype):
    idx = np.asarray([0, 4, 130, 70_000], dtype=np.int32)
    vals = np.asarray([1.5, -2.0, 0.25, 8.0], dtype=vtype)
    got = cls.from_wire(vals, encode_positions(idx), 70_001)
    assert type(got) is Entries and got.size == 70_001
    assert got.idx.dtype == np.int32 and got.vals.dtype == np.float32
    np.testing.assert_array_equal(got.idx, idx)
    np.testing.assert_array_equal(got.vals, vals.astype(np.float32))
    dense = _generic_decompress("bsc", vals, encode_positions(idx), 70_001)
    np.testing.assert_array_equal(np.flatnonzero(dense), idx)


def test_from_wire_of_a_large_key_decodes_to_int64():
    idx = np.asarray([7, 1 << 33], dtype=np.int64)
    got = Entries.from_wire(np.ones(2, np.float32), encode_positions(idx),
                            1 << 34)
    assert got.idx.dtype == np.int64 and got.idx.tolist() == idx.tolist()


@pytest.mark.parametrize("cls", [Pairs, Entries])
def test_from_wire_raises_on_a_count_that_does_not_match(cls):
    coded = encode_positions(np.asarray([1, 2, 3], dtype=np.int32))
    for count in (2, 4):
        with pytest.raises(ValueError, match="coded positions"):
            cls.from_wire(np.ones(count, np.float32), coded, 10)


def test_a_reader_of_plain_positions_refuses_the_code():
    """A ``uint8`` part is an integer array: read as positions it would
    be 0..255, silently. Whoever reads positions without the decoder
    refuses it."""
    coded = encode_positions(np.asarray([300, 301], dtype=np.int32))
    with pytest.raises(ValueError, match="coded positions part"):
        plain_positions(coded)
    same = np.asarray([3, 1, 2], dtype=np.int32)
    assert np.shares_memory(plain_positions(same), same)
    assert plain_positions([[1, 2], [3, 4]]).tolist() == [1, 2, 3, 4]


def test_the_device_compressor_refuses_the_code():
    pytest.importorskip("jax")
    from geomx_tpu.ops import DeviceBSCCompressor

    idx = np.asarray([300, 301], dtype=np.int32)
    with pytest.raises(ValueError, match="coded positions part"):
        DeviceBSCCompressor(0.01).decompress_push(
            "bsc", np.ones(2, np.float32), encode_positions(idx), 1 << 16)


def test_the_native_form_is_what_a_server_runs():
    """The numpy passes are the reference and the fallback; where the
    library builds, neither public function reaches them."""
    if kernels_native.lib() is None:
        pytest.skip("no native kernels on this machine")
    idx = np.asarray([1, 200, 40_000], dtype=np.int32)

    def boom(*a, **kw):
        raise AssertionError("the numpy form ran")

    orig = coding.encode_positions_numpy, coding.decode_positions_numpy
    coding.encode_positions_numpy = coding.decode_positions_numpy = boom
    try:
        coded = encode_positions(idx)
        assert decode_positions(coded, 3, 40_001).tolist() == idx.tolist()
    finally:
        coding.encode_positions_numpy, coding.decode_positions_numpy = orig


# ---------------------------------------------------------------------
# the sum of two ``Entries``: ``native/kernels.cc`` ``gxk_entries_merge``
# against the numpy chain (concatenate, stable argsort, gathers, run-sum)
# ---------------------------------------------------------------------

ITYPES = {"int32": np.int32, "int64": np.int64}


def _entries(positions, itype, size=1 << 20, seed=0):
    """``positions`` with seeded float32 values, negative zero, a
    denormal and a huge one among them."""
    idx = np.asarray(positions, dtype=itype)
    vals = np.random.default_rng([seed, idx.size]).standard_normal(
        idx.size).astype(np.float32)
    vals[::7] = -0.0
    vals[3::11] = 1e-41
    vals[5::13] = 1e38
    return Entries(idx, vals, size)


def _random_positions(seed, count, size=1 << 20):
    return np.sort(np.random.default_rng(seed).choice(
        size, count, replace=False))


def _numpy_merge(monkeypatch, a, b):
    """``a.merge(b)`` as a machine without the library runs it."""
    with monkeypatch.context() as m:
        m.setattr(kernels_native, "lib", lambda: None)
        return a.merge(b)


def _same_entries(got, want):
    assert got.size == want.size
    assert got.idx.dtype == want.idx.dtype
    assert got.vals.dtype == want.vals.dtype == np.float32
    np.testing.assert_array_equal(got.idx, want.idx)
    assert got.vals.tobytes() == want.vals.tobytes()


# name -> (the earlier arriver's positions, the later one's)
OPERANDS = {
    "interleaved": (range(0, 4000, 2), range(1, 4001, 2)),
    "identical": (range(5, 3005, 3), range(5, 3005, 3)),
    "disjoint_later_first": (range(5000, 6000), range(100, 1100)),
    "one_inside_the_other": ([1, 1 << 19], range(10, 2010, 4)),
    "some_in_common": (_random_positions(1, 5000, 40_000),
                       _random_positions(2, 7000, 40_000)),
    "one_entry_each_equal": ([77], [77]),
    "one_entry_each_apart": ([78], [77]),
    "long_tail_of_the_first": (range(0, 9000), [0, 3, 4]),
    "long_tail_of_the_second": ([2, 8999], range(0, 9000)),
    "large": (_random_positions(3, 200_000), _random_positions(4, 210_000)),
}


@pytest.mark.parametrize("itype", list(ITYPES))
@pytest.mark.parametrize("name", list(OPERANDS))
def test_the_native_merge_is_the_numpy_chain_bit_for_bit(monkeypatch, name,
                                                         itype):
    if kernels_native.lib() is None:
        pytest.skip("no native kernels on this machine")
    first, second = OPERANDS[name]
    a = _entries(list(first), ITYPES[itype], seed=1)
    b = _entries(list(second), ITYPES[itype], seed=2)
    got, native = a.merge(b)
    want, numpy_ran = _numpy_merge(monkeypatch, a, b)
    assert native is True and numpy_ran is False
    _same_entries(got, want)
    _same_entries(a.add(b), want)
    assert np.all(got.idx[1:] > got.idx[:-1])
    # a position both hold: the earlier arriver's term first
    both = np.intersect1d(a.idx, b.idx)
    if both.size:
        at = both[0]
        assert got.vals[got.idx == at] == (a.vals[a.idx == at]
                                           + b.vals[b.idx == at])


# name -> (first, second, whether the sum is one of the two operands)
NO_PASS = {
    "empty_second": ([3, 9], [], True),
    "empty_first": ([], [3, 9], True),
    "both_empty": ([], [], True),
    "slices_in_order": (range(0, 500), range(500, 900), False),
}


@pytest.mark.parametrize("form", ["native", "fallback"])
@pytest.mark.parametrize("itype", list(ITYPES))
@pytest.mark.parametrize("name", list(NO_PASS))
def test_a_sum_that_needs_no_pass_takes_none(monkeypatch, name, itype,
                                             form):
    if form == "fallback":
        monkeypatch.setattr(kernels_native, "lib", lambda: None)
    first, second, same = NO_PASS[name]
    a = _entries(list(first), ITYPES[itype], seed=1)
    b = _entries(list(second), ITYPES[itype], seed=2)
    got, native = a.merge(b)
    assert native is None
    assert (got is a or got is b) == same
    np.testing.assert_array_equal(got.idx, np.concatenate((a.idx, b.idx)))
    assert got.vals.tobytes() == np.concatenate((a.vals, b.vals)).tobytes()


@pytest.mark.parametrize("itype", list(ITYPES))
def test_three_parties_fold_in_arrival_order(monkeypatch, itype):
    if kernels_native.lib() is None:
        pytest.skip("no native kernels on this machine")
    parts = [_entries(_random_positions(10 + p, 3000, 20_000),
                      ITYPES[itype], size=20_000, seed=p) for p in range(3)]
    got = parts[0].add(parts[1]).add(parts[2])
    want = _numpy_merge(monkeypatch, _numpy_merge(
        monkeypatch, parts[0], parts[1])[0], parts[2])[0]
    _same_entries(got, want)
    # what the dense += of the three gives, term by term (a -0.0 that
    # one party alone holds stays -0.0 here and is +0.0 there)
    dense = np.zeros(20_000, dtype=np.float32)
    for p in parts:
        dense[p.idx] += p.vals
    np.testing.assert_array_equal(got.dense(), dense)
    held = np.zeros(20_000, dtype=bool)
    for p in parts:
        held[p.idx] = True
    np.testing.assert_array_equal(got.idx, np.flatnonzero(held))


@pytest.mark.parametrize("itype", list(ITYPES))
def test_a_sum_of_exactly_0_stays_until_nonzero(monkeypatch, itype):
    if kernels_native.lib() is None:
        pytest.skip("no native kernels on this machine")
    a = Entries(np.asarray([2, 5, 9], dtype=ITYPES[itype]),
                np.asarray([1.5, -0.25, 0.0], dtype=np.float32), 12)
    b = Entries(np.asarray([1, 2, 9], dtype=ITYPES[itype]),
                np.asarray([4.0, -1.5, -0.0], dtype=np.float32), 12)
    got, native = a.merge(b)
    assert native is True
    assert got.idx.tolist() == [1, 2, 5, 9]
    assert got.vals.tolist() == [4.0, 0.0, -0.25, 0.0]
    _same_entries(got, _numpy_merge(monkeypatch, a, b)[0])
    assert got.nonzero().idx.tolist() == [1, 5]


# name -> (first, second): one of the two does not ascend strictly
NOT_MERGED_NATIVELY = {
    "first_descends": ([9, 4, 6], [1, 5]),
    "second_descends": ([1, 12], [9, 4, 6]),
    "first_repeats": ([1, 5, 5, 8], [2, 6]),
    "second_repeats_at_its_end": ([1, 9], [2, 6, 6]),
    "only_the_tail_is_out_of_order": ([1, 2], [0, 7, 8, 3]),
}


@pytest.mark.parametrize("itype", list(ITYPES))
@pytest.mark.parametrize("name", list(NOT_MERGED_NATIVELY))
def test_a_list_that_does_not_ascend_falls_back_and_says_so(
        monkeypatch, caplog, name, itype):
    if kernels_native.lib() is None:
        pytest.skip("no native kernels on this machine")
    first, second = NOT_MERGED_NATIVELY[name]
    a = _entries(first, ITYPES[itype], size=16, seed=1)
    b = _entries(second, ITYPES[itype], size=16, seed=2)
    assert kernels_native.entries_merge(a.idx, a.vals, b.idx, b.vals) is None
    with caplog.at_level("WARNING", logger="geomx.compression"):
        got, native = a.merge(b)
    assert native is False
    assert "do not both ascend" in caplog.text
    _same_entries(got, _numpy_merge(monkeypatch, a, b)[0])
    # what sorting gives: every position once, equal ones summed in order
    dense = np.zeros(16, dtype=np.float32)
    for e in (a, b):
        np.add.at(dense, e.idx, e.vals)
    np.testing.assert_array_equal(got.idx, np.union1d(a.idx, b.idx))
    np.testing.assert_allclose(got.vals, dense[got.idx], rtol=1e-6)


# name -> what makes the operands unfit for the native pass
UNFIT = {
    "two_position_types": lambda a, b: (a, Entries(
        b.idx.astype(np.int64), b.vals, b.size)),
    "strided_positions": lambda a, b: (Entries(
        np.repeat(a.idx, 2)[::2], a.vals, a.size), b),
    "strided_values": lambda a, b: (a, Entries(
        b.idx, np.repeat(b.vals, 2)[::2], b.size)),
    "unsigned_positions": lambda a, b: (Entries(
        a.idx.astype(np.uint32), a.vals, a.size), Entries(
        b.idx.astype(np.uint32), b.vals, b.size)),
}


@pytest.mark.parametrize("name", list(UNFIT))
def test_operands_the_native_pass_does_not_take_merge_in_numpy(caplog, name):
    a = _entries(range(0, 300, 2), np.int32, seed=1)
    b = _entries(range(1, 300, 3), np.int32, seed=2)
    want = a.merge(b)[0]
    a2, b2 = UNFIT[name](a, b)
    with caplog.at_level("WARNING", logger="geomx.compression"):
        got, native = a2.merge(b2)
    assert native is False and not caplog.text
    np.testing.assert_array_equal(got.idx, want.idx)
    assert got.vals.tobytes() == want.vals.tobytes()


def test_the_native_merge_takes_the_wire_s_read_only_arrays():
    if kernels_native.lib() is None:
        pytest.skip("no native kernels on this machine")
    a = _entries(range(0, 300, 2), np.int32, seed=1)
    b = _entries(range(1, 300, 3), np.int32, seed=2)
    want = a.merge(b)[0]
    for arr in (a.idx, a.vals, b.idx, b.vals):
        arr.flags.writeable = False
    got, native = a.merge(b)
    assert native is True
    _same_entries(got, want)
    assert got.idx.flags.c_contiguous and got.vals.flags.c_contiguous

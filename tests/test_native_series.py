"""The Datadog series encoder's native unit (native/ddseries.cc) on its
own: `vnt_dd_series`' float formatting against CPython's `repr(float)`
(through `_json_num`, the Python loop's renderer), its room check, the
pointer compare that decides arena reuse, and that a call releases the
GIL, and the row-by-row compare with a row shelf. What the encoder
builds on top is pinned by tests/test_egress.py.
"""

from __future__ import annotations

import ctypes
import math
import threading
import time

import numpy as np
import pytest

from veneur_tpu import native
from veneur_tpu.core.egress import _json_num

pytestmark = [
    pytest.mark.egress,
    pytest.mark.skipif(native.load_series() is None,
                       reason="the native series encoder did not build"),
]

SEED = 20261002


def _series(prefixes, values, mid=b""):
    """`vnt_dd_series` over whole arrays -> the bytes it wrote."""
    lib = native.load_series()
    values = np.ascontiguousarray(values, np.float64)
    n = values.shape[0]
    assert len(prefixes) == n
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(p) for p in prefixes], out=offsets[1:])
    cap = int(offsets[-1]) + n * lib.vnt_dd_series_room(len(mid))
    out = np.empty(max(cap, 1), np.uint8)
    wrote = lib.vnt_dd_series(
        b"".join(prefixes), offsets.ctypes.data, offsets.ctypes.data + 8,
        values.ctypes.data, n, mid, len(mid), out.ctypes.data, cap)
    assert 0 <= wrote <= cap
    return out[:wrote].tobytes()


def _native_reprs(values):
    values = np.ascontiguousarray(values, np.float64)
    wrote = _series([b""] * values.shape[0], values)
    return [part[:-len(b"]]}")] for part in wrote.split(b",")]


def _uniform(rng):
    return np.concatenate([
        rng.uniform(-1.0, 1.0, 100_000), rng.uniform(-1e6, 1e6, 100_000),
        rng.uniform(0.0, 1e-3, 50_000), rng.uniform(-1e15, 1e18, 50_000)])


def _integral(rng):
    # counts, and counts over a 10 s interval, up to 1e17
    ints = np.floor(10.0 ** rng.uniform(0.0, 17.0, 150_000))
    return np.concatenate([ints, -ints[:10_000], ints[:50_000] / 10.0])


def _decimals(rng):
    return np.concatenate([
        np.round(rng.uniform(0.0, 1e4, 100_000), 1),
        np.round(rng.uniform(-1e3, 1e3, 100_000), 3),
        rng.integers(0, 10**9, 50_000) / 1e6])


def _bit_patterns(rng):
    # every exponent, NaN payloads and infinities among them
    return rng.integers(0, 2**64, 400_000, dtype=np.uint64).view(np.float64)


def _binades(_rng):
    # 2**k and both neighbours, k = -1074..1023: where the digit count
    # and the exponent's width change
    twos = np.ldexp(1.0, np.arange(-1074, 1024))
    return np.concatenate([twos, np.nextafter(twos, np.inf),
                           np.nextafter(twos, -np.inf), -twos])


def _decades(_rng):
    # 10**k as parsed, and both neighbours: repr's switch between fixed
    # and exponent notation lies at two of them
    tens = np.array([float(f"1e{k}") for k in range(-323, 309)])
    return np.concatenate([tens, np.nextafter(tens, np.inf),
                           np.nextafter(tens, -np.inf), -tens])


def _subnormals(rng):
    bits = rng.integers(1, 2**52, 20_000, dtype=np.uint64)
    return np.concatenate([bits.view(np.float64), -bits.view(np.float64),
                           np.arange(1, 2_000, dtype=np.uint64)
                           .view(np.float64)])


def _edge_cases(_rng):
    return np.array([
        0.0, -0.0, 1.0, -1.0, 0.1, 0.5, 1e16, 9999999999999998.0,
        1e-4, 1e-5, 0.00010000000000000002, 9.999999999999999e-05,
        1e22, 1e23, 1e21, 123456789012345680.0, 12345678901234567.0,
        math.nan, -math.nan, math.inf, -math.inf, 5e-324,
        2.2250738585072014e-308, 2.225073858507201e-308,
        1.7976931348623157e308, -1.7976931348623157e308,
        2.0 ** 53, 2.0 ** 53 + 2.0, 1 / 3, 2 / 3, 100.0, 1e2, 1e100,
        1.5e-7, 0.30000000000000004, 4.35, 33293.6])


@pytest.mark.parametrize("draw", [
    _uniform, _integral, _decimals, _bit_patterns, _binades, _decades,
    _subnormals, _edge_cases], ids=lambda f: f.__name__[1:])
def test_native_float_is_repr(draw):
    """Every value renders as `_json_num` renders it (`repr`, and
    json's spellings of the non-finite): 1.3 M values over the eight
    draws, a fixed seed."""
    values = draw(np.random.default_rng(SEED))
    got = _native_reprs(values)
    want = [_json_num(v).encode() for v in values.tolist()]
    wrong = [(w, g) for w, g in zip(want, got) if w != g]
    assert not wrong, (len(wrong), wrong[:5])
    assert len(got) == values.shape[0]


def test_the_draws_cover_a_million_values():
    rng = np.random.default_rng(SEED)
    assert sum(draw(rng).shape[0] for draw in (
        _uniform, _integral, _decimals, _bit_patterns, _binades, _decades,
        _subnormals, _edge_cases)) >= 1_000_000


def test_series_are_prefix_mid_value_joined_with_commas():
    prefixes = [b'{"metric":"a","tags":["x:y"', b'{"metric":"b","tags":[',
                b""]
    got = _series(prefixes, [1.0, -2.5, math.inf],
                  mid=b'],"points":[[1700000000,')
    assert got == (b'{"metric":"a","tags":["x:y"],"points":[[1700000000,1.0]]}'
                   b',{"metric":"b","tags":[],"points":[[1700000000,-2.5]]}'
                   b',],"points":[[1700000000,Infinity]]}')
    assert _series([], []) == b""


def test_series_refuses_a_buffer_without_room_for_the_longest_value():
    lib = native.load_series()
    values = np.array([-2.2250738585072014e-308, 1.0])
    offsets = np.zeros(3, np.int64)
    room = lib.vnt_dd_series_room(0)
    assert room >= len(repr(float(values[0]))) + len(b"]]},")
    out = np.empty(2 * room, np.uint8)
    beg, end = offsets.ctypes.data, offsets.ctypes.data + 8
    assert lib.vnt_dd_series(b"", beg, end, values.ctypes.data,
                             2, b"", 0, out.ctypes.data, 2 * room - 1) == -1
    assert lib.vnt_dd_series(b"", beg, end, values.ctypes.data,
                             2, b"", 0, out.ctypes.data, 2 * room) > 0


def test_changed_rows_compares_element_identity():
    lib = native.load_series()
    tags = [["a:b"], ["a:b"], [], ["c:d"]]   # equal lists, distinct objects
    names = ["n0", "n1", "n2", "n3"]
    kept_names, kept_tags = (np.array(x, object) for x in (names, [None] * 4))
    for i, t in enumerate(tags):
        kept_tags[i] = t
    new_names, new_tags = kept_names.copy(), kept_tags.copy()
    new_names[1] = "".join(["n", "1"])   # an equal str, another object
    new_tags[3] = list(tags[3])          # an equal list, another object
    changed = np.empty(4, np.int64)

    def compare(a_names, a_tags):
        n = lib.vnt_dd_changed_rows(
            a_names.ctypes.data, kept_names.ctypes.data,
            a_tags.ctypes.data, kept_tags.ctypes.data, 4,
            changed.ctypes.data)
        return changed[:n].tolist()

    assert compare(kept_names.copy(), kept_tags.copy()) == []
    assert compare(new_names, kept_tags) == [1]
    assert compare(new_names, new_tags) == [1, 3]


def test_series_read_prefixes_wherever_they_lie():
    """`beg`/`end` need not be one run of offsets: a row shelf's
    prefixes lie in any order, with other bytes between them."""
    lib = native.load_series()
    base = b"..B..A..."
    beg, end = np.array([5, 2], np.int64), np.array([6, 3], np.int64)
    values = np.array([1.0, 2.0])
    out = np.empty(128, np.uint8)
    wrote = lib.vnt_dd_series(base, beg.ctypes.data, end.ctypes.data,
                              values.ctypes.data, 2, b":", 1,
                              out.ctypes.data, 128)
    assert out[:wrote].tobytes() == b"A:1.0]]},B:2.0]]}"


def test_changed_at_compares_with_the_shelf_row_by_row():
    lib = native.load_series()
    kept_names = np.array([None, "n1", "n2", None, "n4"], object)
    kept_tags = np.empty(5, object)
    kept_tags[1:3], kept_tags[4] = [["a"], ["b"]], ["c"]
    rows = np.array([1, 2, 3, 4], np.int64)
    names = np.array([kept_names[1], "".join(["n", "2"]), "n3",
                      kept_names[4]], object)
    tags = np.empty(4, object)
    tags[0], tags[1], tags[2], tags[3] = (kept_tags[1], kept_tags[2], ["x"],
                                          list(kept_tags[4]))
    changed = np.empty(4, np.int64)
    n = lib.vnt_dd_changed_at(
        names.ctypes.data, tags.ctypes.data, kept_names.ctypes.data,
        kept_tags.ctypes.data, rows.ctypes.data, 4, changed.ctypes.data)
    # an equal str that is another object, a row never shelved, an equal
    # list that is another object
    assert changed[:n].tolist() == [1, 2, 3]


def test_the_library_is_a_cdll_not_a_pydll():
    """ctypes drops the GIL around a `CDLL`'s calls and keeps it around
    a `PyDLL`'s."""
    lib = native.load_series()
    assert type(lib) is ctypes.CDLL
    assert not isinstance(lib, ctypes.PyDLL)


def test_a_second_thread_runs_while_a_large_section_encodes():
    """While one call encodes a million series, a thread that spins
    in Python keeps counting: the call holds no GIL."""
    lib = native.load_series()
    n = 1_000_000
    rng = np.random.default_rng(SEED)
    values = rng.uniform(-1e6, 1e6, n)
    prefix = b'{"metric":"gil.series","type":"gauge","host":"me","tags":["k:v"'
    offsets = np.arange(n + 1, dtype=np.int64) * len(prefix)
    arena = prefix * n
    mid = b'],"points":[[1700000000,'
    cap = len(arena) + n * lib.vnt_dd_series_room(len(mid))
    out = np.empty(cap, np.uint8)
    ticks = [0]
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            ticks[0] += 1

    spinner = threading.Thread(target=spin, daemon=True, name="spinner")
    spinner.start()
    try:
        while ticks[0] == 0:
            time.sleep(0.001)
        before, t0 = ticks[0], time.perf_counter()
        wrote = lib.vnt_dd_series(
            arena, offsets.ctypes.data, offsets.ctypes.data + 8,
            values.ctypes.data, n, mid, len(mid), out.ctypes.data, cap)
        after, wall_s = ticks[0], time.perf_counter() - t0
    finally:
        stop.set()
        spinner.join(timeout=10.0)
    assert not spinner.is_alive()
    assert wrote > len(arena)
    # under the GIL the spinner would not run at all during the call
    # (the caller never gives it up); free, it counts all the while
    assert wall_s > 0.02, wall_s
    assert after - before > 1_000, (after - before, wall_s)

"""Columnar egress: wire encoders that consume FlushBatch arrays directly.

Every sink used to call `batch.materialize()` and loop `for m in metrics`
building one dict/proto/line at a time — at 100k keys that per-InterMetric
Python was the last measured wall (BENCH_r05: `counter` 9.3k/s vs `hll`
3.3M/s). The encoders here walk the FlushBatch sections instead:

* per-row byte fragments (the name/tag-dependent part of a series) are
  rendered ONCE per key lifetime and cached against the row's identity —
  the tags-list object ref that RowMeta shares with every FlushSection —
  so a steady-state flush pays only value formatting + `b"".join`;
* value columns format in bulk off the float64 arrays;
* llhist cumulative buckets ride the BucketSection's CSR entries (one
  per nonzero register, already cumulative) — no per-line recomputation.

Parity is pinned byte-for-byte against the legacy materialize() path by
tests/test_egress.py (JSON key-order-normalized for Datadog, byte-identical
for Prometheus exposition and Cortex remote-write wire); `extras` rows
(status checks, WAL backfill) keep the legacy per-metric rendering, which
also keeps exemplar/backfill clauses exact.
"""

from __future__ import annotations

import json
import struct
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from veneur_tpu.core.flusher import FlushBatch, le_tags
from veneur_tpu.samplers.metrics import InterMetric, MetricType

# fragment caches are bounded so a pathological tag churn can't grow a
# sink's cache without limit; at the cap the cache resets (one cold
# flush) rather than evicting piecemeal
FRAG_CACHE_CAP = 1 << 20
# a section's prefix arena is dropped once this many flushes in a row
# did without it: a quiet interval or two must not cost the next full
# one its arenas, and a section that is gone must not be kept for ever
ARENA_IDLE_FLUSHES = 2
# a section's prefixes by table row (`_RowShelf`) outlive its arenas: a
# warm-up or a quiet spell of several flushes must not cost the first
# full flush after it every prefix; a section gone this long is dropped
SHELF_IDLE_FLUSHES = 16
# a shelf lays its bytes anew once they pass twice its live prefixes
# and this much (rows rendered again leave their old bytes behind)
SHELF_SLACK_BYTES = 1 << 20

_MASK64 = (1 << 64) - 1
_INF = float("inf")


def _bulk_float_strs(values: np.ndarray) -> List[str]:
    """`str(v)` for every value — identical to the f-string/`json.dumps`
    rendering of the same python float (shortest-repr)."""
    return [repr(v) for v in values.tolist()]


def _json_num(v: float) -> str:
    """json.dumps' rendering of one float (Infinity/NaN spellings)."""
    if v == v and v != _INF and v != -_INF:
        return repr(v)
    if v != v:
        return "NaN"
    return "Infinity" if v > 0 else "-Infinity"


# --------------------------------------------------------------------------
# Datadog: series JSON by byte-assembly
# --------------------------------------------------------------------------


class DatadogColumnarEncoder:
    """`{"series": [...]}` body parts straight from FlushBatch columns.

    Per row the invariant JSON prefix — everything up to the inside of
    the `"tags"` array — is cached by `(name, id(tags), kind)`; the
    cache entry holds the tags-list ref so the id can't be recycled.
    A flush then appends `],"points":[[ts,value]]}` per row (buckets
    splice their `le:` tag into the open tags array first). Key order
    inside a series object differs from the legacy `_dd_metric` dict
    (tags rendered last); the parity suite compares key-order
    normalized, which is also the JSON object contract.

    `encode_bodies` does that per-row work for `batch.sections` in the
    native library (native/ddseries.cc), one GIL-free call per section
    slice of a body, from a prefix arena kept per section
    (`_SectionArena`); the Python loop is the fallback where the
    library cannot be had, and what `encode` runs: the reference the
    parity suite holds the native bytes to."""

    def __init__(self, sink):
        from veneur_tpu import native

        self.sink = sink
        # (name, id(tags), kind) -> (tags_ref, prefix_bytes|None, has_tags)
        self._frags: Dict[tuple, tuple] = {}
        # compiled if need be here, where the sink is built: never
        # inside a flush
        self._lib = native.load_series()
        # a section's arena, found again by the identity of its first
        # row: (id(name), id(tags), kind); the arena pins both objects
        self._arenas: Dict[tuple, _SectionArena] = {}
        # the prefixes of the sections that carry their table rows, by
        # row: where an arena misses, its rows are found here in bulk
        self._shelves: List[_RowShelf] = []
        self._flushes = 0  # `encode_bodies` calls: what arenas age by
        # of the last `encode_bodies`: series the native encoder wrote,
        # and section rows whose prefix came from `_frag` (arena misses;
        # on the Python loop, every row)
        self.native_rows = 0
        self.prefix_renders = 0
        # and its account: series put into bodies, and rows of the batch
        # that render to none (a dropped name prefix, a status check);
        # `len(batch)` is their sum, which the sink checks
        self.series_written = 0
        self.series_skipped = 0

    @property
    def name(self) -> str:
        """Which encoder `encode_bodies` runs: "native" or "python"."""
        return "python" if self._lib is None else "native"

    def _prefix(self, name: str, tags: list,
                is_counter: bool) -> Tuple[Optional[bytes], bool]:
        """The series-object bytes through the open tags array (no
        closing `]}`), or None when the metric's name prefix drops it."""
        sink = self.sink
        if sink.metric_name_prefix_drops and any(
                name.startswith(p) for p in sink.metric_name_prefix_drops):
            return None, False
        out_tags = list(sink.tags)
        host = sink.hostname
        device = ""
        per_metric_excludes = ()
        for prefix, excludes in \
                sink.exclude_tags_prefix_by_prefix_metric.items():
            if name.startswith(prefix):
                per_metric_excludes = excludes
                break
        for t in tags:
            if t.startswith("host:"):
                host = t[5:]
            elif t.startswith("device:"):
                device = t[7:]
            elif (any(t.startswith(p) for p in sink.excluded_tag_prefixes)
                  or any(t.startswith(p) for p in per_metric_excludes)):
                continue
            else:
                out_tags.append(t)
        head = {
            "metric": name,
            "type": "rate" if is_counter else "gauge",
            "host": host,
            "interval": int(sink.interval) or 1,
        }
        if device:
            head["device"] = device
        head["tags"] = out_tags
        enc = json.dumps(head, separators=(",", ":")).encode()
        return enc[:-2], bool(out_tags)  # strip the tags-closing `]}`

    def _frag(self, name: str, tags: list, is_counter: bool):
        key = (name, id(tags), is_counter)
        ent = self._frags.get(key)
        if ent is None:
            if len(self._frags) >= FRAG_CACHE_CAP:
                self._frags.clear()
            prefix, has_tags = self._prefix(name, tags, is_counter)
            ent = self._frags[key] = (tags, prefix, has_tags)
        return ent

    def encode(self, batch: FlushBatch) -> Tuple[List[bytes],
                                                 List[InterMetric]]:
        """-> (series body parts, one per series, from the Python loop;
        status checks). Joining parts with b"," inside `{"series":[...]}`
        is the POST body."""
        return self._encode_bodies(batch, sys.maxsize, None, None)

    def encode_bodies(self, batch: FlushBatch, per_body: int,
                      emit) -> Tuple[List[bytes], List[InterMetric]]:
        """`encode`, handing the series over body by body while it
        runs: `emit(parts)` receives each run of `per_body` series as
        soon as one series more exists, so whatever is emitted has a
        successor. -> (the last 1..`per_body` series, status checks); a
        batch of at most `per_body` series emits nothing. A part is one
        series or, from the native encoder, a run of them joined with
        b"," (a view of the buffer it wrote): emitted runs and the
        returned rest, each joined with b",", are `encode`'s parts cut
        every `per_body` and joined."""
        return self._encode_bodies(batch, per_body, emit, self._lib)

    def _encode_bodies(self, batch: FlushBatch, per_body: int, emit,
                       lib) -> Tuple[List[bytes], List[InterMetric]]:
        sink = self.sink
        cut = _BodyCut(per_body, emit)
        checks: List[InterMetric] = []
        # what follows a series' prefix: `],"points":[[<ts>,<value>]]}`
        mid = b'],"points":[[%d,' % batch.timestamp
        interval = sink.interval
        self.native_rows = self.prefix_renders = 0
        self.series_skipped = 0
        self._flushes += 1
        for sec in batch.sections:
            is_counter = sec.mtype == MetricType.COUNTER
            vals = sec.values / interval if is_counter else sec.values
            if lib is None:
                self._section_python(sec, is_counter, vals, mid, cut)
            else:
                self._section_native(lib, sec, is_counter, vals, mid, cut)
        for key in [key for key, arena in self._arenas.items()
                    if arena.used + ARENA_IDLE_FLUSHES < self._flushes]:
            del self._arenas[key]
        self._shelves = [shelf for shelf in self._shelves
                         if shelf.used + SHELF_IDLE_FLUSHES >= self._flushes]
        if batch.bucket_sections:
            les = _dd_le_json()
            for bs in batch.bucket_sections:
                for nm, tags, idxs, values in bs.rows(interval):
                    _tags, prefix, has_tags = self._frag(nm, tags, True)
                    if prefix is None:
                        self.series_skipped += len(idxs)
                        continue
                    head = prefix + b"," if has_tags else prefix
                    for k, v in zip(idxs, values):
                        cut.add(head + les[k] + mid
                                + _json_num(v).encode() + b"]]}")
        for m in batch.extras:
            if sink.metric_name_prefix_drops and any(
                    m.name.startswith(p)
                    for p in sink.metric_name_prefix_drops):
                self.series_skipped += 1
                continue
            if m.type == MetricType.STATUS:
                self.series_skipped += 1
                checks.append(m)
            else:
                cut.add(json.dumps(
                    sink._dd_metric(m), separators=(",", ":")).encode())
        self.series_written = cut.total
        return cut.parts, checks

    def _section_python(self, sec, is_counter: bool, vals: np.ndarray,
                        mid: bytes, cut: "_BodyCut") -> None:
        """One section's series, a part each: the loop the native
        encoder's bytes are held to."""
        finite = np.isfinite(vals).all()
        vals = vals.tolist()
        names = sec.names.tolist()
        tagrows = sec.tags.tolist()
        frag = self._frag
        self.prefix_renders += len(names)
        lo = 0
        while lo < len(names):
            # a row adds at most one part: up to one past the cut
            hi = lo + cut.per_body + 1 - cut.held
            if finite:
                val_strs = [repr(v).encode() for v in vals[lo:hi]]
            else:
                val_strs = [_json_num(v).encode() for v in vals[lo:hi]]
            parts: List[bytes] = []
            for nm, tags, val in zip(names[lo:hi], tagrows[lo:hi],
                                     val_strs):
                _tags, prefix, _ht = frag(nm, tags, is_counter)
                if prefix is None:
                    self.series_skipped += 1
                    continue
                parts.append(prefix + mid + val + b"]]}")
            lo = hi
            cut.extend(parts)

    def _section_native(self, lib, sec, is_counter: bool,
                        vals: np.ndarray, mid: bytes,
                        cut: "_BodyCut") -> None:
        """One section's series through `vnt_dd_series`, a call per
        slice that fits the body being cut: `mid` is the flush's
        `],"points":[[<ts>,` fragment."""
        if not sec.names.shape[0]:
            return
        arena, n = self._arena(lib, sec, is_counter)
        self.series_skipped += sec.names.shape[0] - n
        if arena.rendered is not None:
            vals = vals[arena.rendered[:n]]
        vals = np.ascontiguousarray(vals, np.float64)
        offsets = arena.offsets
        room = lib.vnt_dd_series_room(len(mid))
        lo = 0
        while lo < n:
            k = min(cut.room(), n - lo)
            cap = int(offsets[lo + k] - offsets[lo]) + k * room
            out = np.empty(cap, np.uint8)
            wrote = lib.vnt_dd_series(
                arena.ptr, arena.beg.ctypes.data + 8 * lo,
                arena.end.ctypes.data + 8 * lo,
                vals.ctypes.data + 8 * lo, k, mid, len(mid),
                out.ctypes.data, cap)
            if wrote < 0:
                raise RuntimeError(
                    f"vnt_dd_series: {cap} bytes cannot hold {k} series")
            cut.add(memoryview(out)[:wrote], k)
            lo += k
        self.native_rows += n

    def _arena(self, lib, sec,
               is_counter: bool) -> Tuple["_SectionArena", int]:
        """-> (the section's prefix arena, how many of its series the
        section has). The arena is the kept one if every row is still
        the same `str` and the same tags list as then (the identity
        `_frags` keys on; the kept arrays pin the objects, so an
        address cannot have been recycled), also where the section is
        only the kept one's first rows (keys at its end did not
        report); else it is rebuilt: from the section's row shelf where
        the section has its rows (a key that came or went, or a partial
        flush, costs only the rows never rendered), else from the kept
        arena by position; the rows that differ are looked up through
        `_frag`."""
        names = np.ascontiguousarray(sec.names)
        tags = np.ascontiguousarray(sec.tags)
        n = names.shape[0]
        key = (id(names[0]), id(tags[0]), is_counter)
        kept = self._arenas.get(key)
        if kept is not None:
            same = min(n, kept.names.shape[0])
            changed = np.empty(same, np.int64)
            n_changed = lib.vnt_dd_changed_rows(
                names.ctypes.data, kept.names.ctypes.data,
                tags.ctypes.data, kept.tags.ctypes.data,
                same, changed.ctypes.data)
            if n_changed == 0 and n == same:
                kept.used = self._flushes
                if kept.shelf is not None:
                    kept.shelf.used = self._flushes
                return kept, kept.series_among(n)
        if sec.rows is not None:
            arena = self._shelved(lib, sec, names, tags, is_counter)
        else:
            if kept is None or kept.prefixes is None:
                miss = range(n)
                prefixes = [None] * n
            else:
                miss = changed[:n_changed].tolist() + list(range(same, n))
                prefixes = kept.prefixes[:n] + [None] * (n - same)
            for i in miss:
                prefixes[i] = self._frag(names[i], tags[i], is_counter)[1]
            self.prefix_renders += len(miss)
            arena = _SectionArena(names, tags, prefixes, self._flushes)
        self._arenas[key] = arena
        return arena, arena.offsets.shape[0] - 1

    def _shelved(self, lib, sec, names: np.ndarray, tags: np.ndarray,
                 is_counter: bool) -> "_SectionArena":
        """The section's arena from its row shelf: the shelf whose rows
        hold the section's first or last series (same `str`, same type),
        else a new one. A row whose name or tags list is another object
        than the shelf's (never rendered, recycled to another key,
        re-tagged) is looked up through `_frag` and shelved; the others
        cost no Python: the arena reads their prefixes where the shelf
        keeps them."""
        rows = np.ascontiguousarray(sec.rows, np.int64)
        n = rows.shape[0]
        shelf = next((s for s in self._shelves
                      if s.is_counter == is_counter
                      and (s.holds(int(rows[0]), names[0])
                           or s.holds(int(rows[-1]), names[n - 1]))), None)
        if shelf is None:
            shelf = _RowShelf(is_counter)
            self._shelves.append(shelf)
        shelf.used = self._flushes
        shelf.reserve(int(rows.max()) + 1)
        changed = np.empty(n, np.int64)
        n_changed = lib.vnt_dd_changed_at(
            names.ctypes.data, tags.ctypes.data, shelf.names.ctypes.data,
            shelf.tags.ctypes.data, rows.ctypes.data, n, changed.ctypes.data)
        if n_changed:
            miss = changed[:n_changed]
            shelf.put(rows[miss], names[miss], tags[miss], [
                self._frag(names[i], tags[i], is_counter)[1]
                for i in miss.tolist()])
            self.prefix_renders += int(n_changed)
        size = shelf.size[rows]
        beg = shelf.beg[rows]
        rendered = None
        if (size < 0).any():
            rendered = np.flatnonzero(size >= 0)
            size, beg = size[rendered], beg[rendered]
        return _SectionArena.laid(names, tags, shelf, beg, beg + size,
                                  rendered, self._flushes)


class _RowShelf:
    """One section's prefixes by table row, kept across flushes (rows
    ascend in a section, `FlushSection.rows`): the `names` and `tags`
    objects each row rendered (None: never), which pin them, so an
    address cannot have been recycled; the row's prefix, and where its
    bytes lie in `data` (`beg`, `size`; size -1 where the sink's name
    prefixes drop the row). A row rendered again appends; `data` is
    laid anew when it holds more than twice the live bytes. `used` is
    the flush that last read it."""

    __slots__ = ("is_counter", "names", "tags", "prefixes", "beg", "size",
                 "data", "filled", "live", "used")

    def __init__(self, is_counter: bool):
        self.is_counter = is_counter
        self.names = np.empty(0, object)
        self.tags = np.empty(0, object)
        self.prefixes = np.empty(0, object)
        self.beg = np.zeros(0, np.int64)
        self.size = np.full(0, -1, np.int64)
        self.data = np.empty(0, np.uint8)
        self.filled = 0  # bytes of `data` written
        self.live = 0    # of those, the rows' current prefixes
        self.used = 0

    def holds(self, row: int, name: str) -> bool:
        return row < self.names.shape[0] and self.names[row] is name

    def reserve(self, rows: int) -> None:
        have = self.names.shape[0]
        if rows <= have:
            return
        rows = max(rows, 2 * have)
        for attr, fill in (("names", None), ("tags", None),
                           ("prefixes", None), ("beg", 0), ("size", -1)):
            old = getattr(self, attr)
            grown = np.full(rows, fill, old.dtype)
            grown[:have] = old
            setattr(self, attr, grown)

    def put(self, rows: np.ndarray, names: np.ndarray, tags: np.ndarray,
            prefixes: List[Optional[bytes]]) -> None:
        """Shelves the rows' objects and new prefixes (None: dropped)."""
        self.live -= int(np.maximum(self.size[rows], 0).sum())
        self.names[rows], self.tags[rows] = names, tags
        fresh = np.empty(len(prefixes), object)
        fresh[:] = prefixes
        self.prefixes[rows] = fresh
        size = np.fromiter((-1 if p is None else len(p) for p in prefixes),
                           np.int64, len(prefixes))
        self.size[rows] = size
        blob = b"".join(p for p in prefixes if p is not None)
        self.live += len(blob)
        if self.filled + len(blob) > 2 * self.live + SHELF_SLACK_BYTES:
            self._lay()   # the rows' older bytes are mostly garbage
            return
        at = self.filled + np.cumsum(np.maximum(size, 0)) - np.maximum(size, 0)
        self.beg[rows] = at
        self._append(blob)

    def _append(self, blob: bytes) -> None:
        end = self.filled + len(blob)
        if end > self.data.shape[0]:
            # a new array: arenas laid on the old one keep it
            grown = np.empty(max(end, 2 * self.data.shape[0]), np.uint8)
            grown[:self.filled] = self.data[:self.filled]
            self.data = grown
        self.data[self.filled:end] = np.frombuffer(blob, np.uint8)
        self.filled = end

    def _lay(self) -> None:
        """`data` anew from the live prefixes alone, in row order."""
        live = np.flatnonzero(self.size >= 0)
        sizes = self.size[live]
        self.beg[live] = np.cumsum(sizes) - sizes
        self.data = np.empty(0, np.uint8)
        self.filled = 0
        self._append(b"".join(self.prefixes[live].tolist()))
        self.live = self.filled


class _SectionArena:
    """What `encode_bodies` keeps of one section between flushes: the
    `names` and `tags` arrays it rendered (their elements are the
    column store's cached objects, the same for a row's lifetime), each
    row's prefix (None: dropped by the sink's name prefixes), and the
    prefixes of the rows that render, row `j` of them at
    `arena[beg[j]:end[j]]` (`ptr` is the address `vnt_dd_series`
    reads); `offsets` sums their sizes. `rendered` indexes those rows in
    the section, None when all render; `used` is the flush that last
    encoded from it. Built from `prefixes`, the arena lays them back to
    back; one `laid` on a row `shelf` keeps no `prefixes` (None) and
    reads the shelf's bytes."""

    __slots__ = ("names", "tags", "prefixes", "arena", "ptr", "beg", "end",
                 "offsets", "rendered", "used", "shelf")

    def __init__(self, names: np.ndarray, tags: np.ndarray,
                 prefixes: List[Optional[bytes]], used: int):
        self.names = names
        self.tags = tags
        self.prefixes = prefixes
        self.used = used
        self.shelf = None
        kept = [p for p in prefixes if p is not None]
        self.rendered = None
        if len(kept) != len(prefixes):
            self.rendered = np.fromiter(
                (i for i, p in enumerate(prefixes) if p is not None),
                np.int64, len(kept))
        self.arena = self.ptr = b"".join(kept)
        self.offsets = np.zeros(len(kept) + 1, np.int64)
        np.cumsum(np.fromiter(map(len, kept), np.int64, len(kept)),
                  out=self.offsets[1:])
        self.beg, self.end = self.offsets[:-1], self.offsets[1:]

    @classmethod
    def laid(cls, names: np.ndarray, tags: np.ndarray, shelf: "_RowShelf",
             beg: np.ndarray, end: np.ndarray, rendered: Optional[np.ndarray],
             used: int) -> "_SectionArena":
        """An arena of the rendering rows' prefixes where `shelf` keeps
        them, `shelf.data[beg[j]:end[j]]`. It holds that array: a shelf
        that grows or lays its bytes anew takes another, and appends
        only past what is written."""
        self = cls.__new__(cls)
        self.names, self.tags, self.prefixes = names, tags, None
        self.shelf = shelf
        self.used, self.rendered = used, rendered
        self.arena, self.ptr = shelf.data, shelf.data.ctypes.data
        self.beg, self.end = beg, end
        self.offsets = np.zeros(beg.shape[0] + 1, np.int64)
        np.cumsum(end - beg, out=self.offsets[1:])
        return self

    def series_among(self, rows: int) -> int:
        """How many of the first `rows` rows render."""
        if self.rendered is None:
            return rows
        return int(np.searchsorted(self.rendered, rows))


class _BodyCut:
    """Cuts a flush's series into bodies of `per_body`: `parts` holds
    the body being filled (`held` series in it), and a full one is
    handed to `emit` only when a series more is about to join, so
    whatever is emitted has a successor."""

    __slots__ = ("per_body", "emit", "parts", "held", "total")

    def __init__(self, per_body: int, emit):
        self.per_body = per_body
        self.emit = emit
        self.parts: List[bytes] = []
        self.held = 0
        self.total = 0  # series added so far, over all bodies

    def room(self) -> int:
        """How many series the body being filled still takes; a full
        one is handed over first (the caller has a series to add)."""
        if self.held >= self.per_body:
            self.emit(self.parts)
            self.parts, self.held = [], 0
        return self.per_body - self.held

    def add(self, part: bytes, series: int = 1) -> None:
        """`part`: `series` of them joined with b",", at most `room()`."""
        self.room()
        self.parts.append(part)
        self.held += series
        self.total += series

    def extend(self, parts: List[bytes]) -> None:
        """`add` for a run of parts of one series each."""
        while parts:
            taken = parts[:self.room()]
            self.parts += taken
            self.held += len(taken)
            self.total += len(taken)
            parts = parts[len(taken):]


_DD_LE_JSON: Optional[List[bytes]] = None


def _dd_le_json() -> List[bytes]:
    global _DD_LE_JSON
    if _DD_LE_JSON is None:
        _DD_LE_JSON = [json.dumps(t).encode() for t in le_tags()]
    return _DD_LE_JSON


# --------------------------------------------------------------------------
# Prometheus: exposition text
# --------------------------------------------------------------------------


class PrometheusColumnarRenderer:
    """render_exposition, but off FlushBatch columns — byte-identical
    output (pinned by tests/test_egress.py). Caches the sanitized name
    per metric name and the rendered label interior per tags-list
    identity; section rows are never backfilled, so only `extras` pay
    the per-metric stamp/exemplar logic of the legacy renderer."""

    def __init__(self):
        self._names: Dict[str, str] = {}
        self._labels: Dict[int, tuple] = {}  # id(tags) -> (ref, interior)

    def _name(self, name: str) -> str:
        out = self._names.get(name)
        if out is None:
            from veneur_tpu.sinks.cortex import sanitize_name
            if len(self._names) >= FRAG_CACHE_CAP:
                self._names.clear()
            out = self._names[name] = sanitize_name(name)
        return out

    def _label_interior(self, tags: list) -> str:
        ent = self._labels.get(id(tags))
        if ent is None:
            from veneur_tpu.sinks.cortex import sanitize_label
            from veneur_tpu.sinks.prometheus import escape_label_value
            if len(self._labels) >= FRAG_CACHE_CAP:
                self._labels.clear()
            parts = []
            for t in tags:
                k, _, v = t.partition(":")
                parts.append(
                    f'{sanitize_label(k)}="{escape_label_value(v)}"')
            ent = self._labels[id(tags)] = (tags, ",".join(parts))
        return ent[1]

    def render(self, batch: FlushBatch, exemplars=None,
               openmetrics: bool = False) -> str:
        from veneur_tpu.sinks.prometheus import exemplar_clause_for

        lines: List[str] = []
        exemplified: set = set()
        for sec in batch.sections:
            names = sec.names.tolist()
            tagrows = sec.tags.tolist()
            val_strs = _bulk_float_strs(sec.values)
            check_ex = (exemplars is not None
                        and sec.mtype == MetricType.COUNTER)
            for i, nm in enumerate(names):
                interior = self._label_interior(tagrows[i])
                label_str = "{" + interior + "}" if interior else ""
                clause = ""
                if check_ex:
                    clause = exemplar_clause_for(
                        _ExemplarProbe(nm, tagrows[i]),
                        exemplars, exemplified)
                lines.append(f"{self._name(nm)}{label_str} "
                             f"{val_strs[i]}{clause}")
        if batch.bucket_sections:
            les = _prom_le_labels()
            le_tag_strs = le_tags()
            for bs in batch.bucket_sections:
                for nm, tags, idxs, values in bs.rows():
                    sname = self._name(nm)
                    interior = self._label_interior(tags)
                    pre = "{" + interior + "," if interior else "{"
                    for k, v in zip(idxs, values):
                        clause = ""
                        if exemplars is not None:
                            clause = exemplar_clause_for(
                                _ExemplarProbe(nm, tags + [le_tag_strs[k]]),
                                exemplars, exemplified)
                        lines.append(f"{sname}{pre}{les[k]}}} "
                                     f"{v}{clause}")
        for m in batch.extras:
            if m.type == MetricType.STATUS:
                continue
            interior = self._label_interior(m.tags)
            label_str = "{" + interior + "}" if interior else ""
            clause = exemplar_clause_for(m, exemplars, exemplified)
            if m.backfilled:
                stamp = (f" {int(m.timestamp)}" if openmetrics
                         else f" {int(m.timestamp) * 1000}")
            else:
                stamp = ""
            lines.append(f"{self._name(m.name)}{label_str} {m.value}"
                         f"{stamp}{clause}")
        return "\n".join(lines) + ("\n" if lines else "")


class _ExemplarProbe:
    """Duck-typed COUNTER InterMetric for exemplar_clause_for (the
    clause logic only reads name/tags/type)."""

    __slots__ = ("name", "tags")
    type = MetricType.COUNTER

    def __init__(self, name: str, tags: list):
        self.name = name
        self.tags = tags


_PROM_LE: Optional[List[str]] = None


def _prom_le_labels() -> List[str]:
    """`le="<bound>"` rendered label per sorted bin (+Inf last) —
    bounds never contain escapable characters."""
    global _PROM_LE
    if _PROM_LE is None:
        _PROM_LE = [f'le="{t.partition(":")[2]}"' for t in le_tags()]
    return _PROM_LE


# --------------------------------------------------------------------------
# Cortex: remote-write protobuf TimeSeries frames
# --------------------------------------------------------------------------


class CortexColumnarEncoder:
    """WriteRequest TimeSeries frames hand-packed from FlushBatch
    columns, byte-identical to `_series` + `encode_write_request`
    (pinned by tests/test_egress.py). The sorted Label block per row
    caches against (name, tags identity); samples assemble from the
    bulk little-endian float64 dump of the value column plus one
    precomputed timestamp varint. Bucket rows cache the label block
    split at the `le` insertion point so every bin line is two joins.

    Returns the series FRAMES (field-1 bytes); concatenating a chunk of
    frames IS encode_write_request's output for that chunk, so the
    sink's batch_write_size chunking and snappy+POST stay unchanged."""

    def __init__(self, sink):
        self.sink = sink
        self._blocks: Dict[tuple, tuple] = {}   # (name,id) -> (ref, block)
        self._bucket_blocks: Dict[tuple, tuple] = {}  # -> (ref, pre, post)

    def _label_items(self, name: str, tags: list) -> List[tuple]:
        from veneur_tpu.sinks.cortex import sanitize_label, sanitize_name

        sink = self.sink
        labels = {"__name__": sanitize_name(name)}
        for t in tags:
            k, _, v = t.partition(":")
            if k in sink.excluded_tags:
                continue
            labels[sanitize_label(k)] = v  # last write wins on dupes
        if sink.hostname:  # section rows carry no per-metric hostname
            labels.setdefault("host", sink.hostname)
        return sorted(labels.items())

    def _block(self, name: str, tags: list) -> bytes:
        key = (name, id(tags))
        ent = self._blocks.get(key)
        if ent is None:
            from veneur_tpu.sinks.cortex import _encode_label, _field_bytes
            if len(self._blocks) >= FRAG_CACHE_CAP:
                self._blocks.clear()
            block = b"".join(_field_bytes(1, _encode_label(k, v))
                             for k, v in self._label_items(name, tags))
            ent = self._blocks[key] = (tags, block)
        return ent[1]

    def _bucket_block(self, name: str, tags: list) -> Tuple[bytes, bytes]:
        """(pre, post) label-block halves around the sorted insertion
        point of the `le` label; a base `le:` tag is dropped here
        because the bucket's own le label overwrites it (legacy: the
        appended le tag wins last-write in the labels dict)."""
        key = (name, id(tags))
        ent = self._bucket_blocks.get(key)
        if ent is None:
            from veneur_tpu.sinks.cortex import _encode_label, _field_bytes
            if len(self._bucket_blocks) >= FRAG_CACHE_CAP:
                self._bucket_blocks.clear()
            items = [kv for kv in self._label_items(name, tags)
                     if kv[0] != "le"]
            idx = 0
            while idx < len(items) and items[idx][0] < "le":
                idx += 1
            pre = b"".join(_field_bytes(1, _encode_label(k, v))
                           for k, v in items[:idx])
            post = b"".join(_field_bytes(1, _encode_label(k, v))
                            for k, v in items[idx:])
            ent = self._bucket_blocks[key] = (tags, pre, post)
        return ent[1], ent[2]

    def encode(self, batch: FlushBatch) -> Tuple[List[bytes], int]:
        """-> (TimeSeries frames in legacy order, max metric timestamp
        seen). The max-timestamp fold rides the encode pass (the legacy
        flush re-scanned every metric for it in monotonic mode)."""
        from veneur_tpu.sinks.cortex import (
            _encode_exemplar, _field_bytes, _varint, encode_write_request,
        )

        sink = self.sink
        frames: List[bytes] = []
        exemplified: set = set()
        max_ts = 0
        ts = batch.timestamp
        ts_tail = b"\x10" + _varint((ts * 1000) & _MASK64)
        sample_len = 9 + len(ts_tail)
        sample_hdr = b"\x12" + _varint(sample_len)
        mono = sink.convert_counters_to_monotonic
        check_ex = sink._exemplars is not None
        monotonic = sink._monotonic
        for sec in batch.sections:
            n = sec.names.shape[0]
            if n == 0:
                continue
            if ts > max_ts:
                max_ts = ts
            is_counter = sec.mtype == MetricType.COUNTER
            names = sec.names.tolist()
            tagrows = sec.tags.tolist()
            if is_counter and mono:
                for nm, tg, v in zip(names, tagrows,
                                     sec.values.tolist()):
                    key = (nm, tuple(sorted(tg)), "")
                    monotonic[key] = monotonic.get(key, 0.0) + v
                continue
            vb = sec.values.astype("<f8").tobytes()
            row_ex = check_ex and is_counter
            for i, nm in enumerate(names):
                body = (self._block(nm, tagrows[i]) + sample_hdr
                        + b"\x09" + vb[8 * i:8 * i + 8] + ts_tail)
                if row_ex:
                    ex = self._exemplar(nm, tagrows[i], exemplified)
                    if ex is not None:
                        body += _field_bytes(3, _encode_exemplar(*ex))
                frames.append(b"\x0a" + _varint(len(body)) + body)
        if batch.bucket_sections:
            les = _cortex_le_labels()
            le_strs = le_tags()
            for bs in batch.bucket_sections:
                if bs.names.shape[0] and ts > max_ts:
                    max_ts = ts
                for nm, tags, idxs, values in bs.rows():
                    if mono:
                        for k, v in zip(idxs, values):
                            key = (nm, tuple(sorted(tags + [le_strs[k]])),
                                   "")
                            monotonic[key] = monotonic.get(key, 0.0) + v
                        continue
                    pre, post = self._bucket_block(nm, tags)
                    vrow = struct.pack("<%dd" % len(values), *values)
                    for j, k in enumerate(idxs):
                        body = (pre + les[k] + post + sample_hdr + b"\x09"
                                + vrow[8 * j:8 * j + 8] + ts_tail)
                        if check_ex:
                            ex = self._exemplar(
                                nm, tags + [le_strs[k]], exemplified)
                            if ex is not None:
                                body += _field_bytes(
                                    3, _encode_exemplar(*ex))
                        frames.append(b"\x0a" + _varint(len(body)) + body)
        for m in batch.extras:
            if m.timestamp > max_ts:
                max_ts = m.timestamp
            if m.type == MetricType.STATUS:
                continue
            if m.type == MetricType.COUNTER and mono:
                key = (m.name, tuple(sorted(m.tags)), m.hostname)
                monotonic[key] = monotonic.get(key, 0.0) + float(m.value)
                continue
            row = sink._series(m)
            entry = sink._exemplar_entry(m, exemplified)
            if entry is not None:
                from veneur_tpu.trace.store import trace_id_hex
                tid, ev, ets = entry
                row = row + ((trace_id_hex(tid), float(ev),
                              int(ets * 1000)),)
            frames.append(encode_write_request([row]))
        return frames, max_ts

    def _exemplar(self, name: str, tags: list, exemplified: set):
        """sink._exemplar_entry for a columnar COUNTER row, converted
        to _encode_exemplar's argument tuple."""
        entry = self.sink._exemplar_entry(
            _ExemplarProbe(name, tags), exemplified)
        if entry is None:
            return None
        from veneur_tpu.trace.store import trace_id_hex
        tid, ev, ets = entry
        return trace_id_hex(tid), float(ev), int(ets * 1000)


_CORTEX_LE: Optional[List[bytes]] = None


def _cortex_le_labels() -> List[bytes]:
    global _CORTEX_LE
    if _CORTEX_LE is None:
        from veneur_tpu.sinks.cortex import _encode_label, _field_bytes
        _CORTEX_LE = [
            _field_bytes(1, _encode_label("le", t.partition(":")[2]))
            for t in le_tags()]
    return _CORTEX_LE

"""Golden byte-parity for the columnar egress plane (core/egress.py).

The columnar encoders must emit exactly what the legacy per-InterMetric
paths emit for the SAME FlushBatch — byte-identical for Prometheus
exposition and Cortex remote-write wire, JSON key-order-normalized for
Datadog (the series-object key order legitimately differs; JSON objects
are unordered). The batches come from the real flusher over a mixed
corpus so every family is covered: counters, gauges, timer percentile
gauges + aggregate counters, set-cardinality gauges, and llhist
percentile/sum/count plus the cumulative `.bucket{le:}` matrix.
`extras` add the legacy-only shapes: status checks, hostname-carrying
rows, and WAL-backfilled timestamp lines.
"""

from __future__ import annotations

import dataclasses
import json
import struct
import sys
import threading

import numpy as np
import pytest

from veneur_tpu.core.columnstore import ColumnStore
from veneur_tpu.core.egress import (
    CortexColumnarEncoder, DatadogColumnarEncoder,
    PrometheusColumnarRenderer,
)
from veneur_tpu.core.flusher import (
    flush_columnstore, flush_columnstore_batch)
from veneur_tpu.ops import llhist_ref
from veneur_tpu.samplers.metrics import (
    HistogramAggregates, InterMetric, MetricType,
)
from veneur_tpu.samplers.parser import Parser
from veneur_tpu.sinks.cortex import CortexMetricSink, encode_write_request
from veneur_tpu.sinks.datadog import DatadogMetricSink
from veneur_tpu.sinks.prometheus import (
    PrometheusMetricSink, render_exposition,
)

pytestmark = pytest.mark.egress

PCTS = (0.5, 0.99)
AGGS = HistogramAggregates.from_names(["min", "max", "count"])


def _mk_batch(extras=(), is_local=False):
    # global mode by default: mixed-scope llhists EMIT (bucket sections
    # in the batch) instead of forwarding; forward tests pass True
    store = ColumnStore(counter_capacity=64, gauge_capacity=64,
                        histo_capacity=64, set_capacity=32, batch_cap=256)
    p = Parser()
    lines = []
    for i in range(5):
        lines.append(b"c.%d:%d|c|#env:t,i:%d" % (i, i + 1, i))
        lines.append(b"g.%d:%.2f|g|#env:t" % (i, i * 1.5))
        lines.append(b"t.%d:%.2f|ms|#env:t" % (i, 10.0 + i))
        lines.append(b"t.%d:%.2f|ms|#env:t" % (i, 20.0 + i))
        lines.append(b"s.%d:user%d|s|#env:t" % (i, i))
        lines.append(b"ll.%d:%.3f|l|#env:t,svc:x" % (i, 5.0 + i))
        lines.append(b"ll.%d:%.3f|l|#env:t,svc:x" % (i, 500.0 + i))
    # tag-free rows, host:/device: magic tags, drop-prefix candidates
    lines += [
        b"bare:3|c",
        b"hosted:4|c|#host:other,device:sda,env:t",
        b"dropme.x:1|c|#env:t",
        b"ll.bare:42.5|l",
    ]
    for line in lines:
        p.parse_metric_fast(line, store.process)
    store.apply_all_pending()
    batch, fwd = flush_columnstore_batch(store, is_local, PCTS, AGGS,
                                         collect_forward=is_local)
    batch.extras.extend(extras)
    return batch, fwd


def _extras():
    return [
        InterMetric(name="extra.count", timestamp=1700000000, value=4.0,
                    tags=["q:r"], type=MetricType.COUNTER, hostname="hX"),
        InterMetric(name="svc.ok", timestamp=1700000001, value=1.0,
                    tags=["chk:y"], type=MetricType.STATUS,
                    hostname="hX", message="degraded"),
        InterMetric(name="backfill.g", timestamp=1699990000, value=7.5,
                    tags=["o:p"], type=MetricType.GAUGE, hostname="hB",
                    backfilled=True),
        InterMetric(name="backfill.c", timestamp=1699990000, value=2.0,
                    tags=[], type=MetricType.COUNTER, backfilled=True),
    ]


def _dd_sink(**kw):
    kw.setdefault("tags", ["glob:t"])
    kw.setdefault("metric_name_prefix_drops", ["dropme."])
    kw.setdefault("excluded_tag_prefixes", ["i:"])
    return DatadogMetricSink("datadog", "key", "https://dd.example", "me",
                             10.0, **kw)


# -- Datadog ---------------------------------------------------------------


def test_datadog_parity_normalized():
    batch, _ = _mk_batch(_extras())
    sink = _dd_sink()
    parts, checks = DatadogColumnarEncoder(sink).encode(batch)
    col = [json.loads(p) for p in parts]
    leg = json.loads(json.dumps([
        sink._dd_metric(m) for m in batch.materialize()
        if m.type != MetricType.STATUS
        and not m.name.startswith("dropme.")]))
    assert col == leg  # same objects in the same ORDER
    assert [c.name for c in checks] == ["svc.ok"]


def test_datadog_flush_columnar_posts_same_series(monkeypatch):
    """End to end through flush_batch: the raw byte-assembled bodies
    decode to the same series the legacy dict+json.dumps flush posts."""
    from veneur_tpu.sinks import datadog as ddmod

    posted = []

    def fake_post(url, body, **kw):
        # vhttp.post gzips internally; the fake sees the raw body
        posted.append((url, bytes(body)))

    def fake_post_json(url, payload, **kw):
        posted.append((url, json.dumps(payload).encode()))

    monkeypatch.setattr(ddmod.vhttp, "post", fake_post)
    monkeypatch.setattr(ddmod.vhttp, "post_json", fake_post_json)
    batch, _ = _mk_batch(_extras())
    sink = _dd_sink(num_workers=1)
    sink.flush_batch(batch)
    col_series = [json.loads(b)["series"] for u, b in posted
                  if "/series" in u]
    col_checks = [json.loads(b) for u, b in posted if "check_run" in u]
    posted.clear()
    sink2 = _dd_sink(num_workers=1)
    sink2.flush(batch.materialize())
    leg_series = [json.loads(b)["series"] for u, b in posted
                  if "/series" in u]
    leg_checks = [json.loads(b) for u, b in posted if "check_run" in u]
    assert col_series == leg_series
    assert col_checks == leg_checks


def _capture_posts(monkeypatch):
    """Fake `vhttp.post` / `post_json` of the Datadog sink's module ->
    the list of (kind, url, raw body, posting thread's name)."""
    from veneur_tpu.sinks import datadog as ddmod

    posted = []

    def fake_post(url, body, **kw):
        posted.append(("raw", url, bytes(body),
                       threading.current_thread().name))

    def fake_post_json(url, payload, **kw):
        posted.append(("json", url, json.dumps(payload).encode(),
                       threading.current_thread().name))

    monkeypatch.setattr(ddmod.vhttp, "post", fake_post)
    monkeypatch.setattr(ddmod.vhttp, "post_json", fake_post_json)
    return posted


class _FailingEncoder(DatadogColumnarEncoder):
    """Raises once `bodies_before` bodies were handed off (0: before
    any), at the next body's hand-off or at the end of the encode."""

    def __init__(self, sink, bodies_before):
        super().__init__(sink)
        self.bodies_before = bodies_before

    def encode_bodies(self, batch, per_body, emit):
        handed = []

        def failing_emit(parts):
            if len(handed) >= self.bodies_before:
                raise RuntimeError("boom")
            handed.append(parts)
            emit(parts)

        super().encode_bodies(batch, per_body, failing_emit)
        raise RuntimeError("boom")


def test_datadog_columnar_fallback_on_encoder_error(monkeypatch):
    """An encoder that fails before any body was handed off: nothing
    was posted, so the legacy path delivers the whole batch."""
    posted = _capture_posts(monkeypatch)
    batch, _ = _mk_batch()
    sink = _dd_sink(num_workers=1, flush_max_per_body=20)
    sink._encoder = _FailingEncoder(sink, bodies_before=0)
    sink.flush_batch(batch)  # must not raise; legacy path delivers
    # the legacy flush dumps its bodies itself: raw, as the columnar's
    assert {kind for kind, *_ in posted} == {"raw"}
    series = [s for _, url, body, _ in posted if "/series" in url
              for s in json.loads(body)["series"]]
    assert len(series) == len(DatadogColumnarEncoder(sink).encode(batch)[0])


# None: the cap the sink derives from the host's cores
@pytest.mark.parametrize("num_workers", [1, 4, None])
def test_datadog_encoder_error_after_hand_off_posts_no_series_twice(
        monkeypatch, num_workers):
    """Once a body went to a POST worker the fallback would post its
    series again: the flush fails instead, after the workers ended."""
    from veneur_tpu.sinks.datadog import SeriesPartlySent

    posted = _capture_posts(monkeypatch)
    batch, _ = _mk_batch()
    sink = _dd_sink(num_workers=num_workers, flush_max_per_body=20)
    parts, _checks = DatadogColumnarEncoder(sink).encode(batch)
    sink._encoder = _FailingEncoder(sink, bodies_before=2)
    with pytest.raises(SeriesPartlySent):
        sink.flush_batch(batch)
    # what was handed off was sent, once, and nothing else was
    assert sorted(body for _, _, body, _ in posted) == sorted(
        _bodies(parts, 20)[:2])
    assert not any(t.name.startswith("datadog-post-")
                   for t in threading.enumerate())


def _bodies(parts, per_body):
    return [b'{"series":[' + b",".join(parts[i:i + per_body]) + b"]}"
            for i in range(0, len(parts), per_body)]


def _per_body(shape: str, n_parts: int) -> int:
    per_body = {
        "one_body": n_parts,
        "exact_multiple": next(d for d in range(2, n_parts)
                               if n_parts % d == 0),
        "remainder": 20,
        "larger_than_batch": n_parts + 14,
        "one_series_a_body": 1,
    }[shape]
    if shape == "remainder":
        assert n_parts % per_body and n_parts > 2 * per_body
    return per_body


@pytest.mark.parametrize("num_workers", [1, 4, None])
@pytest.mark.parametrize("shape", [
    "one_body", "exact_multiple", "remainder", "larger_than_batch",
    "one_series_a_body"])
def test_datadog_pipeline_posts_the_bodies_of_encode(monkeypatch, shape,
                                                     num_workers):
    """The pipeline sends exactly `encode()`'s parts cut every
    `flush_max_per_body`: the same bytes whatever the number of bodies
    and workers, and with one worker in the same order."""
    posted = _capture_posts(monkeypatch)
    batch, _ = _mk_batch(_extras())
    parts, checks = DatadogColumnarEncoder(_dd_sink()).encode(batch)
    per_body = _per_body(shape, len(parts))
    sink = _dd_sink(num_workers=num_workers, flush_max_per_body=per_body)
    sink.flush_batch(batch)
    want = _bodies(parts, per_body)
    got = [body for kind, url, body, _ in posted
           if kind == "raw" and "/api/v1/series" in url]
    assert sorted(got) == sorted(want)
    if num_workers == 1:
        assert got == want
    # the checks leave after the last body, from the sink thread
    assert [kind for kind, *_ in posted[-len(checks):]] == ["json"]
    posters = {thread for kind, _, _, thread in posted if kind == "raw"}
    me = threading.current_thread().name
    if len(want) == 1:
        assert posters == {me}
    else:
        assert me not in posters and len(posters) <= sink.num_workers


def test_datadog_pipeline_under_thread_stress(monkeypatch):
    """More POST workers than cores and the interpreter switching
    threads every 10 us: each body still leaves exactly once, and the
    sink's own count of what it sent agrees."""
    import sys

    from veneur_tpu.sinks import datadog as ddmod

    posted = _capture_posts(monkeypatch)
    batch, _ = _mk_batch(_extras())
    parts, _checks = DatadogColumnarEncoder(_dd_sink()).encode(batch)
    sink = _dd_sink(num_workers=32, flush_max_per_body=1)
    pipelines = []

    class Kept(ddmod._BodyPosts):
        def __init__(self, *args):
            super().__init__(*args)
            pipelines.append(self)

    monkeypatch.setattr(ddmod, "_BodyPosts", Kept)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for _ in range(5):
            del posted[:]
            batch.timing.spans.clear()
            sink.flush_columnar(batch)
            got = [body for kind, _, body, _ in posted if kind == "raw"]
            assert sorted(got) == sorted(_bodies(parts, 1))
            [wall] = [s for s in batch.timing.spans
                      if s["name"] == "egress_post_wall"]
            assert wall["bodies"] == len(parts)
            assert wall["bytes"] == sum(map(len, got))
            # the workers' counts came back to rest: no update was lost
            posts = pipelines.pop()
            assert (posts.unanswered, posts.in_flight) == (0, 0)
            assert 1 <= wall["peak_in_flight"] <= wall["workers"] <= 32
            assert wall["workers"] == len(posts.workers)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.name.startswith("datadog-post-")
                   for t in threading.enumerate())


@pytest.mark.parametrize("encoder", ["native", "python"])
def test_datadog_encode_bodies_cuts_encodes_parts(encoder):
    """`encode_bodies` emits only runs of `per_body` series that have a
    successor, and returns the rest: together, `encode()`'s parts (the
    native encoder's parts hold several series, joined as the body
    joins them)."""
    batch, _ = _mk_batch(_extras())
    enc = _dd_encoder(_dd_sink(), encoder)
    parts, checks = enc.encode(batch)
    for per_body in (1, 2, 7, len(parts) // 2, len(parts), len(parts) + 1):
        emitted = []
        rest, checks_2 = enc.encode_bodies(batch, per_body, emitted.append)
        assert rest
        assert [b",".join(run) for run in emitted + [rest]] == [
            b",".join(parts[i:i + per_body])
            for i in range(0, len(parts), per_body)]
        if encoder == "python":
            assert emitted + [rest] == [parts[i:i + per_body] for i in
                                        range(0, len(parts), per_body)]
        assert [c.name for c in checks_2] == [c.name for c in checks]


# -- the native encoder and its prefix arena -------------------------------


def _dd_encoder(sink, encoder: str) -> DatadogColumnarEncoder:
    """The sink's encoder on the named path: "native" where the
    library builds (skips where it does not), "python" with the
    library taken away."""
    enc = DatadogColumnarEncoder(sink)
    if encoder == "python":
        enc._lib = None
    elif enc._lib is None:
        pytest.skip("the native series encoder did not build")
    assert enc.name == encoder
    return enc


def _cut_bodies(enc, batch, per_body):
    """-> the bodies `encode_bodies` cuts, as the sink would post them."""
    emitted = []
    rest, _checks = enc.encode_bodies(batch, per_body, emitted.append)
    return [b'{"series":[' + b",".join(run) + b"]}"
            for run in emitted + [rest]]


def _section_series(enc, batch) -> int:
    """How many of `encode()`'s parts come from `batch.sections`."""
    parts, _checks = enc.encode(batch)
    return (len(parts) - sum(bs.line_count() for bs in batch.bucket_sections)
            - sum(m.type != MetricType.STATUS for m in batch.extras))


@pytest.mark.parametrize("cut", [
    "one_body", "exact_multiple", "remainder", "larger_than_batch",
    "one_series_a_body", "inside_a_section", "at_a_section_boundary",
    "inside_the_bucket_rows", "at_the_first_extra"])
def test_native_bodies_are_the_python_loops(cut):
    """The native encoder's bodies are the Python loop's, byte for
    byte, wherever the cut falls."""
    batch, _ = _mk_batch(_extras())
    sink = _dd_sink()
    loop = _dd_encoder(sink, "python")
    parts, _checks = loop.encode(batch)
    in_sections = _section_series(loop, batch)
    first = batch.sections[0].names.shape[0]
    assert batch.sections[0].names.tolist().count("dropme.x") == 1
    per_body = {
        # the first section has one dropped row: its series end at
        # first - 1, and the next section's begin there
        "inside_a_section": first - 3,
        "at_a_section_boundary": first - 1,
        "inside_the_bucket_rows": in_sections + 2,
        "at_the_first_extra": len(parts) - 3,
    }.get(cut) or _per_body(cut, len(parts))
    enc = _dd_encoder(sink, "native")
    for _ in range(2):   # arena cold, then warm
        assert _cut_bodies(enc, batch, per_body) == _bodies(parts, per_body)
        assert enc.native_rows == in_sections
    assert enc.prefix_renders == 0
    assert loop.native_rows == 0


class _Flushes:
    """One column store flushed again and again: its sections' names
    and tags are the tables' cached objects, the same from flush to
    flush for a row's lifetime."""

    def __init__(self, rows=True):
        self.store = ColumnStore(counter_capacity=64, gauge_capacity=64,
                                 histo_capacity=64, set_capacity=32,
                                 batch_cap=256)
        self.parser = Parser()
        self.rows = rows

    def flush(self, lines, reclaim_idle=0):
        for line in lines:
            self.parser.parse_metric_fast(line, self.store.process)
        self.store.apply_all_pending()
        batch, _fwd = flush_columnstore_batch(self.store, False, PCTS, AGGS)
        if reclaim_idle:
            self.store.counters.reclaim_idle(reclaim_idle)
        if not self.rows:   # sections that do not say their table rows
            batch.sections = [dataclasses.replace(sec, rows=None)
                              for sec in batch.sections]
        return batch


def _key_lines(counters=range(10), tail=True):
    lines = [b"k.c%d:%d|c|#env:t,i:%d" % (i, i + 1, i) for i in counters]
    for i in range(6):
        lines += [b"k.g%d:%d.5|g|#env:t" % (i, i),
                  b"k.t%d:%d|ms|#env:t" % (i, 10 + i),
                  b"k.t%d:%d|ms|#env:t" % (i, 30 + i),
                  b"k.s%d:u%d|s" % (i, i), b"k.l%d:%d|l|#svc:x" % (i, i + 1)]
    if tail:   # the last rows of the counter and of the gauge section
        lines += [b"hosted:4|c|#host:other,device:sda,env:t",
                  b"dropme.x:1|c|#env:t", b"bare:3|g"]
    return lines


def _section_rows(batch) -> int:
    return sum(sec.names.shape[0] for sec in batch.sections)


def _assert_native_is_the_loop(enc, sink, batch, per_body=7):
    loop = _dd_encoder(sink, "python")
    want = _bodies(loop.encode(batch)[0], per_body)
    assert _cut_bodies(enc, batch, per_body) == want
    return want


def test_arena_is_reused_whole_by_a_second_flush_of_the_same_keys():
    flushes, sink = _Flushes(), _dd_sink()
    enc = _dd_encoder(sink, "native")
    first = flushes.flush(_key_lines())
    _assert_native_is_the_loop(enc, sink, first)
    assert enc.prefix_renders == _section_rows(first)
    for _ in range(2):
        again = flushes.flush(_key_lines())
        bodies = _assert_native_is_the_loop(enc, sink, again)
        assert enc.prefix_renders == 0
        # the dropped row renders nothing and counts toward no body
        assert enc.native_rows == _section_rows(again) - 1
        assert enc.native_rows == _section_series(enc, again)
    joined = b"".join(bodies)
    assert b"dropme.x" not in joined and b'"i:' not in joined
    assert b'"host":"other"' in joined and b'"device":"sda"' in joined


@pytest.mark.parametrize("rows", [True, False], ids=["shelf", "by_position"])
def test_arena_rows_after_a_key_that_comes_or_goes_are_looked_up_again(rows):
    """A key that stops reporting shifts the rows behind it in its
    section, and so does its return: those rows miss the arena. Where
    the section says its table rows, they are found again on its row
    shelf and nothing renders; else their prefixes come from `_frags`,
    the rest of the flush reused by position."""
    flushes, sink = _Flushes(rows), _dd_sink()
    enc = _dd_encoder(sink, "native")
    _assert_native_is_the_loop(enc, sink, flushes.flush(_key_lines()))
    without = [i for i in range(10) if i != 5]
    _assert_native_is_the_loop(enc, sink,
                               flushes.flush(_key_lines(without)))
    [counters] = [s for s in flushes.flush(_key_lines(without)).sections
                  if "k.c0" in s.names.tolist()]
    behind = counters.names.shape[0] - counters.names.tolist().index("k.c6")
    assert behind >= 4
    _assert_native_is_the_loop(enc, sink,
                               flushes.flush(_key_lines(without)))
    assert enc.prefix_renders == 0
    _assert_native_is_the_loop(enc, sink, flushes.flush(_key_lines()))
    # by position: k.c5 and the rows behind it
    assert enc.prefix_renders == (0 if rows else behind + 1)
    _assert_native_is_the_loop(enc, sink, flushes.flush(_key_lines()))
    assert enc.prefix_renders == 0


def test_arena_serves_a_section_that_is_its_first_rows():
    """Keys at a section's end that skip an interval (here the counter
    section's last two rows, one of them dropped by its name, and the
    gauge section's last) cost nothing when they go nor when they
    return: the kept arena's first rows serve the shorter section."""
    flushes, sink = _Flushes(), _dd_sink()
    enc = _dd_encoder(sink, "native")
    _assert_native_is_the_loop(enc, sink, flushes.flush(_key_lines()))
    for tail in (False, True, False, False, True):
        batch = flushes.flush(_key_lines(tail=tail))
        bodies = _assert_native_is_the_loop(enc, sink, batch)
        assert enc.prefix_renders == 0
        assert enc.native_rows == _section_rows(batch) - tail
        assert (b'"hosted"' in b"".join(bodies)) == tail


def test_arenas_outlive_a_flush_of_other_sections_and_age_out(monkeypatch):
    """An interval with one stray series (a server's own, alone in its
    flush) leaves the arenas of the sections it lacks in place; a
    section that stays away longer than `ARENA_IDLE_FLUSHES` flushes
    loses its arena but finds its rows on its shelf, and one away longer
    than `SHELF_IDLE_FLUSHES` renders anew."""
    from veneur_tpu.core import egress

    monkeypatch.setattr(egress, "SHELF_IDLE_FLUSHES",
                        egress.ARENA_IDLE_FLUSHES + 2)
    flushes, sink = _Flushes(), _dd_sink()
    enc = _dd_encoder(sink, "native")
    full = flushes.flush(_key_lines())
    _assert_native_is_the_loop(enc, sink, full)
    arenas = len(enc._arenas)
    assert arenas == len(full.sections)
    for _ in range(egress.ARENA_IDLE_FLUSHES):
        _assert_native_is_the_loop(
            enc, sink, flushes.flush([b"stray.unique:u1|s|#service:me"]))
    assert enc.prefix_renders <= 1 and len(enc._arenas) == arenas + 1
    _assert_native_is_the_loop(enc, sink, flushes.flush(_key_lines()))
    assert enc.prefix_renders == 0
    for _ in range(egress.ARENA_IDLE_FLUSHES + 1):
        stray = flushes.flush([b"stray.unique:u1|s|#service:me"])
        _assert_native_is_the_loop(enc, sink, stray)
    assert len(enc._arenas) == 1
    again = flushes.flush(_key_lines())
    _assert_native_is_the_loop(enc, sink, again)
    assert enc.prefix_renders == 0
    for _ in range(egress.SHELF_IDLE_FLUSHES + 1):
        stray = flushes.flush([b"stray.unique:u1|s|#service:me"])
        _assert_native_is_the_loop(enc, sink, stray)
    assert len(enc._arenas) == len(enc._shelves) == 1
    again = flushes.flush(_key_lines())
    _assert_native_is_the_loop(enc, sink, again)
    assert enc.prefix_renders == _section_rows(again)


def test_shelf_serves_a_whole_flush_after_two_halves_and_quiet_flushes():
    """A server that flushed its keys only in parts (a tick that split
    a burst), then a few flushes with none of them, renders nothing for
    the first flush of them all: every row is on its section's shelf."""
    from veneur_tpu.core import egress

    flushes, sink = _Flushes(), _dd_sink()
    enc = _dd_encoder(sink, "native")
    for half in ([0, 1, 2, 3, 4], [0, 5, 6, 7, 8, 9]):
        _assert_native_is_the_loop(
            enc, sink, flushes.flush(_key_lines(half, tail=half[-1] == 9)))
    for _ in range(egress.ARENA_IDLE_FLUSHES + 1):
        _assert_native_is_the_loop(
            enc, sink, flushes.flush([b"stray.unique:u1|s|#service:me"]))
    whole = flushes.flush(_key_lines())
    bodies = _assert_native_is_the_loop(enc, sink, whole)
    assert enc.prefix_renders == 0
    assert enc.native_rows == _section_rows(whole) - 1   # dropme.x
    assert b"dropme.x" not in b"".join(bodies)


def test_shelf_lays_its_bytes_anew_when_rows_render_again(monkeypatch):
    """A row re-tagged flush after flush appends a prefix each time; the
    shelf lays its live prefixes out anew once the garbage outgrows
    them, and the bodies stay the Python loop's."""
    from veneur_tpu.core import egress

    monkeypatch.setattr(egress, "SHELF_SLACK_BYTES", 0)
    flushes, sink = _Flushes(), _dd_sink()
    enc = _dd_encoder(sink, "native")
    _assert_native_is_the_loop(enc, sink, flushes.flush(_key_lines()))
    filled = []
    for k in range(16):
        batch = flushes.flush(_key_lines())
        section = batch.sections[0]
        section.tags[2] = list(section.tags[2]) + [f"late:{k}"]
        bodies = _assert_native_is_the_loop(enc, sink, batch)
        assert enc.prefix_renders == 1
        assert b"".join(bodies).count(b'"late:%d"' % k) == 1
        [shelf] = [s for s in enc._shelves
                   if s.holds(int(section.rows[0]), section.names[0])]
        assert shelf.filled <= 2 * shelf.live
        filled.append(shelf.filled)
    assert any(b < a for a, b in zip(filled, filled[1:]))   # laid anew


def test_arena_sees_a_row_recycled_to_another_key():
    flushes, sink = _Flushes(), _dd_sink()
    enc = _dd_encoder(sink, "native")
    table = flushes.store.counters
    _assert_native_is_the_loop(enc, sink, flushes.flush(_key_lines()))
    [gone] = [r for r, m in enumerate(table.meta)
              if m is not None and m.name == "k.c9"]
    for _ in range(3):
        _assert_native_is_the_loop(
            enc, sink, flushes.flush(_key_lines(range(9)), reclaim_idle=1))
    assert table.meta[gone] is None and enc.prefix_renders == 0
    batch = flushes.flush(_key_lines(range(9)) + [b"k.new:7|c|#env:t"])
    assert table.meta[gone].name == "k.new"
    bodies = _assert_native_is_the_loop(enc, sink, batch)
    assert b"k.new" in b"".join(bodies) and b"k.c9" not in b"".join(bodies)
    assert 1 <= enc.prefix_renders <= 3   # k.new; hosted, dropme behind it


def test_arena_sees_a_tags_list_that_is_another_object():
    flushes, sink = _Flushes(), _dd_sink()
    enc = _dd_encoder(sink, "native")
    _assert_native_is_the_loop(enc, sink, flushes.flush(_key_lines()))
    batch = flushes.flush(_key_lines())
    section = batch.sections[1]
    section.tags[2] = list(section.tags[2]) + ["late:tag"]
    bodies = _assert_native_is_the_loop(enc, sink, batch)
    assert enc.prefix_renders == 1
    assert b"".join(bodies).count(b'"late:tag"') == 1


def test_arena_survives_a_frag_cache_reset_mid_flush(monkeypatch):
    from veneur_tpu.core import egress

    monkeypatch.setattr(egress, "FRAG_CACHE_CAP", 5)
    flushes, sink = _Flushes(), _dd_sink()
    enc = _dd_encoder(sink, "native")
    first = flushes.flush(_key_lines())
    _assert_native_is_the_loop(enc, sink, first)
    assert len(enc._frags) <= 5 < _section_rows(first)
    _assert_native_is_the_loop(enc, sink, flushes.flush(_key_lines()))
    assert enc.prefix_renders == 0


def test_arena_of_a_section_whose_type_changed_is_not_reused():
    """An arena is found by its first row and its type: a gauge section
    of the rows a counter section had renders anew (`"type":"gauge"`)."""
    flushes, sink = _Flushes(), _dd_sink()
    enc = _dd_encoder(sink, "native")
    batch = flushes.flush(_key_lines())
    _assert_native_is_the_loop(enc, sink, batch)
    first = batch.sections[0]
    assert first.mtype == MetricType.COUNTER
    first.mtype = MetricType.GAUGE
    _assert_native_is_the_loop(enc, sink, batch)
    assert enc.prefix_renders == first.names.shape[0]


def test_sink_without_the_library_posts_the_same_bodies(monkeypatch):
    """With the library unavailable the Python loop is the sink's
    encoder, chosen by that alone: same bodies, `encoder: "python"`."""
    from veneur_tpu import native

    posted = _capture_posts(monkeypatch)
    batch, _ = _mk_batch(_extras())
    with_library = _dd_sink(num_workers=1, flush_max_per_body=20)
    if with_library._encoder.name != "native":
        pytest.skip("the native series encoder did not build")
    with_library.flush_columnar(batch)
    want = [body for kind, _, body, _ in posted if kind == "raw"]
    spans = batch.timing.spans
    [encode] = [s for s in spans if s["name"] == "egress_encode"]
    assert encode["encoder"] == "native" and encode["native_rows"] > 0
    assert encode["prefix_renders"] == _section_rows(batch)

    def no_compiler(*args, **kwargs):
        raise FileNotFoundError("g++")

    monkeypatch.setattr(native, "_SERIES", native._Unit(
        "ddseries.cc", "libvntddseries-absent", native._declare_series))
    monkeypatch.setattr(native._SERIES, "_compile", no_compiler)
    without = _dd_sink(num_workers=1, flush_max_per_body=20)
    assert without._encoder.name == "python"
    assert "g++" in native._SERIES.err
    del posted[:], spans[:]
    without.flush_columnar(batch)
    assert [body for kind, _, body, _ in posted if kind == "raw"] == want
    [encode] = [s for s in spans if s["name"] == "egress_encode"]
    assert encode["encoder"] == "python" and encode["native_rows"] == 0
    assert encode["prefix_renders"] == _section_rows(batch)


# -- Prometheus ------------------------------------------------------------


def _fake_exemplars(clauses):
    def exemplars(name, tags):
        return clauses.get(name, "")
    return exemplars


def test_prometheus_parity_plain_and_openmetrics():
    batch, _ = _mk_batch(_extras())
    legacy = batch.materialize()
    r = PrometheusColumnarRenderer()
    assert r.render(batch) == render_exposition(legacy)
    ex = _fake_exemplars({
        "c.0": ' # {trace_id="ab"} 1.0 1700000000.000',
        "ll.1.bucket": ' # {trace_id="cd"} 501.0 1700000000.000',
        "extra.count": ' # {trace_id="ef"} 4.0 1700000000.000',
    })
    for om in (False, True):
        got = PrometheusColumnarRenderer().render(
            batch, exemplars=ex, openmetrics=om)
        want = render_exposition(legacy, exemplars=ex, openmetrics=om)
        assert got == want
    # the suite must actually exercise the clauses + backfilled stamps
    om_text = render_exposition(legacy, exemplars=ex, openmetrics=True)
    assert '# {trace_id="ab"}' in om_text
    assert '# {trace_id="cd"}' in om_text
    assert "backfill_g" in om_text and " 1699990000" in om_text


def test_prometheus_sink_columnar_exposition():
    batch, _ = _mk_batch(_extras())
    sink = PrometheusMetricSink("prometheus")
    sink.flush_batch(batch)
    assert sink.exposition_plain() == render_exposition(
        batch.materialize())
    # lazy OM render comes from the stored batch
    assert sink.exposition_openmetrics() == render_exposition(
        batch.materialize(), openmetrics=True) + "# EOF\n"


def test_prometheus_repeater_falls_back_to_legacy(monkeypatch):
    batch, _ = _mk_batch()
    sink = PrometheusMetricSink("prometheus",
                                repeater_address="127.0.0.1:1",
                                network="udp")
    sink.flush_batch(batch)  # repeater wants InterMetrics; no raise
    assert sink.exposition_plain() == render_exposition(
        batch.materialize())


# -- Cortex ----------------------------------------------------------------


class _FakeExemplarStore:
    def __init__(self, entries):
        self.entries = entries  # name -> (trace_id, value, ts)

    def for_series(self, name, tags=()):
        return self.entries.get(name)


def _cortex_series(sink, metrics):
    exemplified = set()
    series = []
    for m in metrics:
        if m.type == MetricType.STATUS:
            continue
        if (m.type == MetricType.COUNTER
                and sink.convert_counters_to_monotonic):
            key = (m.name, tuple(sorted(m.tags)), m.hostname)
            sink._monotonic[key] = (
                sink._monotonic.get(key, 0.0) + float(m.value))
            continue
        row = sink._series(m)
        entry = sink._exemplar_entry(m, exemplified)
        if entry is not None:
            from veneur_tpu.trace.store import trace_id_hex
            tid, ev, ets = entry
            row = row + ((trace_id_hex(tid), float(ev), int(ets * 1000)),)
        series.append(row)
    return series


def test_cortex_parity_bytes():
    batch, _ = _mk_batch(_extras())
    sink = CortexMetricSink("cortex", "http://c/api", "myhost",
                            excluded_tags=["i"])
    sink._exemplars = _FakeExemplarStore({
        "c.0": (0xAB, 1.5, 1700000000.25),
        "extra.count": (0xEF, 4.0, 1700000001.0),
    })
    frames, max_ts = CortexColumnarEncoder(sink).encode(batch)
    legacy = batch.materialize()
    sink2 = CortexMetricSink("cortex", "http://c/api", "myhost",
                             excluded_tags=["i"])
    sink2._exemplars = sink._exemplars
    want = encode_write_request(_cortex_series(sink2, legacy))
    assert b"".join(frames) == want
    assert max_ts == max(m.timestamp for m in legacy)


def test_cortex_parity_monotonic_mode():
    batch, _ = _mk_batch(_extras())
    col = CortexMetricSink("cortex", "http://c/api", "myhost",
                           convert_counters_to_monotonic=True)
    leg = CortexMetricSink("cortex", "http://c/api", "myhost",
                           convert_counters_to_monotonic=True)
    frames, max_ts = CortexColumnarEncoder(col).encode(batch)
    series = _cortex_series(leg, batch.materialize())
    assert b"".join(frames) == encode_write_request(series)
    assert col._monotonic == leg._monotonic  # counters + buckets folded
    assert any("le:+Inf" in k[1] for k in col._monotonic)
    # the re-emit stamp comes from the SAME fold, legacy-compatible
    assert max_ts == max(m.timestamp for m in batch.materialize())
    col_frames = [encode_write_request([r])
                  for r in col._monotonic_series(max_ts)]
    leg_frames = [encode_write_request([r])
                  for r in leg._monotonic_series(max_ts)]
    assert b"".join(col_frames) == b"".join(leg_frames)


def test_cortex_flush_columnar_posts_same_bytes(monkeypatch):
    from veneur_tpu.sinks import cortex as cxmod

    posted = []
    monkeypatch.setattr(
        cxmod.vhttp, "post",
        lambda url, body, **kw: posted.append(bytes(body)))
    batch, _ = _mk_batch(_extras())
    sink = CortexMetricSink("cortex", "http://c/api", "myhost",
                            batch_write_size=7)
    sink.flush_batch(batch)
    col = list(posted)
    posted.clear()
    sink2 = CortexMetricSink("cortex", "http://c/api", "myhost",
                             batch_write_size=7)
    sink2.flush(batch.materialize())
    assert col == posted  # chunk boundaries AND bytes identical


# -- streaming forward (pre-encoded wire) ----------------------------------


def test_forward_wire_prebuilt_matches_reencode():
    _, fwd = _mk_batch(is_local=True)
    from veneur_tpu.forward.convert import forwardable_to_wire

    assert len(fwd)
    first = forwardable_to_wire(fwd)
    fwd.wire = first
    assert forwardable_to_wire(fwd) == first  # deterministic
    fwd.invalidate_wire()
    assert fwd.wire is None


def test_carryover_merge_invalidates_wire():
    from veneur_tpu.forward.convert import forwardable_to_wire
    from veneur_tpu.util.resilience import Carryover

    _, fwd_a = _mk_batch(is_local=True)
    _, fwd_b = _mk_batch(is_local=True)
    co = Carryover(max_intervals=4)
    fwd_a.wire = forwardable_to_wire(fwd_a)
    co.stash(fwd_a)
    fwd_b.wire = forwardable_to_wire(fwd_b)
    merged = co.drain_into(fwd_b)
    assert merged.wire is None  # stale frames must not be sent
    # stash-merge path too: pending + new both had wire set
    fwd_b.wire = forwardable_to_wire(fwd_b)
    co.stash(fwd_b)
    _, fwd_c = _mk_batch(is_local=True)
    fwd_c.wire = forwardable_to_wire(fwd_c)
    co.stash(fwd_c)
    assert co._pending.wire is None


# -- encode/send observability ---------------------------------------------


def test_note_egress_rows_in_observatory():
    from veneur_tpu.core.latency import LatencyObservatory

    obs = LatencyObservatory(enabled=True)
    obs.note_egress("datadog", 0.002, 0.030)
    obs.note_egress("datadog", 0.004, 0.010)
    obs.note_egress("cortex", 0.001, 0.020)
    rows = obs.telemetry_rows()
    names = {(n, tuple(sorted(tags))) for n, _v, _k, tags in rows}
    assert any(n == "egress.encode_s.p99" and ("sink:datadog",) == t
               for n, t in names)
    assert any(n == "egress.send_s.count" and ("sink:cortex",) == t
               for n, t in names)
    rep = obs.report()
    assert set(rep["egress"]) == {"datadog", "cortex"}
    assert rep["egress"]["datadog"]["encode"]["count"] == 2


def test_sink_note_egress_reports_and_tags_span():
    class _Lat:
        def __init__(self):
            self.calls = []

        def note_egress(self, sink, enc, snd):
            self.calls.append((sink, enc, snd))

    sink = PrometheusMetricSink("prometheus")
    lat = _Lat()
    sink._latency = lat
    sink.note_egress(0.5, 0.25)
    assert lat.calls == [("prometheus", 0.5, 0.25)]


def test_datadog_flush_tags_its_span_with_the_encoder(monkeypatch):
    """`note_egress` of a columnar Datadog flush names the encoder that
    ran it on the ambient `flush.sink` span."""
    from veneur_tpu.trace import context as trace_ctx

    class _Span:
        def __init__(self):
            self.tags = {}

        def set_tag(self, key, value):
            self.tags[key] = value

    _capture_posts(monkeypatch)
    batch, _ = _mk_batch()
    sink = _dd_sink()
    span = _Span()
    token = trace_ctx._current_span.set(span)
    try:
        sink.flush_columnar(batch)
    finally:
        trace_ctx._current_span.reset(token)
    assert span.tags["egress.encoder"] == sink._encoder.name
    assert sink._encoder.name in ("native", "python")


@pytest.mark.parametrize("encoder, lost", [
    ("native", 0), ("python", 0), ("native", 3), ("python", 3)])
def test_datadog_flush_counts_its_own_series(monkeypatch, caplog, encoder,
                                             lost):
    """The flush checks its own count: the series put into bodies and
    the rows that render to none (a dropped name prefix, a status check)
    are the batch's rows. An encoder doctored to lose `lost` series on
    the way to a body is counted and logged; nothing else changes."""
    from veneur_tpu.core import egress

    posted = _capture_posts(monkeypatch)
    batch, _ = _mk_batch(_extras())
    sink = _dd_sink(num_workers=1, flush_max_per_body=20)
    sink._encoder = _dd_encoder(sink, encoder)
    if lost:
        real_add = egress._BodyCut.add
        budget = [lost]

        def losing_add(self, part, series=1):
            if budget[0] and self.total > 30:
                budget[0] -= 1
                if series == 1:
                    return      # a whole series never reaches a body
                part = part[:bytes(part).rindex(b',{"metric"')]
                series -= 1     # ... or the last of a native run
            real_add(self, part, series)

        def losing_extend(self, parts):
            for part in parts:
                self.add(part)

        monkeypatch.setattr(egress._BodyCut, "add", losing_add)
        monkeypatch.setattr(egress._BodyCut, "extend", losing_extend)
    with caplog.at_level("ERROR", logger="veneur_tpu.sinks.datadog"):
        sink.flush_columnar(batch)
    [encode] = [s for s in batch.timing.spans if s["name"] == "egress_encode"]
    assert encode["count_mismatch"] == lost
    series = [s for kind, _, body, _ in posted if kind == "raw"
              for s in json.loads(body)["series"]]
    whole = len(DatadogColumnarEncoder(sink).encode(batch)[0])
    assert len(series) == whole - lost
    # one row is a dropped prefix and one a status check: neither is
    # missed, and neither is a series
    assert whole == len(batch) - 2
    errors = [r for r in caplog.records if "datadog encode wrote" in
              r.getMessage()]
    assert len(errors) == (1 if lost else 0)
    if lost:
        assert f"wrote {whole - lost} series of a batch of {len(batch)}" \
            in errors[0].getMessage()


# -- sustained churn soak --------------------------------------------------


@pytest.mark.slow
def test_egress_parity_soak():
    """Rounds of fresh flushes through LONG-lived encoders (caches warm
    and churn across rounds: id-keyed fragments must never serve stale
    bytes) stay byte-exact against the legacy renderers."""
    dd = _dd_sink()
    dd_enc = DatadogColumnarEncoder(dd)
    dd_native = _dd_encoder(dd, "native")
    prom = PrometheusColumnarRenderer()
    cx = CortexMetricSink("cortex", "http://c/api", "myhost")
    cx_enc = CortexColumnarEncoder(cx)
    for round_no in range(8):
        extras = _extras() if round_no % 2 else []
        batch, _ = _mk_batch(extras)
        legacy = batch.materialize()
        parts, _checks = dd_enc.encode(batch)
        leg = json.loads(json.dumps([
            dd._dd_metric(m) for m in legacy
            if m.type != MetricType.STATUS
            and not m.name.startswith("dropme.")]))
        assert [json.loads(p) for p in parts] == leg
        assert _cut_bodies(dd_native, batch, 13) == _bodies(parts, 13)
        assert prom.render(batch) == render_exposition(legacy)
        frames, _ = cx_enc.encode(batch)
        want = encode_write_request(
            [cx._series(m) for m in legacy
             if m.type != MetricType.STATUS])
        assert b"".join(frames) == want


# -- llhist registers as their nonzero bins, at every density --------------

def _feed_lines(store, lines):
    p = Parser()
    for line in lines:
        p.parse_metric_fast(line, store.process)
    store.apply_all_pending()


def _feed_six_samples_a_row(store):
    rng = np.random.default_rng(11)
    _feed_lines(store, [b"six.%d:%r|l|#env:t,k:%d" % (i, float(v), i)
                        for i in range(40)
                        for v in rng.lognormal(1.0, 2.0, 6)])


def _feed_one_dense_row(store):
    # a value in each of 2,300 bins (1,300 positive, 1,000 negative) for
    # one key, among keys with a bin or two
    mids = np.concatenate([
        llhist_ref.BIN_MID[llhist_ref.POS_BASE + 400:][:1300],
        llhist_ref.BIN_MID[llhist_ref.NEG_BASE + 700:][:1000]])
    lines = [b"dense:%r|l|#env:t" % float(v) for v in mids]
    lines += [b"dense:%r|l|#env:t" % float(v) for v in mids[::7]]
    for i in range(12):
        lines += [b"thin.%d:%d|l|#env:t" % (i, i + 1),
                  b"thin.%d:%d.5|l|#env:t" % (i, 10 * i)]
    _feed_lines(store, lines)


def _feed_negative_zero_clamped(store):
    _feed_lines(store, [
        b"neg:-3.5|l|#env:t", b"neg:-3.5|l|#env:t", b"neg:-120|l|#env:t",
        b"neg:7|l|#env:t|@0.25",
        b"zero:0|l", b"zero:-0.0|l", b"zero:1e-12|l", b"zero:-1e-14|l",
        b"top:1e20|l|#env:t", b"top:-1e20|l|#env:t", b"top:9.99e15|l|#env:t",
        b"rate:2.5|l|@0.001", b"rate:-2.5|l|@0.5"])


def _feed_touched_but_reclaimed(store):
    # a straggler batch lands on a row that idle reclamation has already
    # recycled: touched, with registers, and no meta to name it
    _feed_lines(store, [b"gone:4|l|#env:t", b"kept:5|l|#env:t"])
    [gone] = [r for r, m in enumerate(store.llhists.meta)
              if m is not None and m.name == "gone"]
    flush_columnstore_batch(store, False, PCTS, AGGS)
    for _ in range(3):
        _feed_lines(store, [b"kept:5|l|#env:t"])
        store.llhists.reclaim_idle(1)
        flush_columnstore_batch(store, False, PCTS, AGGS)
    assert store.llhists.meta[gone] is None
    _feed_lines(store, [b"kept:6|l|#env:t", b"kept:60|l|#env:t"])
    store.llhists.add_batch(np.array([gone, gone], np.int32),
                            np.array([4.0, 44.0]), np.ones(2))
    store.apply_all_pending()


def _feed_local_mixed_scope(store):
    lines = []
    for i in range(8):
        lines += [b"mixed.%d:%d|l|#env:t" % (i, i + 1),
                  b"mixed.%d:%d|l|#env:t" % (i, 100 * (i + 1)),
                  b"mine.%d:%d.25|l|#env:t,veneurlocalonly" % (i, i),
                  b"mine.%d:-%d|l|#env:t,veneurlocalonly" % (i, i + 2)]
    lines += [b"theirs:8.5|l|#veneurglobalonly"]
    _feed_lines(store, lines)


def _exact(m):
    return (m.name, tuple(m.tags), struct.pack("<d", m.value), int(m.type))


@pytest.mark.parametrize("feed", [
    _feed_six_samples_a_row, _feed_one_dense_row,
    _feed_negative_zero_clamped, _feed_touched_but_reclaimed,
    _feed_local_mixed_scope], ids=lambda f: f.__name__[len("_feed_"):])
def test_llhist_nonzero_bins_match_the_per_row_oracle(feed):
    """What the columnar flush builds from an llhist's nonzero registers
    is what the per-row oracle builds from the whole row: names, tags,
    `le:` bounds and every value bit for bit; and each columnar
    encoder's bytes are the legacy encoder's."""
    is_local = feed is _feed_local_mixed_scope
    stores = [ColumnStore(llhist_capacity=64, batch_cap=256)
              for _ in range(2)]
    for store in stores:
        feed(store)
    final, fwd_l = flush_columnstore(stores[0], is_local, PCTS, AGGS,
                                     collect_forward=is_local)
    batch, fwd_b = flush_columnstore_batch(stores[1], is_local, PCTS, AGGS,
                                           collect_forward=is_local)
    legacy = batch.materialize()
    assert len(batch) == len(legacy) == len(final)
    assert sorted(map(_exact, legacy)) == sorted(map(_exact, final))
    counts = {(m.name[:-len(".count")], tuple(m.tags)): m.value
              for m in legacy if m.name.endswith(".count")}
    infs = {(m.name[:-len(".bucket")], tuple(m.tags[:-1])): m.value
            for m in legacy if m.tags[-1:] == ["le:+Inf"]}
    assert counts == infs and counts
    [section] = batch.bucket_sections
    assert section.line_count() == sum(
        m.name.endswith(".bucket") for m in final)
    if feed is _feed_one_dense_row:
        assert np.diff(section.indptr).max() >= 2000
    # a local forwards whole rows, widened for the global's merge
    assert [(meta.name, bins.dtype, bins.tolist())
            for meta, bins in fwd_b.llhists] == [
        (meta.name, np.dtype(np.int64), bins.tolist())
        for meta, bins in fwd_l.llhists]
    assert bool(fwd_b.llhists) == is_local

    dd = _dd_sink()
    parts, _checks = DatadogColumnarEncoder(dd).encode(batch)
    assert [json.loads(p) for p in parts] == json.loads(json.dumps(
        [dd._dd_metric(m) for m in legacy]))
    for per_body in (sys.maxsize, 50):
        assert _cut_bodies(_dd_encoder(dd, "native"), batch,
                           per_body) == _bodies(parts, per_body)
    assert PrometheusColumnarRenderer().render(batch) == \
        render_exposition(legacy)
    for mono in (False, True):
        col, leg = (CortexMetricSink("cortex", "http://c/api", "myhost",
                                     convert_counters_to_monotonic=mono)
                    for _ in range(2))
        frames, _max_ts = CortexColumnarEncoder(col).encode(batch)
        assert b"".join(frames) == encode_write_request(
            _cortex_series(leg, legacy))
        assert col._monotonic == leg._monotonic

"""Zipf-skewed timer keys through the digest table, against the
references: the dense branch of `batch_tdigest.host_slots` (a key with
more than C = 128 samples in one batch is folded into batch-local
k-buckets and marked full) and the whole-table compacts that it forces,
which no uniform stream reaches.

2,000 timer keys whose popularity follows YCSB's zipfian distribution
(theta 0.99, the `timers100k-zipf` deployment's skew) send 6.4 batches
of `BATCH` lines, cut by the table at its `batch_cap` as the native pump
hands them over: the three or four hottest keys are dense in every
batch, so every batch after the first compacts the table, and the last,
partial batch is applied (and compacted) on the flush's readout path.
After the flush:

  - a key of more than 63 samples: each percentile within 0.02 in rank
    of its own data (as the benchmark's comparison ranks it);
  - count, min and max of every key exact;
  - a key of up to 63 samples: value for value against
    `ops/tdigest_ref.py` (both keep every such sample a centroid of its
    own; the benchmark compares up to 8 so, and the rank rule cannot
    hold a key of 9-16 samples to 0.02 at p = 0.99: below the largest
    sample a value ranks at most 1 - 0.5 / n);
  - `dense_keys_total` and `compacts_total` equal to what the stream
    implies: the staging-occupancy rule replayed over its batches;
  - with a device observatory, every compact's chip seconds booked by
    its completion stamp; without one, none;
  - the compacts fold only the rows whose staging would overflow or is
    half full: a key that never stages 64 samples stays staged exactly
    through them.

The same stream runs through a `HistoTable`, a `ColumnStore` flushed by
the server's columnar flush, and a four-shard `ColumnStore`.
"""

import numpy as np
import pytest

from veneur_tpu.core.columnstore import ColumnStore, HistoTable
from veneur_tpu.core.deviceobs import DeviceObservatory
from veneur_tpu.core.flusher import flush_columnstore_batch
from veneur_tpu.ops import batch_tdigest, tdigest_ref
from veneur_tpu.samplers.metrics import HistogramAggregates
from veneur_tpu.samplers.parser import Parser

C = batch_tdigest.C
KEYS = 2000
THETA = 0.99
BATCH = 4096
LINES = 6 * BATCH + 1638       # the last batch stays pending to the flush
PCTS = (0.5, 0.9, 0.99)
AGGS = HistogramAggregates.from_names(["min", "max", "count"])
TIMER_RANK_GAP = 0.02          # benchmark/configs/timers100k-zipf.json
COLD_TIMER_REL_GAP = 1.5e-6
# up to here adjacent samples lie more than one k-unit apart even at the
# median (dk/dq = 2 x 100 / pi there), so no compact merges two of them
EXACT_MAX = 63


def zipf_stream(seed):
    """(key, value) of every line, in send order: key ranks drawn with
    probability r^-theta / H(KEYS; theta), values as a timer's text
    carries them (three decimals)."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, KEYS + 1, dtype=np.float64) ** -THETA
    keys = rng.choice(KEYS, LINES, p=p / p.sum())
    values = np.round(rng.lognormal(3.0, 1.0, LINES), 3)
    return keys, values


def implied(rows, capacity, home=None, shards=1):
    """Compacts and dense keys the occupancy rule implies for `rows` cut
    into batches of BATCH lines, the last applied by the flush's readout:
    keys with staged samples whose batch would take them past C are
    compacted first, in one program over the table (a sharded table: the
    shard), with every row at least half full (FOLD_ALONG), and only
    those rows start staging anew; a key with more than C samples in its
    batch is dense and leaves its row full. On shards
    each batch is masked to the rows homed there (`home`), and a shard
    that a batch misses is not touched."""
    compacts = {"live": 0, "readout": 0}
    dense = {"live": 0, "readout": 0}
    starts = range(0, len(rows), BATCH)
    for shard in range(shards):
        staged = np.zeros(capacity, np.int64)
        for at in starts:
            mine = rows[at:at + BATCH]
            if home is not None:
                mine = mine[home[at:at + BATCH] == shard]
                if mine.size == 0:
                    continue
            path = "readout" if at == starts[-1] else "live"
            g = np.bincount(mine, minlength=capacity)
            over = (staged > 0) & (staged + g > C)
            if over.any():
                compacts[path] += 1
                staged[over | (staged >= batch_tdigest.FOLD_ALONG)] = 0
            hot = g > C
            dense[path] += int(hot.sum())
            staged += g
            staged[hot] = C
    return compacts, dense


def intern_keys(table):
    parser, metrics = Parser(), []
    for i in range(KEYS):
        parser.parse_metric_fast(b"zipf.%d:1|ms" % i, metrics.append)
    return np.array([table.intern(m) for m in metrics], np.int32)


def feed(table, rows, values, seed):
    """The lines in pump-sized chunks of uneven length."""
    rng = np.random.default_rng(seed)
    at = 0
    while at < len(rows):
        n = int(rng.integers(500, 3000))
        table.add_batch(rows[at:at + n].copy(),
                        values[at:at + n].astype(np.float32),
                        np.ones(len(rows[at:at + n]), np.float32))
        at += n


def rank_gap(data, value, p):
    """Distance in rank from p to the bracket a flushed value may stand
    for in the data (benchmark/harness/compare.py's rule): each distinct
    value sits at the centre of its weight in the exact CDF."""
    values, counts = np.unique(data, return_counts=True)
    centre = (np.cumsum(counts) - counts / 2.0) / counts.sum()
    slack = abs(value) * 1e-6
    below = int(np.searchsorted(values, value - slack)) - 1
    above = int(np.searchsorted(values, value + slack, "right"))
    lo = centre[below] if below >= 0 else 0.0
    hi = centre[above] if above < values.size else 1.0
    return max(0.0, lo - p, p - hi)


def check_against_references(keys, values, got):
    """`got(key, stat)`: the flushed value of one key, stat in count,
    min, max and the percentiles."""
    worst = 0.0
    exact = 0
    for key in range(KEYS):
        data = values[keys == key]
        if data.size == 0:
            continue
        f32 = data.astype(np.float32)
        assert got(key, "count") == float(data.size), key
        assert got(key, "min") == float(f32.min()), key
        assert got(key, "max") == float(f32.max()), key
        if data.size <= EXACT_MAX:
            exact += 1
            ref = tdigest_ref.MergingDigest(100.0)
            for v in data.tolist():
                ref.add(v)
            for p in PCTS:
                q = ref.quantile(p)
                assert abs(got(key, p) - q) <= COLD_TIMER_REL_GAP * abs(q), (
                    key, p, got(key, p), q)
        else:
            for p in PCTS:
                worst = max(worst, rank_gap(f32, got(key, p), p))
    assert 0.0 < worst <= TIMER_RANK_GAP, worst
    assert exact > 1000   # the long tail is there, compared exactly


def test_histo_table_folds_dense_keys_within_the_digest_bound():
    keys, values = zipf_stream(2_147_450_001)
    table = HistoTable(capacity=KEYS, batch_cap=BATCH)
    table.family = "histogram"
    rows = intern_keys(table)[keys]
    feed(table, rows, values, 1)
    out, _export, touched, _meta = table.snapshot_and_reset(
        PCTS, need_export=False)
    compacts, dense = implied(rows, table.capacity)
    # the hottest keys are dense in every batch, so every batch after
    # the first compacts the whole table, the readout's last one too
    assert dense["live"] >= 3 * (LINES // BATCH)
    assert compacts == {"live": LINES // BATCH - 1, "readout": 1}
    assert table.compacts_total == compacts
    assert table.dense_keys_total == dense
    # no observatory: nothing stamped
    assert table.compact_chip_seconds_total == {"live": 0.0, "readout": 0.0}
    row_of = intern_keys(table)
    assert touched[row_of[np.unique(keys)]].all()
    pcol = {p: i for i, p in enumerate(PCTS)}

    def got(key, stat):
        row = row_of[key]
        if stat in pcol:
            return float(out["quantiles"][row, pcol[stat]])
        return float(out[stat][row])

    check_against_references(keys, values, got)


def test_a_hot_keys_compact_leaves_every_other_row_staged():
    """The compacts the hottest keys force batch after batch fold only
    the rows whose staging would overflow or is at least half full: a
    key that never stages half its C slots keeps every sample staged
    exactly (its main grid empty), so it is recompressed once, by the
    flush, as the reference's one merge would; a key that passed them
    has a main grid."""
    keys, values = zipf_stream(2_147_450_009)
    table = HistoTable(capacity=KEYS, batch_cap=BATCH)
    table.family = "histogram"
    rows = intern_keys(table)[keys]
    feed(table, rows, values, 3)
    table.apply_pending()
    assert table.compacts_total["live"] == LINES // BATCH
    n = np.bincount(rows, minlength=table.capacity)
    staged = np.asarray(table.state["sweights"]).sum(axis=1)
    main = np.asarray(table.state["weights"]).sum(axis=1)
    quiet = (n > 0) & (n < batch_tdigest.FOLD_ALONG)
    assert quiet.sum() > 1000
    np.testing.assert_array_equal(main[quiet], 0.0)
    np.testing.assert_array_equal(staged[quiet], n[quiet])
    assert (main[n > 2 * C] > 0).all()
    np.testing.assert_array_equal(main + staged, n)


@pytest.mark.parametrize("shards", [
    1, pytest.param(4, marks=pytest.mark.mesh)])
@pytest.mark.parametrize("observatory", [True, False])
def test_column_store_flush_of_dense_keys_meets_the_references(
        shards, observatory):
    keys, values = zipf_stream(2_147_450_002 + shards)
    store = ColumnStore(counter_capacity=64, gauge_capacity=64,
                        histo_capacity=KEYS, set_capacity=32,
                        llhist_capacity=64, batch_cap=BATCH,
                        shard_devices=shards if shards > 1 else 0)
    if observatory:
        store.attach_deviceobs(DeviceObservatory())
    histos = store.histos
    rows = intern_keys(histos)[keys]
    feed(histos, rows, values, 2)
    batch, _fwd = flush_columnstore_batch(store, False, PCTS, AGGS,
                                          collect_forward=False)
    flushed = {m.name: m.value for m in batch.materialize()}
    want_c, want_d = implied(
        rows, histos.capacity,
        histos._home_of(rows) if shards > 1 else None, shards)
    assert want_d["live"] > 0 and want_c["readout"] >= 1
    assert histos.compacts_total == want_c
    assert histos.dense_keys_total == want_d
    rows_at = {(name, tuple(tags)): value for name, value, tags in (
        (r[0], r[2], r[3]) for r in store.telemetry_rows()
        if r[0].startswith("ingest.tdigest."))}
    for path in ("live", "readout"):
        assert rows_at[("ingest.tdigest.dense_keys_total",
                        (f"path:{path}",))] == want_d[path]
        chip = rows_at.get(("ingest.tdigest.compact_chip_seconds_total",
                            (f"path:{path}",)))
        if observatory:
            # the flush joined the watcher after its readout: every
            # compact handed over before it is stamped and booked
            assert chip == histos.compact_chip_seconds_total[path] > 0
        else:
            assert chip is None
            assert histos.compact_chip_seconds_total[path] == 0.0

    def got(key, stat):
        suffix = (f"{int(stat * 100)}percentile" if isinstance(stat, float)
                  else stat)
        return flushed[f"zipf.{key}.{suffix}"]

    check_against_references(keys, values, got)
    if observatory:
        store.deviceobs.close()

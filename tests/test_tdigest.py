"""Statistical correctness of the scalar t-digest and the batched device
kernel, mirroring the reference's InEpsilon-style tests
(reference tdigest/histo_test.go:16-199)."""

import math
import random

import numpy as np
import pytest

from veneur_tpu.ops import batch_tdigest as btd
from veneur_tpu.ops.tdigest_ref import MergingDigest


def _means_weights(state):
    """Centroid (means, weights) view of a slot-accumulator digest state."""
    w = np.asarray(state["weights"])
    wv = np.asarray(state["wv"])
    means = np.divide(wv, w, out=np.zeros_like(wv), where=w > 0)
    return means, w


def uniform_digest(rng, n=10000):
    td = MergingDigest(100)
    data = [rng.random() for _ in range(n)]
    for x in data:
        td.add(x, 1.0)
    return td, data


class TestScalarDigest:
    def test_uniform_quantiles(self):
        rng = random.Random(42)
        td, data = uniform_digest(rng)
        data.sort()
        for q in (0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99):
            got = td.quantile(q)
            want = data[int(q * len(data))]
            assert got == pytest.approx(want, abs=0.02)
        assert td.min == pytest.approx(min(data))
        assert td.max == pytest.approx(max(data))
        assert td.count() == pytest.approx(len(data))
        assert td.sum() == pytest.approx(sum(data), rel=1e-3)

    def test_centroid_count_bounded(self):
        rng = random.Random(7)
        td, _ = uniform_digest(rng, 50000)
        td._merge_all_temps()
        assert len(td.means) <= int(math.pi * 100 / 2 + 0.5)

    def test_cdf(self):
        rng = random.Random(3)
        td, data = uniform_digest(rng)
        for v in (0.1, 0.5, 0.9):
            assert td.cdf(v) == pytest.approx(v, abs=0.02)
        assert td.cdf(-1) == 0.0
        assert td.cdf(2) == 1.0

    def test_merge_two_digests(self):
        rng = random.Random(9)
        a = MergingDigest(100)
        b = MergingDigest(100)
        data = []
        for i in range(20000):
            x = rng.normalvariate(100, 15)
            data.append(x)
            (a if i % 2 == 0 else b).add(x, 1.0)
        a.merge(b, rng=rng)
        data.sort()
        for q in (0.1, 0.5, 0.9):
            want = data[int(q * len(data))]
            assert a.quantile(q) == pytest.approx(want, rel=0.02)
        assert a.count() == pytest.approx(len(data))

    def test_weighted_samples(self):
        td = MergingDigest(100)
        # weight w at value v is equivalent to w repeats
        for v in (1.0, 2.0, 3.0):
            td.add(v, 100.0)
        assert td.count() == pytest.approx(300)
        assert td.quantile(0.5) == pytest.approx(2.0, abs=0.6)

    def test_serialization_roundtrip(self):
        rng = random.Random(5)
        td, _ = uniform_digest(rng)
        td2 = MergingDigest.from_data(td.data())
        for q in (0.1, 0.5, 0.9):
            assert td2.quantile(q) == pytest.approx(td.quantile(q))
        assert td2.count() == pytest.approx(td.count())

    def test_rejects_invalid(self):
        td = MergingDigest(100)
        with pytest.raises(ValueError):
            td.add(math.nan, 1)
        with pytest.raises(ValueError):
            td.add(math.inf, 1)
        with pytest.raises(ValueError):
            td.add(1.0, 0)


class TestBatchedDigest:
    def _ingest(self, per_key_data, num_keys, batch=4096, rng=None):
        """Feed {row: [(value, weight)...]} through apply_batch in chunks."""
        state = btd.init_state(num_keys)
        coo = [(r, v, w) for r, samples in per_key_data.items()
               for (v, w) in samples]
        (rng or random).shuffle(coo)
        for i in range(0, len(coo), batch):
            chunk = coo[i:i + batch]
            pad = batch - len(chunk)
            rows = np.array([c[0] for c in chunk] + [num_keys] * pad, np.int32)
            vals = np.array([c[1] for c in chunk] + [0.0] * pad, np.float32)
            wts = np.array([c[2] for c in chunk] + [0.0] * pad, np.float32)
            state = btd.apply_batch(state, rows, vals, wts)
        # fold staged batches into the main grid, as the table does
        # periodically and at every snapshot
        return btd.compact(state, np.ones(num_keys, bool))[0]

    def test_matches_scalar_reference_uniform(self):
        rng = random.Random(11)
        n, num_keys = 20000, 4
        per_key = {k: [(rng.random(), 1.0) for _ in range(n)]
                   for k in range(num_keys)}
        state = self._ingest(per_key, num_keys, rng=rng)
        ps = (0.01, 0.25, 0.5, 0.75, 0.99)
        out = btd.flush_quantiles(state, ps)
        for k in range(num_keys):
            data = sorted(v for v, _ in per_key[k])
            for j, q in enumerate(ps):
                got = float(out["quantiles"][k, j])
                want = data[int(q * len(data))]
                assert got == pytest.approx(want, abs=0.02), (k, q)
            assert float(out["count"][k]) == pytest.approx(n, rel=1e-3)
            assert float(out["sum"][k]) == pytest.approx(sum(data), rel=1e-2)
            assert float(out["min"][k]) == pytest.approx(data[0], abs=1e-6)
            assert float(out["max"][k]) == pytest.approx(data[-1], abs=1e-6)

    def test_lognormal_tail_quantiles(self):
        rng = random.Random(13)
        n = 30000
        data = [rng.lognormvariate(0, 1) for _ in range(n)]
        state = self._ingest({0: [(v, 1.0) for v in data]}, 1, rng=rng)
        out = btd.flush_quantiles(state, (0.5, 0.9, 0.99))
        data.sort()
        for j, q in enumerate((0.5, 0.9, 0.99)):
            got = float(out["quantiles"][0, j])
            want = data[int(q * n)]
            assert got == pytest.approx(want, rel=0.05), q

    def test_weights_respected(self):
        # two values with very different weights shift the median
        state = self._ingest({0: [(0.0, 1.0), (10.0, 9.0)]}, 1)
        out = btd.flush_quantiles(state, (0.5,))
        assert float(out["quantiles"][0, 0]) > 5.0
        assert float(out["count"][0]) == pytest.approx(10.0)

    def test_untouched_rows_unaffected(self):
        rng = random.Random(17)
        state = btd.init_state(3)
        state = self._ingest({0: [(rng.random(), 1.0) for _ in range(1000)]},
                             3, rng=rng)
        before = np.asarray(state["wv"]).copy()
        # a batch touching only row 2 must leave rows 0/1 bit-identical:
        # apply lands in staging, so main rows never move, and rows 0/1
        # gain no staged weight
        rows = np.array([2] * 64, np.int32)
        vals = np.random.default_rng(0).random(64).astype(np.float32)
        wts = np.ones(64, np.float32)
        state = btd.apply_batch(state, rows, vals, wts)
        after = np.asarray(state["wv"])
        np.testing.assert_array_equal(before[0], after[0])
        np.testing.assert_array_equal(before[1], after[1])
        stage_w = np.asarray(state["sweights"])
        assert float(np.sum(stage_w[0])) == 0.0
        assert float(np.sum(stage_w[1])) == 0.0
        assert float(np.sum(stage_w[2])) == 64.0
        # after compaction the staged weight lands in row 2's main grid;
        # a compact of row 2 alone leaves rows 0/1 as they were
        state, _done = btd.compact(state, np.array([False, False, True]))
        assert float(np.sum(np.asarray(state["weights"])[2])) == 64.0
        assert float(np.sum(np.asarray(state["sweights"])[2])) == 0.0
        np.testing.assert_array_equal(before[0], np.asarray(state["wv"])[0])
        np.testing.assert_array_equal(before[1], np.asarray(state["wv"])[1])

    def test_centroid_budget(self):
        rng = random.Random(19)
        state = self._ingest(
            {0: [(rng.random(), 1.0) for _ in range(50000)]}, 1, rng=rng)
        nonzero = int(np.sum(np.asarray(state["weights"])[0] > 0))
        assert nonzero <= btd.C

    def test_merge_centroid_rows_import(self):
        # build a digest on host, import it into an empty device table
        rng = random.Random(23)
        td = MergingDigest(100)
        data = [rng.normalvariate(50, 10) for _ in range(20000)]
        for v in data:
            td.add(v)
        td._merge_all_temps()
        m_row, w_row = btd.pack_centroids(td.means, td.weights)
        means = m_row[None, :]
        weights = w_row[None, :]
        state = btd.init_state(2)
        state = btd.merge_centroid_rows(
            state, np.array([0], np.int32), means, weights,
            np.array([td.min], np.float32), np.array([td.max], np.float32),
            np.array([td.reciprocal_sum], np.float32))
        out = btd.flush_quantiles(state, (0.5, 0.9))
        data.sort()
        assert float(out["quantiles"][0, 0]) == pytest.approx(
            data[len(data) // 2], rel=0.02)
        assert float(out["count"][0]) == pytest.approx(len(data), rel=1e-3)
        # row 1 untouched
        assert math.isnan(float(out["quantiles"][1, 0]))

    def test_distributed_merge_equivalence(self):
        """Two shards each ingest half; merging their centroid stores must
        match a single-shard ingest statistically."""
        rng = random.Random(29)
        data = [rng.normalvariate(0, 1) for _ in range(20000)]
        half = len(data) // 2
        s1 = self._ingest({0: [(v, 1.0) for v in data[:half]]}, 1, rng=rng)
        s2 = self._ingest({0: [(v, 1.0) for v in data[half:]]}, 1, rng=rng)
        merged = btd.merge_centroid_rows(
            s1, np.array([0], np.int32),
            *_means_weights(s2),
            np.asarray(s2["dmin"]), np.asarray(s2["dmax"]),
            np.asarray(s2["drecip"]))
        out = btd.flush_quantiles(merged, (0.1, 0.5, 0.9))
        data.sort()
        for j, q in enumerate((0.1, 0.5, 0.9)):
            want = data[int(q * len(data))]
            assert float(out["quantiles"][0, j]) == pytest.approx(
                want, abs=0.05), q
        assert float(out["count"][0]) == pytest.approx(len(data), rel=1e-3)


class TestPackCentroidsMany:
    def test_parity_with_per_key_pack(self):
        """The segmented packer must conserve each digest's mass and
        weighted mean exactly, and may only differ from pack_centroids
        by weight shifting to an ADJACENT k-scale slot (floor(k) flips
        at a bucket boundary from cumsum rounding)."""
        rng = np.random.default_rng(11)
        ms, ws = [], []
        for i in range(800):
            n = int(rng.integers(0, 160))
            m = rng.standard_normal(n) * 100
            w = rng.random(n) * 5
            if n and rng.random() < 0.1:
                w[:] = 0.0                       # weightless digest
            if n and rng.random() < 0.2:
                w[rng.random(n) < 0.4] = 0.0     # holes
            ms.append(m)
            ws.append(w)
        ms.append(np.array([]))                  # empty digest
        ws.append(np.array([]))
        OM, OW = btd.pack_centroids_many(ms, ws)
        exact = 0
        for i in range(len(ms)):
            em, ew = btd.pack_centroids(ms[i], ws[i])
            if (np.allclose(OW[i], ew, atol=1e-6)
                    and np.allclose(OM[i] * OW[i], em * ew, atol=1e-4)):
                exact += 1
                continue
            wmax = ws[i].max() if len(ws[i]) else 0.0
            # an adjacent-slot shift changes exactly one prefix sum by
            # the shifted weight (<= the digest's largest weight)
            np.testing.assert_allclose(
                np.cumsum(OW[i]), np.cumsum(ew), atol=wmax * 1.01 + 1e-9)
            assert abs(OW[i].sum() - ew.sum()) < 1e-9
            assert abs((OM[i] * OW[i]).sum() - (em * ew).sum()) < 1e-4
        # drift must stay rare, not the norm
        assert exact >= len(ms) * 0.97, exact

    def test_empty_batch(self):
        OM, OW = btd.pack_centroids_many([], [])
        assert OM.shape == (0, btd.C) and OW.shape == (0, btd.C)


class TestFusedExportFlush:
    def test_fused_matches_legacy_compact_flush_export(self):
        """flush_export_packed must produce the exact export grid the
        compact->export path produces (same sort, same segment reduce)
        and quantiles within the digest's own tolerance of the legacy
        two-pass flush."""
        import numpy as np

        from veneur_tpu.ops import batch_tdigest as bt

        rng = np.random.default_rng(5)
        K, B = 257, 4096
        ps = (0.25, 0.5, 0.9, 0.99)
        state = bt.init_state(K)
        for _ in range(3):
            rows = rng.integers(0, K, B).astype(np.int32)
            vals = rng.normal(50, 20, B).astype(np.float32)
            wts = rng.choice([1.0, 2.0], B).astype(np.float32)
            state = bt.apply_batch(state, rows, vals, wts)
            state, _done = bt.compact(state, np.ones(K, bool))
        rows = rng.integers(0, K, B).astype(np.int32)
        vals = rng.lognormal(1, 1, B).astype(np.float32)
        wts = np.ones(B, np.float32)
        state = bt.apply_batch(state, rows, vals, wts)  # staged, uncompacted

        packed, export_packed = bt.flush_export_packed(state, ps)
        fused_out = bt.unpack_flush(np.asarray(packed), len(ps))
        f_means, f_w, f_min, f_max, f_recip = bt.unpack_export(
            export_packed)

        legacy, _done = bt.compact(dict(state), np.ones(K, bool))
        legacy_packed = bt.flush_quantiles_packed(
            legacy, ps, fold_staging=False)
        legacy_out = bt.unpack_flush(np.asarray(legacy_packed), len(ps))
        l_means, l_w, l_min, l_max, l_recip = bt.export_centroids(legacy)

        np.testing.assert_allclose(f_w, l_w, rtol=1e-6, atol=1e-4)
        np.testing.assert_allclose(f_means, l_means, rtol=1e-5, atol=1e-3)
        np.testing.assert_array_equal(f_min, l_min)
        np.testing.assert_array_equal(f_max, l_max)
        np.testing.assert_allclose(f_recip, l_recip, rtol=1e-6)
        np.testing.assert_allclose(fused_out["count"], legacy_out["count"],
                                   rtol=1e-5)
        np.testing.assert_allclose(fused_out["sum"], legacy_out["sum"],
                                   rtol=1e-4)
        # quantiles: fused interpolates over the finer pre-merge grid;
        # both must agree within the digest's own approximation band
        q_f = fused_out["quantiles"]
        q_l = legacy_out["quantiles"]
        spread = np.maximum(legacy_out["max"] - legacy_out["min"], 1e-6)
        rel = np.abs(q_f - q_l) / spread[:, None]
        assert np.nanmax(rel) < 0.05, np.nanmax(rel)

"""Circllhist log-linear histogram family invariants.

The family's contract, pinned here:
- binning brackets every finite value (reference = device: same host
  code path);
- quantile error is bounded by one bin width;
- merges are exact register additions — associative, commutative, and
  bit-identical through the forward plane (local -> global merge equals
  a single node that saw every sample, the acceptance pin);
- carryover of failed forward intervals is lossless (register sums),
  including under the PR-2 chaos soak.
"""

from __future__ import annotations

import numpy as np
import pytest

from veneur_tpu.core.columnstore import ColumnStore
from veneur_tpu.core.flusher import (
    ForwardableState, flush_columnstore, flush_columnstore_batch)
from veneur_tpu.ops import batch_llhist, llhist_ref
from veneur_tpu.samplers import metrics as m
from veneur_tpu.samplers.metrics import HistogramAggregates, MetricType
from veneur_tpu.samplers.parser import Parser

PCTS = (0.5, 0.9, 0.99)
AGGS = HistogramAggregates.from_names(["min", "max", "count"])


def _mk_store(**kw):
    kw.setdefault("llhist_capacity", 64)
    return ColumnStore(counter_capacity=64, gauge_capacity=64,
                       histo_capacity=64, set_capacity=32, batch_cap=128,
                       **kw)


def _feed(store, lines):
    p = Parser()
    for line in lines:
        p.parse_metric_fast(line, store.process)
    store.apply_all_pending()


class TestBinning:
    def test_bins_bracket_values(self):
        rng = np.random.default_rng(0)
        vals = np.concatenate([
            rng.lognormal(0, 4, 2000),
            -rng.lognormal(0, 4, 2000),
            rng.uniform(-1000, 1000, 1000),
        ])
        idx = llhist_ref.bin_index(vals)
        in_range = (np.abs(vals) >= llhist_ref.MIN_MAG) & (
            np.abs(vals) < llhist_ref.MAX_MAG)
        left = llhist_ref.BIN_LEFT[idx[in_range]]
        width = llhist_ref.BIN_WIDTH[idx[in_range]]
        v = vals[in_range]
        assert np.all(v >= left - 1e-12 * np.abs(v))
        assert np.all(v <= left + width + 1e-12 * np.abs(v))

    def test_relative_bin_width_bounded(self):
        # log-linear guarantee: width / |lower edge| <= 1/10
        nz = llhist_ref.BIN_WIDTH > 0
        rel = llhist_ref.BIN_WIDTH[nz] / np.abs(llhist_ref.BIN_LEFT[nz])
        assert np.all(rel <= 0.1 + 1e-12)

    def test_zero_and_out_of_range(self):
        assert llhist_ref.bin_index(0.0) == llhist_ref.ZERO_BIN
        assert llhist_ref.bin_index(1e-30) == llhist_ref.ZERO_BIN
        top_pos = llhist_ref.bin_index(1e30)
        assert llhist_ref.BIN_LEFT[top_pos] == pytest.approx(
            99 * 10.0 ** (llhist_ref.EXP_MAX - 1))
        assert llhist_ref.clamped_mask([1e30, 1e-30, 5.0]).tolist() == \
            [True, True, False]

    def test_sign_symmetry(self):
        vals = np.array([0.123, 7.7, 42.0, 9999.0])
        pos = llhist_ref.bin_index(vals)
        neg = llhist_ref.bin_index(-vals)
        assert np.array_equal(
            neg - pos, np.full(4, llhist_ref.MANT * llhist_ref.NEXP))

    def test_scalar_matches_vector(self):
        vals = [0.0, 1.0, -2.5, 3e7, 1e-9]
        vec = llhist_ref.bin_index(vals)
        for v, i in zip(vals, vec):
            assert llhist_ref.bin_index(v) == i


class TestQuantiles:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_error_bounded_by_one_bin_width(self, seed):
        rng = np.random.default_rng(seed)
        samples = rng.lognormal(rng.uniform(-2, 4), rng.uniform(0.3, 2),
                                5000)
        if seed % 2:
            samples = np.concatenate([samples, -samples[:1000]])
        h = llhist_ref.LLHist()
        h.insert_many(samples)
        for p in (0.0, 0.1, 0.5, 0.9, 0.99, 1.0):
            true = np.quantile(samples, p)
            got = h.quantile(p)
            width = llhist_ref.BIN_WIDTH[llhist_ref.bin_index(true)]
            assert abs(got - true) <= width + 1e-9, (p, got, true)

    def test_empty_reads_zero(self):
        h = llhist_ref.LLHist()
        assert h.quantile(0.5) == 0.0
        assert h.count() == 0 and h.sum() == 0.0

    def test_batch_readout_matches_reference(self):
        rng = np.random.default_rng(4)
        samples = rng.lognormal(2, 1, 4000)
        rows = rng.integers(0, 50, 4000).astype(np.int32)
        bins, wts = batch_llhist.bin_batch_host(samples)
        state = batch_llhist.apply_batch(
            batch_llhist.init_state(64), rows, bins, wts)
        out = batch_llhist.flush_packed(state, PCTS)
        ref = np.zeros((64, llhist_ref.BINS), np.int64)
        np.add.at(ref, (rows, bins), wts)
        assert np.array_equal(
            np.asarray(state)[:, :llhist_ref.BINS], ref)
        q = np.asarray(out["quantiles"])
        for r in range(50):
            np.testing.assert_allclose(
                q[r], llhist_ref.quantiles(ref[r], PCTS), rtol=1e-5)
            assert np.asarray(out["count"])[r] == ref[r].sum()


class TestMergeInvariants:
    def test_merge_associative_commutative_fuzz(self):
        rng = np.random.default_rng(5)
        chunks = [rng.lognormal(1, 1.5, rng.integers(10, 500))
                  for _ in range(6)]
        hists = []
        for c in chunks:
            h = llhist_ref.LLHist()
            h.insert_many(c)
            hists.append(h)

        def merged(order):
            acc = llhist_ref.LLHist()
            for i in order:
                acc.merge(hists[i])
            return acc.bins

        base = merged(range(6))
        assert np.array_equal(base, merged([5, 3, 1, 0, 4, 2]))
        assert np.array_equal(base, merged([2, 4, 0, 1, 3, 5]))
        # associativity: ((a+b)+c) == (a+(b+c)) via pairwise trees
        ab = llhist_ref.LLHist(hists[0].bins + hists[1].bins)
        ab.merge(hists[2])
        bc = llhist_ref.LLHist(hists[1].bins + hists[2].bins)
        bc.merge(hists[0])
        assert np.array_equal(ab.bins, bc.bins)
        # and against the one-shot reference over the union stream
        union = llhist_ref.LLHist()
        union.insert_many(np.concatenate(chunks))
        assert np.array_equal(merged(range(6)), union.bins)

    def test_split_ingest_equals_union_ingest(self):
        rng = np.random.default_rng(6)
        samples = rng.lognormal(3, 1, 2000)
        lines = [b"mrg.k:%.5f|l" % v for v in samples]
        whole, left, right = _mk_store(), _mk_store(), _mk_store()
        _feed(whole, lines)
        _feed(left, lines[:1000])
        _feed(right, lines[1000:])
        snap = {}
        for name, st in (("whole", whole), ("left", left),
                         ("right", right)):
            out, bins, touched, meta = st.llhists.snapshot_and_reset(PCTS)
            snap[name] = bins[0]
        assert np.array_equal(snap["whole"], snap["left"] + snap["right"])


class TestWire:
    def test_llhistwire_roundtrip_fuzz(self):
        from veneur_tpu.forward import llhistwire
        rng = np.random.default_rng(7)
        for _ in range(30):
            bins = np.zeros(llhist_ref.BINS, np.int64)
            n = int(rng.integers(0, 200))
            if n:
                idx = rng.choice(llhist_ref.BINS, n, replace=False)
                bins[idx] = rng.integers(1, 1 << 48, n)
            assert np.array_equal(
                llhistwire.unmarshal(llhistwire.marshal(bins)), bins)
        dense = rng.integers(0, 5, llhist_ref.BINS).astype(np.int64)
        assert np.array_equal(
            llhistwire.unmarshal(llhistwire.marshal(dense)), dense)

    def test_proto_roundtrip_bit_exact(self):
        """forwardable llhist -> metricpb -> import decode recovers the
        registers bit-exactly."""
        from veneur_tpu.forward import llhistwire
        from veneur_tpu.forward.convert import (forwardable_to_protos,
                                                forwardable_to_wire)
        from veneur_tpu.forward.protos import metric_pb2

        store = _mk_store()
        _feed(store, [b"wire.k:%.4f|l|#env:t" % v
                      for v in np.random.default_rng(8).lognormal(2, 1, 300)])
        _, fwd = flush_columnstore(store, True, PCTS, AGGS)
        assert len(fwd.llhists) == 1
        meta, bins = fwd.llhists[0]
        protos = forwardable_to_protos(fwd)
        [pb] = [p for p in protos if p.WhichOneof("value") == "llhist"]
        assert pb.type == metric_pb2.LLHist
        rt = metric_pb2.Metric.FromString(pb.SerializeToString())
        assert np.array_equal(llhistwire.unmarshal(rt.llhist.bins), bins)
        # wire bytes match the proto serialization exactly
        assert pb.SerializeToString() in forwardable_to_wire(fwd)


class TestForwardTier:
    def test_global_percentile_bit_identical_to_single_node(self):
        """THE acceptance pin: two locals forward their bins; the global
        merge is bit-identical to a single-node llhist over the union
        stream — quantiles, counts, sums, buckets, everything."""
        from veneur_tpu.forward import server as fsrv
        from veneur_tpu.forward.convert import forwardable_to_protos
        from veneur_tpu.forward.protos import metric_pb2

        rng = np.random.default_rng(9)
        samples = rng.lognormal(2, 1.2, 1000)
        line = b"fwd.lat:%.6f|l|#svc:api"
        single = _mk_store()
        _feed(single, [line % v for v in samples])
        want, _ = flush_columnstore(single, False, PCTS, AGGS)

        locals_ = [_mk_store(), _mk_store()]
        _feed(locals_[0], [line % v for v in samples[:500]])
        _feed(locals_[1], [line % v for v in samples[500:]])
        global_store = _mk_store()

        class _Srv:
            _ignored = []

            class _S:
                pass
        srv = _Srv()
        srv._server = _Srv._S()
        srv._server.store = global_store
        buf = fsrv._MergeBuffer(srv)
        for st in locals_:
            _, fwd = flush_columnstore(st, True, PCTS, AGGS)
            for pb in forwardable_to_protos(fwd):
                buf.add(metric_pb2.Metric.FromString(pb.SerializeToString()))
        buf.flush_all()
        got, _ = flush_columnstore(global_store, False, PCTS, AGGS)

        def key(mm):
            return (mm.name, tuple(sorted(mm.tags)), int(mm.type))

        want_map = {key(mm): mm.value for mm in want}
        got_map = {key(mm): mm.value for mm in got}
        assert want_map.keys() == got_map.keys()
        for k in want_map:  # BIT-identical, not approximately equal
            assert got_map[k] == want_map[k], k

    def test_forward_import_over_grpc(self):
        """Full-plane integration: ForwardClient -> ImportServer."""
        from veneur_tpu.config import Config
        from veneur_tpu.core.server import Server
        from veneur_tpu.forward.client import ForwardClient
        from veneur_tpu.forward.server import ImportServer

        cfg = Config()
        cfg.interval = 3600.0
        cfg.statsd_listen_addresses = []
        cfg.apply_defaults()
        global_server = Server(cfg)
        imp = ImportServer(global_server, "127.0.0.1:0")
        imp.start()
        client = ForwardClient(imp.address, deadline=10.0)
        try:
            local = _mk_store()
            _feed(local, [b"grpc.lat:%.4f|l" % v for v in
                          np.random.default_rng(10).lognormal(1, 1, 200)])
            _, fwd = flush_columnstore(local, True, PCTS, AGGS)
            bins_sent = fwd.llhists[0][1].copy()
            assert client.forward(fwd) > 0
            table = global_server.store.llhists
            out, bins, touched, meta = table.snapshot_and_reset(PCTS)
            assert bins.shape[0] == 1
            assert np.array_equal(bins[0], bins_sent)
        finally:
            client.close()
            imp.stop()
            global_server.shutdown()


class TestTableBatchPath:
    def test_add_batch_matches_per_sample_add(self):
        """The columnar entry point (pre-interned rows, raw values,
        1/sample_rate weights) must land the same registers as the
        per-sample add path."""
        rng = np.random.default_rng(13)
        vals = rng.lognormal(1, 1, 600)
        rates = rng.choice([1.0, 0.5, 0.1], 600)
        s_batch, s_single = _mk_store(), _mk_store()
        p = Parser()
        stub = []
        p.parse_metric_fast(b"ab.k:1|l", stub.append)
        row_b = s_batch.llhists.intern(stub[0])
        s_batch.llhists.add_batch(
            np.full(600, row_b, np.int32), vals, 1.0 / rates)
        s_batch.llhists.apply_pending()
        from veneur_tpu.samplers.metrics import UDPMetric
        mm = stub[0]
        for v, r in zip(vals, rates):
            s_single.llhists.add(UDPMetric(
                key=mm.key, digest=mm.digest, digest64=mm.digest64,
                value=float(v), sample_rate=float(r), tags=mm.tags,
                scope=mm.scope))
        s_single.llhists.apply_pending()
        _, bins_b, _, _ = s_batch.llhists.snapshot_and_reset(PCTS)
        _, bins_s, _, _ = s_single.llhists.snapshot_and_reset(PCTS)
        assert np.array_equal(bins_b[0], bins_s[0])
        assert s_batch.llhists.samples_total == \
            s_single.llhists.samples_total


class TestEncodingSwitch:
    def test_parser_l_type(self):
        p = Parser()
        got = []
        p.parse_metric_fast(b"enc.x:1.5:2.5|l|#a:b", got.append)
        assert [mm.key.type for mm in got] == [m.LLHIST, m.LLHIST]
        assert [mm.value for mm in got] == [1.5, 2.5]

    def test_circllhist_encoding_routes_histograms(self):
        store = _mk_store(histogram_encoding="circllhist")
        _feed(store, [b"enc.t:12.5|ms", b"enc.h:3.5|h", b"enc.l:1|l"])
        assert len(store.llhists.rows) == 3
        assert len(store.histos.rows) == 0

    def test_tdigest_encoding_keeps_histograms(self):
        store = _mk_store()
        _feed(store, [b"enc.t:12.5|ms", b"enc.l:1|l"])
        assert len(store.histos.rows) == 1
        assert len(store.llhists.rows) == 1

    def test_unknown_encoding_rejected(self):
        with pytest.raises(ValueError):
            _mk_store(histogram_encoding="sparkline")


class TestFlushEmission:
    def test_buckets_cumulative_with_inf(self):
        store = _mk_store()
        _feed(store, [b"em.q:1.0:1.0:5.0:50.0|l|#env:t"])
        final, _ = flush_columnstore(store, False, PCTS, AGGS)
        buckets = [mm for mm in final if mm.name == "em.q.bucket"]
        assert buckets, [mm.name for mm in final]
        assert all(mm.type == MetricType.COUNTER for mm in buckets)
        vals = [mm.value for mm in buckets]
        assert vals == sorted(vals)  # cumulative over ascending le
        inf = [mm for mm in buckets if "le:+Inf" in mm.tags]
        assert len(inf) == 1 and inf[0].value == 4.0
        count = [mm for mm in final if mm.name == "em.q.count"]
        assert count[0].value == 4.0
        assert count[0].type == MetricType.COUNTER

    def test_local_mixed_forwards_not_emits(self):
        store = _mk_store()
        _feed(store, [b"fw.q:3.5|l"])
        final, fwd = flush_columnstore(store, True, PCTS, AGGS)
        assert not [mm for mm in final if mm.name.startswith("fw.q")]
        assert len(fwd.llhists) == 1

    def test_local_only_rows_flush_locally(self):
        store = _mk_store()
        _feed(store, [b"lo.q:3.5|l|#veneurlocalonly"])
        final, fwd = flush_columnstore(store, True, PCTS, AGGS)
        assert [mm for mm in final if mm.name == "lo.q.count"]
        assert not fwd.llhists

    def test_batch_path_parity(self):
        lines = [b"par.q:%.4f|l|#env:t" % v for v in
                 np.random.default_rng(11).lognormal(1, 1, 400)]
        lines += [b"par.local:2.5|l|#veneurlocalonly",
                  b"par.glob:9.5|l|#veneurglobalonly"]
        for is_local in (False, True):
            s1, s2 = _mk_store(), _mk_store()
            _feed(s1, lines)
            _feed(s2, lines)
            final, fwd1 = flush_columnstore(s1, is_local, PCTS, AGGS)
            batch, fwd2 = flush_columnstore_batch(s2, is_local, PCTS, AGGS)

            def key(mm):
                return (mm.name, round(float(mm.value), 6),
                        tuple(sorted(mm.tags)), int(mm.type))
            assert sorted(map(key, batch.materialize())) == \
                sorted(map(key, final))
            assert len(fwd1.llhists) == len(fwd2.llhists)
            for (m1, b1), (m2, b2) in zip(
                    sorted(fwd1.llhists, key=lambda e: e[0].name),
                    sorted(fwd2.llhists, key=lambda e: e[0].name)):
                assert m1.name == m2.name
                assert np.array_equal(b1, b2)


def _sparse_table(rows: int, width: int, seed: int = 5) -> np.ndarray:
    """An int32 register table as the device hands it over: six
    lognormal samples a row (the benchmark's llhist traffic), some
    negative."""
    rng = np.random.default_rng(seed)
    table = np.zeros((rows, width), np.int32)
    vals = rng.lognormal(1.0, 2.0, (rows, 6)) * rng.choice(
        [1.0, 1.0, -1.0], (rows, 6))
    np.add.at(table, (np.repeat(np.arange(rows), 6),
                      llhist_ref.bin_index(vals.ravel())), 1)
    return table


class TestSumDefinition:
    """One definition of `.sum` (llhist_ref.entry_sums): it reads the
    registers alone (ROADMAP D16)."""

    @pytest.mark.parametrize("variant", [
        "permuted_rows", "padded_width", "int64_registers", "row_by_row",
        "split_over_shards"])
    def test_sum_depends_on_the_registers_alone(self, variant):
        table = _sparse_table(200, llhist_ref.BINS)
        # one row with 1,125 live registers, of one sign: the BLAS
        # comparison below cannot hold where +-1e15 bins cancel
        table[7, 1:llhist_ref.NEG_BASE:2] = 9
        want = llhist_ref.entry_sums(
            *llhist_ref.nonzero_entries(table), table.shape[0])
        if variant == "permuted_rows":
            perm = np.random.default_rng(1).permutation(table.shape[0])
            got = np.empty_like(want)
            got[perm] = llhist_ref.entry_sums(
                *llhist_ref.nonzero_entries(table[perm]), table.shape[0])
        elif variant == "padded_width":
            padded = batch_llhist.pad_rows_to_device(table)
            assert padded.shape[1] == batch_llhist.BINS_PAD
            got = llhist_ref.entry_sums(
                *llhist_ref.nonzero_entries(padded), table.shape[0])
        elif variant == "int64_registers":
            got = llhist_ref.entry_sums(
                *llhist_ref.nonzero_entries(table.astype(np.int64)),
                table.shape[0])
        elif variant == "row_by_row":
            got = np.array([llhist_ref.approx_sum(row) for row in table])
        else:
            # registers merge exactly, so whatever parts a row was
            # summed from, its sum is the whole row's
            parts = np.random.default_rng(2).integers(0, 4, table.shape)
            merged = sum((table * (parts == k)).astype(np.int64)
                         for k in range(4))
            got = llhist_ref.entry_sums(
                *llhist_ref.nonzero_entries(merged), table.shape[0])
        assert got.tobytes() == want.tobytes()
        np.testing.assert_allclose(
            want, table.astype(np.float64) @ llhist_ref.BIN_MID,
            rtol=1e-12)

    def test_cumulative_entries_are_the_rows_cumulative_buckets(self):
        table = _sparse_table(50, batch_llhist.BINS_PAD)
        table[3, :llhist_ref.BINS:2] = 4
        table[9] = 0  # touched, nothing left: no entry, an +Inf of 0
        rows, bins, counts = llhist_ref.nonzero_entries(table)
        indptr, rank, cum, total = llhist_ref.cumulative_entries(
            rows, bins, counts, table.shape[0])
        assert cum.dtype == total.dtype == np.int64
        for i, row in enumerate(table[:, :llhist_ref.BINS]):
            upper, csum = llhist_ref.LLHist(row).cumulative_buckets()
            lo, hi = indptr[i], indptr[i + 1]
            assert llhist_ref.UPPER_SORTED[rank[lo:hi]].tolist() == \
                upper.tolist()
            assert cum[lo:hi].tolist() == csum.tolist()
            assert total[i] == row.sum()

    def test_assembly_allocates_no_dense_table(self):
        """1,024 touched llhist rows flush without a (rows, BINS) array
        beside the transferred int32 table: a float64 or int64 one is
        36.9 MB, a bool one 4.6 MB."""
        import tracemalloc

        rows = 1024
        store = ColumnStore(llhist_capacity=rows, batch_cap=8192)
        table = store.llhists
        p = Parser()
        ids = []
        for i in range(rows):
            p.parse_metric_fast(b"ll.%d:1|l" % i,
                                lambda mm: ids.append(table.intern(mm)))
        ids = np.repeat(np.asarray(ids, np.int32), 6)
        vals = np.random.default_rng(3).lognormal(1.0, 2.0, ids.size)

        def one_flush():
            table.add_batch(ids, vals, np.ones(ids.size))
            store.apply_all_pending()
            return flush_columnstore_batch(store, False, PCTS, AGGS)

        one_flush()  # compile, fill the name and tag caches
        transferred = rows * batch_llhist.BINS_PAD * 4
        tracemalloc.start()
        try:
            batch, _fwd = one_flush()
            _now, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        [section] = batch.bucket_sections
        assert section.names.shape[0] == rows
        assert 5000 < section.le_idx.shape[0] <= 6 * rows
        # the transferred table itself is traced only where np.asarray
        # of the device array copies (on the CPU backend it does not)
        room = 3 * 2**20
        assert peak < room or transferred <= peak < transferred + room, peak


class TestCarryover:
    def test_merge_forwardable_llhists_sum(self):
        from veneur_tpu.core.columnstore import RowMeta
        from veneur_tpu.samplers.metrics import MetricScope
        from veneur_tpu.util.resilience import merge_forwardable

        def meta(name):
            return RowMeta(name=name, tags=[], joined_tags="", digest32=1,
                           scope=MetricScope.MIXED, wire_type=m.LLHIST)

        a = np.zeros(llhist_ref.BINS, np.int64)
        b = np.zeros(llhist_ref.BINS, np.int64)
        a[10], b[10], b[20] = 5, 7, 3
        newer = ForwardableState(llhists=[(meta("x"), a)])
        older = ForwardableState(llhists=[(meta("x"), b),
                                          (meta("y"), b.copy())])
        merged = merge_forwardable(newer, older)
        by_name = {mm.name: bins for mm, bins in merged.llhists}
        assert by_name["x"][10] == 12 and by_name["x"][20] == 3
        assert by_name["y"][10] == 7

    @pytest.mark.chaos
    def test_carryover_register_sum_lossless_under_chaos(self):
        """PR-2 chaos soak, llhist edition: rounds of forwarding with a
        30% injected fault rate deliver exactly the register sums a
        fault-free run delivers — nothing lost, nothing double-counted."""
        from veneur_tpu.forward.client import ForwardClient
        from veneur_tpu.testing.forwardtest import ForwardTestServer
        from veneur_tpu.util import chaos as chaos_mod
        from veneur_tpu.util.chaos import Chaos
        from veneur_tpu.forward import llhistwire

        def run_rounds(error_rate, rounds=8, seed=12):
            received = []
            ft = ForwardTestServer(received.extend)
            ft.start()
            chaos = (Chaos(error_rate=error_rate,
                           seams=("forward_send",), seed=seed)
                     if error_rate else None)
            client = ForwardClient(ft.address, deadline=5.0, chaos=chaos)
            client.retry.max_attempts = 1  # carryover alone must carry
            client.carryover.max_intervals = 1000
            client.breaker.failure_threshold = 10_000
            rng = np.random.default_rng(seed)
            sent = np.zeros(llhist_ref.BINS, np.int64)
            try:
                store = _mk_store()
                for i in range(rounds):
                    _feed(store, [b"soak.lat:%.4f|l" % v
                                  for v in rng.lognormal(1, 1, 50)])
                    _, fwd = flush_columnstore(store, True, PCTS, AGGS)
                    sent += fwd.llhists[0][1]
                    client.forward(fwd)
                if chaos is not None:
                    chaos.enabled = False
                # clean drain flush for any pending carryover
                client.forward(ForwardableState())
                assert client.carryover.depth == 0
                got = np.zeros(llhist_ref.BINS, np.int64)
                for pb in received:
                    if pb.WhichOneof("value") == "llhist":
                        got += llhistwire.unmarshal(pb.llhist.bins)
                return got, sent
            finally:
                client.close()
                ft.stop()

        got_chaos, sent_chaos = run_rounds(0.3)
        got_clean, sent_clean = run_rounds(0.0)
        assert np.array_equal(sent_chaos, sent_clean)
        assert np.array_equal(got_clean, sent_clean)  # control
        assert np.array_equal(got_chaos, sent_chaos)  # zero loss

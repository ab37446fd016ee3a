"""HyperLogLog accuracy tests: scalar reference and batched device kernel.
The p=14 sketch has ~0.8% standard error; we allow 3 sigma."""

import numpy as np
import pytest

from veneur_tpu.ops import batch_hll as bhll
from veneur_tpu.ops.hll_ref import HLL, hash_member, pos_val


class TestScalarHLL:
    @pytest.mark.parametrize("n", [100, 1000, 10000, 100000])
    def test_estimate_accuracy(self, n):
        h = HLL()
        for i in range(n):
            h.insert(b"member-%d" % i)
        assert h.estimate() == pytest.approx(n, rel=0.03)

    def test_duplicates_not_counted(self):
        h = HLL()
        for _ in range(5):
            for i in range(1000):
                h.insert(b"m%d" % i)
        assert h.estimate() == pytest.approx(1000, rel=0.03)

    def test_merge(self):
        a, b = HLL(), HLL()
        for i in range(5000):
            a.insert(b"a%d" % i)
            b.insert(b"b%d" % i)
        a.merge(b)
        assert a.estimate() == pytest.approx(10000, rel=0.03)

    def test_merge_overlapping(self):
        a, b = HLL(), HLL()
        for i in range(5000):
            a.insert(b"x%d" % i)
            b.insert(b"x%d" % i)
        a.merge(b)
        assert a.estimate() == pytest.approx(5000, rel=0.03)

    def test_serialization_roundtrip(self):
        a = HLL()
        for i in range(1234):
            a.insert(b"v%d" % i)
        b = HLL.from_bytes(a.to_bytes())
        assert b.estimate() == a.estimate()

    def test_empty(self):
        assert HLL().estimate() == pytest.approx(0, abs=1)


class TestBatchedHLL:
    def _ingest(self, members_by_row, num_keys, batch=4096):
        regs = bhll.init_state(num_keys)
        coo = []
        for row, members in members_by_row.items():
            for member in members:
                idx, rho = pos_val(hash_member(member))
                coo.append((row, idx, rho))
        for i in range(0, len(coo), batch):
            chunk = coo[i:i + batch]
            pad = batch - len(chunk)
            rows = np.array([c[0] for c in chunk] + [num_keys] * pad, np.int32)
            idxs = np.array([c[1] for c in chunk] + [0] * pad, np.int32)
            rhos = np.array([c[2] for c in chunk] + [0] * pad, np.int32)
            regs = bhll.apply_batch(regs, rows, idxs, rhos)
        return regs

    def test_matches_scalar(self):
        members = [b"user-%d" % i for i in range(20000)]
        regs = self._ingest({0: members, 1: members[:500]}, 2)
        scalar = HLL()
        for member in members:
            scalar.insert(member)
        est = bhll.estimate(regs)
        assert float(est[0]) == pytest.approx(scalar.estimate(), rel=1e-6)
        assert float(est[1]) == pytest.approx(500, rel=0.05)
        # registers must be identical to the scalar sketch
        np.testing.assert_array_equal(np.asarray(regs)[0], scalar.regs)

    def test_empty_row_estimates_zero(self):
        regs = bhll.init_state(2)
        est = bhll.estimate(regs)
        assert float(est[0]) == 0.0

    def test_merge_rows(self):
        a = self._ingest({0: [b"a%d" % i for i in range(3000)]}, 2)
        b_scalar = HLL()
        for i in range(3000):
            b_scalar.insert(b"b%d" % i)
        merged = bhll.merge_rows(
            a, np.array([0], np.int32), b_scalar.regs[None, :])
        est = bhll.estimate(merged)
        assert float(est[0]) == pytest.approx(6000, rel=0.03)

    def test_shard_merge(self):
        a = self._ingest({0: [b"m%d" % i for i in range(4000)]}, 1)
        b = self._ingest({0: [b"m%d" % i for i in range(2000, 6000)]}, 1)
        merged = bhll.merge(a, b)
        est = bhll.estimate(merged)
        assert float(est[0]) == pytest.approx(6000, rel=0.03)


class TestScalarKernels:
    def test_counters(self):
        from veneur_tpu.ops import scalars
        state = scalars.init_counters(4)
        rows = np.array([0, 0, 1, 4, 2], np.int32)  # 4 = padding
        vals = np.array([1.0, 2.0, 5.0, 99.0, 1.0], np.float32)
        rates = np.array([1.0, 0.5, 1.0, 1.0, 0.1], np.float32)
        state = scalars.apply_counters(state, rows, vals, rates)
        assert scalars.counter_values(state).tolist() == [5.0, 5.0, 10.0, 0.0]

    def test_counter_truncation_per_sample(self):
        # parity: each sample contributes trunc(value/rate)
        from veneur_tpu.ops import scalars
        state = scalars.init_counters(1)
        rows = np.array([0, 0], np.int32)
        vals = np.array([1.0, 1.0], np.float32)
        rates = np.array([0.3, 0.3], np.float32)
        state = scalars.apply_counters(state, rows, vals, rates)
        # trunc(3.33)*2, not trunc(6.66)
        assert float(scalars.counter_values(state)[0]) == 6.0

    def test_counter_kahan_precision(self):
        # many small batches must not drift past f32 granularity
        from veneur_tpu.ops import scalars
        state = scalars.init_counters(1)
        rows = np.zeros(1024, np.int32)
        vals = np.full(1024, 33.0, np.float32)
        rates = np.ones(1024, np.float32)
        for _ in range(600):  # 600 * 1024 * 33 = 20,275,200 > 2^24
            state = scalars.apply_counters(state, rows, vals, rates)
        got = float(scalars.counter_values(state)[0])
        assert got == 600 * 1024 * 33.0

    def test_gauges_last_write_wins(self):
        from veneur_tpu.ops import scalars
        state = scalars.init_gauges(3)
        rows = np.array([0, 1, 0, 3], np.int32)
        vals = np.array([1.0, 2.0, 7.0, 99.0], np.float32)
        state = scalars.apply_gauges(state, rows, vals)
        assert state["value"].tolist() == [7.0, 2.0, 0.0]
        assert state["set"].tolist() == [True, True, False]
        # second batch: only row 1 updated
        state = scalars.apply_gauges(
            state, np.array([1], np.int32), np.array([5.0], np.float32))
        assert state["value"].tolist() == [7.0, 5.0, 0.0]


class TestSparseSetTable:
    """Two-tier set representation (reference keeps small HLLs sparse,
    vendor hyperloglog sparse.go): small keys never allocate device
    registers, hot keys promote mid-interval, and both tiers produce
    identical estimates and register rows."""

    def _mk(self, capacity=512, batch_cap=64, promote_samples=0,
            max_dev_slots=0):
        from veneur_tpu.core.columnstore import SetTable
        return SetTable(capacity, batch_cap, sparse=True,
                        promote_samples=promote_samples,
                        max_dev_slots=max_dev_slots)

    def _stub(self, name):
        from veneur_tpu.samplers.parser import Parser
        out = []
        Parser().parse_metric_fast(b"%s:x|s" % name, out.append)
        return out[0]

    @staticmethod
    def _promote_interned(table) -> int:
        """Promote every interned row the slot limit admits; returns
        the promoted-slot count."""
        with table.lock:
            for row in range(len(table.meta)):
                if table._slot_of[row] < 0:
                    table._promote_locked(row)
            return table._nslots

    def test_small_sets_stay_off_device(self):
        # explicit high threshold: the point here is the sparse tier's
        # estimate/register parity, independent of the promote policy
        import numpy as np
        from veneur_tpu.ops import hll_ref
        table = self._mk(promote_samples=2048)
        members = [b"m%d" % i for i in range(500)]
        rows, idxs, rhos = [], [], []
        stub = self._stub(b"sp.small")
        with table.lock:
            row = table.row_for(stub)
        for m in members:
            i, r = hll_ref.pos_val(hll_ref.hash_member(m))
            rows.append(row); idxs.append(i); rhos.append(r)
        table.add_batch(np.array(rows, np.int32), np.array(idxs, np.int32),
                        np.array(rhos, np.int32))
        table.apply_pending()
        assert table._nslots == 0  # never promoted
        est, regs, touched, _ = table.snapshot_and_reset()
        oracle = hll_ref.HLL()
        for m in members:
            oracle.insert(m)
        assert float(est[row]) == oracle.estimate()
        np.testing.assert_array_equal(regs[row], oracle.regs)

    def test_hot_key_promotes_and_matches_dense(self):
        import numpy as np
        from veneur_tpu.ops import hll_ref
        table = self._mk(batch_cap=256)
        stub = self._stub(b"sp.hot")
        with table.lock:
            row = table.row_for(stub)
        oracle = hll_ref.HLL()
        rng = np.random.default_rng(3)
        for chunk in range(5):
            members = [b"h%d" % i for i in rng.integers(0, 100_000, 1000)]
            cols = ([], [], [])
            for m in members:
                i, r = hll_ref.pos_val(hll_ref.hash_member(m))
                oracle.insert(m)
                cols[0].append(row); cols[1].append(i); cols[2].append(r)
            table.add_batch(np.array(cols[0], np.int32),
                            np.array(cols[1], np.int32),
                            np.array(cols[2], np.int32))
        table.apply_pending()
        assert table._slot_of[row] >= 0  # promoted mid-interval
        est, regs, _t, _m = table.snapshot_and_reset()
        # pre-promotion backlog folded in: registers exactly match oracle
        np.testing.assert_array_equal(regs[row], oracle.regs)
        assert float(est[row]) == oracle.estimate()

    def test_ladder_climb_inside_one_interval_matches_reference(self):
        """300 keys of 16 members promote at their 16th and climb the
        bank from 256 to 2,048 slots within one interval; their first 15
        members wait in the host backlog and fold into the bank at the
        flush in one `fold_backlog`. Ten keys of 5 members stay on the
        host. After the fold and the collect, every estimate and every
        register row is the reference's, and the flush counters say what
        the round did."""
        import numpy as np
        from veneur_tpu.ops import hll_ref
        table = self._mk(capacity=512, batch_cap=256, promote_samples=16)
        assert table._ladder() == [256, 2048]
        rng = np.random.default_rng(2_147_484_101)
        sizes = [16] * 300 + [5] * 10
        rows, oracle, lines = [], [], []
        for k, n in enumerate(sizes):
            with table.lock:
                rows.append(table.row_for(self._stub(b"lad.%d" % k)))
            oracle.append(hll_ref.HLL())
            for m in rng.integers(0, 1 << 40, n).tolist():
                member = b"u%d" % m
                oracle[k].insert(member)
                lines.append((rows[k], *hll_ref.pos_val(
                    hll_ref.hash_member(member))))
        order = rng.permutation(len(lines))
        cols = np.asarray(lines, np.int32)[order].T
        for at in range(0, cols.shape[1], 100):   # the pump's chunks
            table.add_batch(*(c[at:at + 100].copy() for c in cols))
        assert table._dev_cap == 2048 and table.slot_ladder_climbs_total == 1
        # a promoted key's lines before its promotion's chunk wait in
        # the backlog; the rest of the bank's entries are pending or
        # applied already
        coo_rows = np.concatenate([c[0] for c in table._coo])
        backlog = int(np.count_nonzero(table._slot_of[coo_rows] >= 0))
        assert 300 * 12 < backlog <= 300 * 15
        pending = table._n
        snap = table.collect(table.readout(table.swap_out(), collect=False))
        est, regs = snap["estimates"], snap["registers"]
        for k, row in enumerate(rows):
            assert float(est[row]) == oracle[k].estimate(), k
            np.testing.assert_array_equal(regs[row], oracle[k].regs)
        assert table.fold_entries_total == backlog + pending
        # the rung's fold size holds the whole backlog: one dispatch,
        # after the last pending batch's
        assert table._fold_size(2048) == 2048 * 15
        assert table.fold_dispatches_total == -(
            -backlog // table._fold_size(2048)) + (1 if pending else 0)
        assert table.fold_dispatches_total == 1 + (1 if pending else 0)
        assert (table.device_rows_total, table.host_rows_total) == (300, 10)

    def test_dev_slot_cap_keeps_overflow_keys_sparse(self):
        """Past MAX_DEV_SLOTS (the HBM guard) hot keys stay on the host
        tier and still estimate correctly."""
        import numpy as np
        from veneur_tpu.ops import hll_ref
        table = self._mk(batch_cap=256, promote_samples=4, max_dev_slots=2)
        rows_of = {}
        for name in (b"cap.a", b"cap.b", b"cap.c", b"cap.d"):
            stub = self._stub(name)
            with table.lock:
                rows_of[name] = table.row_for(stub)
        oracle = {n: hll_ref.HLL() for n in rows_of}
        cols = ([], [], [])
        for n, row in rows_of.items():
            for i in range(200):
                m = b"%s-%d" % (n, i)
                oracle[n].insert(m)
                ix, rh = hll_ref.pos_val(hll_ref.hash_member(m))
                cols[0].append(row); cols[1].append(ix); cols[2].append(rh)
        table.add_batch(np.array(cols[0], np.int32),
                        np.array(cols[1], np.int32),
                        np.array(cols[2], np.int32))
        table.apply_pending()
        assert table._nslots == 2  # capped, not 4
        est, regs, _t, _m = table.snapshot_and_reset()
        for n, row in rows_of.items():
            assert float(est[row]) == oracle[n].estimate(), n
            np.testing.assert_array_equal(regs[row], oracle[n].regs)

    def test_promotion_of_interned_rows_stops_at_the_slot_cap(self):
        """Promoting every interned row stops at the slot cap; estimates
        after a real interval stay correct."""
        import numpy as np
        from veneur_tpu.ops import hll_ref
        table = self._mk(batch_cap=256, promote_samples=2048,
                         max_dev_slots=3)
        rows = []
        for name in (b"pw.a", b"pw.b", b"pw.c", b"pw.d"):
            stub = self._stub(name)
            with table.lock:
                rows.append(table.row_for(stub))
        assert table._nslots == 0  # nothing promoted yet (big threshold)
        assert self._promote_interned(table) == 3  # capped at max_dev_slots
        assert sorted(int(table._slot_of[r]) >= 0 for r in rows) == \
            [False, True, True, True]
        # a normal interval after prewarm: samples route per tier and
        # the flush estimates every key correctly
        oracle = {r: hll_ref.HLL() for r in rows}
        cols = ([], [], [])
        for r in rows:
            for i in range(20):
                m = b"%d-%d" % (r, i)
                oracle[r].insert(m)
                ix, rh = hll_ref.pos_val(hll_ref.hash_member(m))
                cols[0].append(r); cols[1].append(ix); cols[2].append(rh)
        table.add_batch(np.array(cols[0], np.int32),
                        np.array(cols[1], np.int32),
                        np.array(cols[2], np.int32))
        table.apply_pending()
        est, regs, _t, _m = table.snapshot_and_reset()
        for r in rows:
            assert float(est[r]) == oracle[r].estimate(), r
            np.testing.assert_array_equal(regs[r], oracle[r].regs)

    def test_import_merge_at_slot_cap_folds_to_host_tier(self):
        """merge_batch past MAX_DEV_SLOTS must fold imported registers
        into the sparse tier, not scatter to slot -1 (which aliases the
        last device row and corrupts another key)."""
        import numpy as np
        from veneur_tpu.ops import hll_ref
        table = self._mk(batch_cap=256, promote_samples=4, max_dev_slots=1)
        # occupy the single device slot with a promoted key
        hot_stub = self._stub(b"imp.hot")
        with table.lock:
            hot_row = table.row_for(hot_stub)
        hot_oracle = hll_ref.HLL()
        cols = ([], [], [])
        for i in range(50):
            m = b"hot-%d" % i
            hot_oracle.insert(m)
            ix, rh = hll_ref.pos_val(hll_ref.hash_member(m))
            cols[0].append(hot_row); cols[1].append(ix); cols[2].append(rh)
        table.add_batch(np.array(cols[0], np.int32),
                        np.array(cols[1], np.int32),
                        np.array(cols[2], np.int32))
        table.apply_pending()
        assert table._slot_of[hot_row] >= 0 and table._nslots == 1
        # import a dense sketch for a DIFFERENT key: promotion is capped
        imp_oracle = hll_ref.HLL()
        for i in range(300):
            imp_oracle.insert(b"imp-%d" % i)
        imp_stub = self._stub(b"imp.capped")
        table.merge_batch([imp_stub], imp_oracle.regs[None, :])
        with table.lock:
            imp_row = table.row_for(imp_stub)
        assert table._slot_of[imp_row] < 0  # stayed on the host tier
        est, regs, _t, _m = table.snapshot_and_reset()
        # the imported key estimates correctly from the host tier...
        assert float(est[imp_row]) == imp_oracle.estimate()
        np.testing.assert_array_equal(regs[imp_row], imp_oracle.regs)
        # ...and the promoted key was not corrupted by a -1 scatter
        assert float(est[hot_row]) == hot_oracle.estimate()
        np.testing.assert_array_equal(regs[hot_row], hot_oracle.regs)

    def test_capacity_clamps_promotion_until_growth(self):
        """With capacity < MAX_DEV_SLOTS the promotion limit is the row
        capacity (slots beyond the table's rows are unreachable); when
        the host table grows, promotion resumes and the device cap grows
        with it."""
        import numpy as np
        from veneur_tpu.core.columnstore import SetTable
        table = SetTable(capacity=8, batch_cap=64, sparse=True,
                         promote_samples=1, max_dev_slots=65536)
        # intern 8 rows at capacity 8
        stubs = [self._stub(b"cl.%d" % i) for i in range(8)]
        with table.lock:
            for s in stubs:
                table.row_for(s)
        assert self._promote_interned(table) == 8
        assert table._nslots == 8
        # at the clamp: a promotion attempt is a no-op, not state growth
        table._promote_locked(0)
        assert table._nslots == 8
        # interning a 9th key doubles the host table; promotion resumes
        extra = self._stub(b"cl.extra")
        with table.lock:
            row9 = table.row_for(extra)
        assert table.capacity == 16
        assert self._promote_interned(table) == 9
        assert table._slot_of[row9] >= 0
        assert table._dev_cap >= 9  # device cap regrew past the old clamp
        # and the dense tier still aggregates for the new slot
        ix, rh = 5, 3
        table.add_batch(np.array([row9], np.int32),
                        np.array([ix], np.int32), np.array([rh], np.int32))
        table.apply_pending()
        est, regs, _t, _m = table.snapshot_and_reset()
        assert regs[row9][ix] == rh

    def test_interval_reset_demotes(self):
        import numpy as np
        table = self._mk(batch_cap=256)
        stub = self._stub(b"sp.reset")
        with table.lock:
            row = table.row_for(stub)
        rows = np.full(4096, row, np.int32)
        idxs = np.arange(4096).astype(np.int32) % 16384
        rhos = np.ones(4096, np.int32)
        table.add_batch(rows, idxs, rhos)
        table.apply_pending()
        assert table._nslots == 1
        table.snapshot_and_reset()
        assert table._nslots == 0  # interval-scoped, like every family
        est, _r, _t, _m = table.snapshot_and_reset()
        assert float(est[row]) == 0.0


# -- the flush's backlog fold: one device program ----------------------------
#
# Each case feeds a sparse table (key, register, rho) triples in the
# pump's chunks, swaps, and folds the captured generation through the
# table's own readout. Registers and estimates must be the reference's
# (`hll_ref`, built from the same triples) and the captured bank must be
# byte for byte what the fold before `fold_backlog` made of the same snap:
# the last pending batch, then the promoted rows' backlog in batch_cap
# chunks of `apply_batch`. The "pallas" side runs the TPU pass itself
# (sort, block offsets, the kernel) in interpret mode.

def _fold_case(name, rng):
    """(table kwargs, phases of (key, register, rho) triples, a threshold
    to set between the phases or None, the fold's dispatches)."""
    M = bhll.M

    def members(keys, n, first=0):
        return [(k, int(rng.integers(0, M)), int(rng.integers(1, 20)))
                for k in keys for _ in range(first, n)]

    if name == "duplicates":
        # one register of each key thrice in its backlog, rho 5, 9, 2;
        # the key promotes at its 4th triple
        one = [(k, 77 + k, r) for k in range(6) for r in (5, 9, 2)]
        return (dict(capacity=64, promote_samples=4),
                [one, members(range(6), 3)], None, 1)
    if name == "empty":
        # every key promotes at its first chunk: nothing waits
        return (dict(capacity=64, promote_samples=1),
                [members(range(10), 20)], None, 0)
    if name == "at_size":
        # 256 keys x 4 waiting = 1,024 pairs, the rung's fold size
        return (dict(capacity=256, promote_samples=5),
                [members(range(256), 4), members(range(256), 1)], None, 1)
    if name == "over_in_chunks":
        # 200 keys wait 8 members each; the threshold then falls to 2, so
        # 1,600 pairs meet a fold size of 1,024 (256 x 1, one window)
        return (dict(capacity=256, promote_samples=16),
                [members(range(200), 8), members(range(200), 1)], 2, 2)
    if name == "cpu_auto":
        # the CPU backend's own threshold (2,048): three keys promote at
        # their 2,048th member, twenty stay on the host
        hot = members(range(3), 2100)
        return (dict(capacity=64, promote_samples=0),
                [hot[i::3] for i in range(3)] + [members(range(3, 23), 7)],
                None, 1)
    if name == "ladder_climb":
        # 300 keys promote at their 16th member and climb the bank
        # 256 -> 2,048 inside the interval
        return (dict(capacity=512, promote_samples=16),
                [members(range(300), 15), members(range(300), 1)], None, 1)
    raise KeyError(name)


FOLD_CASES = ["duplicates", "empty", "at_size", "over_in_chunks",
              "cpu_auto", "ladder_climb"]


def _chunked_fold(snap, batch_cap):
    """The fold as it was before `fold_backlog`, on a copy of the snap's
    captured generation."""
    import jax.numpy as jnp
    pad_row = np.int32(2**31 - 1)
    state = jnp.copy(snap["state"])
    if snap["cols"] is not None:
        state = bhll.apply_batch(state, *snap["cols"])
    coo = snap["sparse"]["coo"]
    if not coo:
        return np.asarray(state)
    rows, idx, rho = (np.concatenate([c[i] for c in coo]) for i in range(3))
    slots = snap["sparse"]["slot_of"][rows]
    hot = slots >= 0
    slots, idx, rho = slots[hot], idx[hot], rho[hot]
    for i in range(0, slots.shape[0], batch_cap):
        pad = batch_cap - slots[i:i + batch_cap].shape[0]
        state = bhll.apply_batch(
            state, np.r_[slots[i:i + batch_cap], np.full(pad, pad_row)],
            np.r_[idx[i:i + batch_cap], np.zeros(pad, np.int32)],
            np.r_[rho[i:i + batch_cap], np.zeros(pad, np.int32)])
    return np.asarray(state)


@pytest.mark.parametrize("side", ["xla", "pallas"])
@pytest.mark.parametrize("case", FOLD_CASES)
def test_backlog_fold_matches_reference_and_the_chunked_fold(
        case, side, monkeypatch):
    import functools

    import jax

    from veneur_tpu.core.columnstore import SetTable
    from veneur_tpu.ops import hll_ref
    from veneur_tpu.samplers.parser import Parser

    kernel_calls = []
    if side == "pallas":
        interpreted = jax.jit(functools.partial(bhll._fold_sorted,
                                                interpret=True),
                              donate_argnums=0)

        def fold(*args):
            kernel_calls.append(args[1].shape[0])
            return interpreted(*args)
        monkeypatch.setattr(bhll, "fold_backlog", fold)
    rng = np.random.default_rng(2_147_484_400 + FOLD_CASES.index(case))
    kwargs, phases, lowered, dispatches = _fold_case(case, rng)
    table = SetTable(batch_cap=64, sparse=True, **kwargs)
    keys = 1 + max(k for phase in phases for k, _i, _r in phase)
    rows = []
    for k in range(keys):
        out = []
        Parser().parse_metric_fast(b"fold.%d:x|s" % k, out.append)
        with table.lock:
            rows.append(table.row_for(out[0]))
    oracle = np.zeros((keys, bhll.M), np.int8)
    for n, phase in enumerate(phases):
        if n == 1 and lowered is not None:
            table._promote_samples = lowered
        triples = np.asarray(phase, np.int32).reshape(-1, 3)
        np.maximum.at(oracle, (triples[:, 0], triples[:, 1]),
                      triples[:, 2].astype(np.int8))
        cols = (np.asarray(rows, np.int32)[triples[:, 0]], triples[:, 1],
                triples[:, 2])
        for at in range(0, triples.shape[0], 100):   # the pump's chunks
            table.add_batch(*(c[at:at + 100].copy() for c in cols))
    snap = table.swap_out()
    want_bank = _chunked_fold(snap, table.batch_cap)
    coo = snap["sparse"]["coo"]
    backlog = int(np.count_nonzero(snap["sparse"]["slot_of"][
        np.concatenate([c[0] for c in coo])] >= 0)) if coo else 0
    pending = int(np.count_nonzero(snap["cols"][0] != np.int32(2**31 - 1))) \
        if snap["cols"] is not None else 0
    snap = table.collect(table.readout(snap, collect=False))
    est, regs = snap["estimates"], snap["registers"]
    for k, row in enumerate(rows):
        np.testing.assert_array_equal(regs[row], oracle[k], err_msg=str(k))
        assert float(est[row]) == hll_ref.HLL(oracle[k]).estimate(), k
    if case == "duplicates":
        assert all(oracle[k][77 + k] >= 9 for k in range(6))
    np.testing.assert_array_equal(np.asarray(regs._dev), want_bank)
    assert table.fold_dispatches_total == dispatches + (pending > 0)
    assert table.fold_entries_total == backlog + pending
    if side == "pallas":
        assert len(kernel_calls) == dispatches
        # every dispatch of a rung has its one length
        assert set(kernel_calls) <= {bhll.fold_length(
            table._dev_cap, table.PROMOTE_SAMPLES)}

"""The server's main-path device programs compile for a TPU v5e.

No chip is attached here: the TPU compiler is installed and compiles for
a chip that is described, not attached (guide on-chip-measurement §2,
step 3). Each case lowers one jitted program of the ingest / flush /
import path (the first two taken from the server's own warm-up list,
`warm_programs`) at the widths a deployment runs (C = 128 and 2C = 256
centroid columns, 16,384 HLL registers, BINS_PAD llhist bins) and 8,192
rows — enough rows to tile, few enough that a case takes seconds — and
asks the v5e compiler for an executable. What it refuses here it would
refuse on the chip, at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and under pytest-xdist
every worker imports every test file. Keep all such tests in this file.
A compile that passes is not a chip run.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from veneur_tpu.core import columnstore
from veneur_tpu.ops import batch_hll, batch_llhist, batch_tdigest, scalars

K = 8192          # table rows per case
B = 16384         # COO batch width (tpu.batch_cap of examples/example.yaml)
R = 512           # imported rows per merge batch
PS = (0.5, 0.9, 0.99)
C = batch_tdigest.C


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # the compiler logs under /tmp otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip is written to the persistent
    # cache but cannot be read back without the chip: cache off here
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # a trace another test file left in this worker (same shapes, CPU
    # side of batch_tdigest's backend branch) must not be reused here,
    # nor ours by the files that follow
    jax.clear_caches()
    yield desc
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_segment_reduce(monkeypatch):
    """batch_tdigest picks its segment-reduce at trace time from
    jax.default_backend(), which is the CPU here: steer the probe so the
    TPU side (_segment_reduce_matmul) is what compiles."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(tree, sharding):
    """Pytree of arrays/ShapeDtypeStructs -> the same shapes, placed on
    the described chip (no device holds an array here)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _vec(n, dtype):
    return jax.ShapeDtypeStruct((n,), dtype)


def _mat(n, m, dtype):
    return jax.ShapeDtypeStruct((n, m), dtype)


def _table(init):
    """Shapes of one family's K-row device table."""
    return jax.eval_shape(lambda: init(K))


f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8

# -- the warm-up's list -----------------------------------------------------
#
# What `Server._warmup` compiles at start-up for a one-device store
# (`warm_programs`, core/columnstore.py): the cases ARE that list, so a
# program that joins it is compiled for the chip here too, and nothing
# the server warms goes uncompiled. Both readouts of the digest family:
# a global's (`need_export` false) and a forwarding local's.

# a small table of each family, only for its list and its shapes: K
# rows of state from `_fresh_state_at`, a B-wide padding batch from
# `_prewarm_cols`
ONE_DEVICE = {family: cls(capacity=64, batch_cap=B)
              for family, cls in (("counter", columnstore.CounterTable),
                                  ("gauge", columnstore.GaugeTable),
                                  ("histogram", columnstore.HistoTable),
                                  ("llhist", columnstore.LLHistTable),
                                  ("set", columnstore.SetTable))}
WARM_CASES = [
    pytest.param(family, False, wp.program, id=f"{family}.{wp.program}")
    for family, table in ONE_DEVICE.items()
    for wp in table.warm_programs(PS, False)
] + [pytest.param("histogram", True, "readout",
                  id="histogram.readout.export")]

_SEGMENT_REDUCERS = (batch_tdigest.compact,
                     batch_tdigest.flush_export_packed,
                     batch_tdigest.merge_centroid_rows)


def _compile_for_v5e(program, args, static):
    lowered = program.lower(*args, *static)
    if program in _SEGMENT_REDUCERS:
        # the TPU side of the trace-time branch is a one-hot matmul
        assert "dot_general" in lowered.as_text()
    compiled = lowered.compile()
    # an executable for the described TPU, not for the CPU this runs on
    assert "tpu" in compiled.as_text().lower()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0


@pytest.mark.parametrize("family,need_export,program", WARM_CASES)
def test_warm_up_program_compiles_for_v5e(
        one_chip, tpu_segment_reduce, family, need_export, program):
    table = ONE_DEVICE[family]
    state = jax.eval_shape(lambda: table._fresh_state_at(K))
    [wp] = [wp for wp in table.warm_programs(PS, need_export)
            if wp.program == program]
    args = _shapes(wp.args(state, table._prewarm_cols()), one_chip)
    _compile_for_v5e(wp.fn, args, wp.static)


# -- the import path, which no capacity implies -----------------------------
#
# (id, jitted program, its table's init, the other arguments);
# batch_llhist.merge takes a second table, marked by its init
IMPORT_CASES = [
    ("scalars.merge_gauges", scalars.merge_gauges,
     scalars.init_gauges, (_vec(R, i32), _vec(R, f32))),
    ("batch_tdigest.merge_centroid_rows", batch_tdigest.merge_centroid_rows,
     batch_tdigest.init_state,
     (_vec(R, i32), _mat(R, C, f32), _mat(R, C, f32),
      _vec(R, f32), _vec(R, f32), _vec(R, f32))),
    ("batch_hll.merge_rows", batch_hll.merge_rows,
     batch_hll.init_state, (_vec(R, i32), _mat(R, batch_hll.M, i8))),
    ("batch_llhist.merge", batch_llhist.merge,
     batch_llhist.init_state, (batch_llhist.init_state,)),
]


@pytest.mark.parametrize("program,init,others",
                         [pytest.param(*c[1:], id=c[0])
                          for c in IMPORT_CASES])
def test_import_path_program_compiles_for_v5e(
        one_chip, tpu_segment_reduce, program, init, others):
    others = tuple(_table(o) if callable(o) else o for o in others)
    _compile_for_v5e(program, _shapes((_table(init),) + others, one_chip),
                     ())


# -- the set bank at its largest rung ----------------------------------------
#
# `sets100k` (benchmark/configs) holds 100,000 keys in a 131,072 x 16,384
# int8 bank (2 GiB); a flush holds two such generations. Its fold's
# scatter and its estimate must neither stop compiling nor keep a whole
# bank as a temporary (at 16,384 rows the scatter keeps one, D17).

@pytest.mark.parametrize("program,others", [
    (batch_hll.apply_batch, (_vec(B, i32),) * 3),
    (batch_hll.estimate, ())], ids=["apply_batch", "estimate"])
def test_set_bank_programs_fit_at_131072_rows(one_chip, program, others):
    bank = _mat(131072, batch_hll.M, i8)
    compiled = program.lower(*_shapes((bank,) + others, one_chip)).compile()
    assert "tpu" in compiled.as_text().lower()
    assert compiled.memory_analysis().temp_size_in_bytes < 131072 * 16384 // 8


# The flush's backlog fold (`fold_backlog`: the sort and the Pallas kernel
# over row blocks) at the top rung of `sets100k` and at `global100k`'s
# 16,384 slots: it must update the bank in place at both (the scatter
# keeps a whole 268 MB bank at 16,384 rows, D17; the fold must not).

@pytest.mark.parametrize("rows", [131072, 16384])
def test_backlog_fold_updates_the_bank_in_place(one_chip, tpu_segment_reduce,
                                                rows):
    n = batch_hll.fold_length(rows, 16)
    assert n == rows * 15 + 1024
    args = _shapes((_mat(rows, batch_hll.M, i8), _vec(n, i32), _vec(n, i32)),
                   one_chip)
    compiled = batch_hll.fold_backlog.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu" in text.lower() and "tpu_custom_call" in text
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == rows * batch_hll.M
    assert ma.temp_size_in_bytes < rows * batch_hll.M // 8


# `compact` at the 131,072 rows of `timers100k` (and `timers100k-zipf`,
# where it runs about once a batch): the table's four (K, C) grids are
# donated and updated in place where the row mask says, and the one
# output besides them is the scalar `done` handle that its completion
# stamp holds (core/deviceobs.py) while the next apply consumes the state.

def test_compact_updates_the_table_in_place_beside_its_stamp_handle(
        one_chip, tpu_segment_reduce):
    rows = 131072
    state = _shapes(jax.eval_shape(lambda: batch_tdigest.init_state(rows)),
                    one_chip)
    lowered = batch_tdigest.compact.lower(
        state, jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip))
    _state_out, done = lowered.out_info
    assert done.shape == () and done.dtype == f32
    compiled = lowered.compile()
    assert "tpu" in compiled.as_text().lower()
    ma = compiled.memory_analysis()
    grids = 4 * rows * C * 4
    assert ma.alias_size_in_bytes >= grids
    assert ma.output_size_in_bytes - ma.alias_size_in_bytes <= 4096


# -- the four-shard deployment's two heaviest collectives -------------------
#
# `global100k-shards4` (benchmark/configs) merges, every flush, four
# per-device t-digest grids of 32,768 rows and four HLL banks of 16,384 x
# 16,384 int8 over the shard axis. Both at the cell's own shapes, for the
# four described chips, so that neither can stop compiling (or stop
# fitting a chip's 16 GB) unseen. No more than these two (D18).

@pytest.mark.parametrize("name,init,rows", [
    ("merge_histo_stacked", batch_tdigest.init_state, 32768),
    ("merge_hll_stacked", batch_hll.init_state, 16384)])
def test_collective_merge_compiles_for_four_v5e_chips(
        topo, tpu_segment_reduce, name, init, rows):
    from veneur_tpu.parallel import collectives

    n = len(topo.devices)
    assert n == 4
    stacked_on = collectives.shard_sharding(
        collectives.local_mesh(topo.devices))
    stacked = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype,
                                       sharding=stacked_on),
        jax.eval_shape(lambda: init(rows)))
    compiled = getattr(collectives, name).lower(stacked).compile()
    text = compiled.as_text()
    assert "tpu" in text.lower()
    # a reduction over the shard axis: the chips exchange state
    assert any(op in text for op in ("all-reduce", "all-gather",
                                     "all-to-all", "collective-permute"))
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert 0 < per_chip < 16 * 2**30, per_chip

"""The tiled partition, compiled for a described TPU v5e (no chip
attached, nothing runs): what only the TPU's compiler decides and the
chip then pays for (PERF.md §6). Its tiles go through the
Pallas lane compaction, so no row scatter of the row's width is left
(the compiler sorts a scatter's indices and wants its operand
words-minor); and layout assignment would hand a words-minor
layout (512 B for a 44-byte row) to every window-sized buffer, so none
may have it: the kernel reads the rows-minor tile as it lies. And a buffer
that crosses a `conditional` is copied on its way in and out, so the
packed table may cross none inside the split loop (PR 30). And the
histogram's one-hot is factored, so no plane of F x 256 elements a row
is written out, and the gradients' bf16 remainder survives XLA's default
flags (PR 32). And the ranking objective's pair planes stay under the
slice bound, at `msltr`'s widths too (PR 34). Only this file loads the
TPU's library, inside the fixture."""
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.mark.parametrize("rows", [1 << 19,      # whole tiles
                                  300_000])     # a ragged last tile
@pytest.mark.parametrize("d_cols", [11, 21, 39])  # `higgs`, `criteo`, `msltr`
def test_tiled_partition_compiles_to_tile_sized_scatters(one_chip, rows,
                                                         d_cols):
    """Its tiles go through the Mosaic kernel, and no row scatter of the
    row's width is left (with the scan: tile-sized scatters, none wider
    than SCATTER_TILE_ROWS); every window-sized buffer stays rows-minor."""
    from lightgbm_tpu.models import device_learner as dl
    from lightgbm_tpu.ops.pallas import partition_kernel
    assert rows > dl.SCATTER_TILE_ROWS
    assert partition_kernel.engaged("tpu", dl.SCATTER_TILE_ROWS, d_cols)
    txt = jax.jit(lambda w, k: dl.partition_window(w, k)).lower(
        jax.ShapeDtypeStruct((rows, d_cols), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    scatters = re.findall(r"= u32\[\d+,%d\]\{[^}]*\} scatter\(" % d_cols,
                          txt)
    assert not scatters, scatters
    assert 'custom_call_target="tpu_custom_call"' in txt
    # {0,1...}: rows minor, the packed table's layout; {1,0...}: words minor
    window = set(re.findall(r"u32\[%d,%d\](\{[01],[01])" % (rows, d_cols),
                            txt))
    assert window == {"{0,1"}, window


def test_data_parallel_tree_partitions_through_the_kernel(v5e_2x2):
    """The data-parallel learner's whole-tree program (the compact core
    on each shard's rows under `shard_map`), compiled for the four chips
    of the described v5e 2x2, 100,000 rows a shard (one rung over a
    scatter tile) at `higgs`'s 11-word rows: each shard's partition holds
    the Mosaic kernel, and no row scatter of the table's width is left.
    The learner is built on this process's devices for its statics and
    per-feature arrays; the rows are shapes alone."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models import device_learner as dl
    from lightgbm_tpu.parallel.learners import DeviceDataParallelTreeLearner
    features, local = 28, 100_000
    r = np.random.RandomState(features)
    cfg = Config({"objective": "binary", "num_leaves": 15, "max_bin": 255,
                  "min_data_in_bin": 1, "verbosity": -1})
    lrn = DeviceDataParallelTreeLearner(
        cfg, Dataset(r.randn(4000, features).astype(np.float32), config=cfg,
                     label=(r.rand(4000) > 0.5).astype(np.float64)),
        mesh=Mesh(np.array(jax.devices()[:4]), ("data",)))
    assert lrn.strategy == "compact"
    # the packed table's row: the codes, the gradient words, the row id
    d_cols = lrn.table_row_bytes // 4
    assert d_cols == 11
    assert dl._size_classes(local)[-1] > dl.SCATTER_TILE_ROWS
    lrn.mesh = Mesh(np.array(v5e_2x2.devices), ("data",))
    lrn.local_n = local
    fn = lrn._sharded_tree_fn(with_bag_key=False)

    def shaped(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(lrn.mesh, spec))

    rows = 4 * local
    txt = jax.jit(fn).lower(
        shaped((rows,) + lrn.codes_pack.shape[1:], lrn.codes_pack.dtype,
               P("data", None)),
        shaped((rows,) + lrn.codes_row.shape[1:], lrn.codes_row.dtype,
               P("data", None)),
        shaped((rows,), jnp.float32, P("data")),
        shaped((rows,), jnp.float32, P("data")),
        shaped((rows,), jnp.float32, P("data")),
        shaped((features,), jnp.bool_, P()),
        shaped((2,), jnp.uint32, P())).compile().as_text()
    scatters = re.findall(r"= u32\[\d+,%d\]\{[^}]*\} scatter\(" % d_cols,
                          txt)
    assert not scatters, scatters
    assert 'custom_call_target="tpu_custom_call"' in txt
    # the shard's own packed table: the module is per shard
    assert re.search(r"u32\[%d,%d\]" % (2 * local, d_cols), txt)


@pytest.mark.parametrize("features, d_cols", [(28, 11),     # `higgs`
                                              (67, 21),     # `criteo-share`
                                              (137, 39)])   # `msltr`
def test_split_scan_and_wide_rows_compile(one_chip, features, d_cols):
    """The split scan over a cell's (features, 256 bins) plane, with both
    children summed from the bins (PR 29), and one tiled partition of the
    cell's packed row width: what the chip's compiler refuses here costs
    no chip time."""
    from lightgbm_tpu.models import device_learner as dl
    from lightgbm_tpu.ops import split as split_ops

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scalar, per_f = shaped((), jnp.float32), shaped((features,), jnp.int32)
    txt = split_ops.find_best_split.lower(
        shaped((features, 256, 3), jnp.float32), scalar, scalar, scalar,
        per_f, per_f, per_f, shaped((features,), jnp.bool_), per_f,
        scalar, scalar, num_bins=256, l1=0.0, l2=0.0, max_delta_step=0.0,
        min_data_in_leaf=20, min_sum_hessian=1e-3,
        min_gain_to_split=0.0).compile().as_text()
    assert "f32[%d,256,3]" % features in txt
    rows = 2 * dl.SCATTER_TILE_ROWS
    jax.jit(lambda w, k: dl.partition_window(w, k)).lower(
        shaped((rows, d_cols), jnp.uint32),
        shaped((rows,), jnp.int32)).compile()


def _histogram_module(one_chip, features, rows):
    """`build_histogram` at 256 bins under the root's scope, compiled
    for the described chip at the flags this process has (XLA's
    defaults: the suite sets none that touch precision)."""
    from lightgbm_tpu.ops import histogram as hist_ops

    def root_hist(codes, gh):
        with jax.named_scope("lgbm.root_hist"):
            return hist_ops.build_histogram(codes, gh, 256)

    return jax.jit(root_hist).lower(
        jax.ShapeDtypeStruct((rows, features), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((rows, 3), jnp.float32, sharding=one_chip)
    ).compile().as_text()


@pytest.mark.parametrize("features", [28,       # `higgs`
                                      67,       # `criteo-share`
                                      137])     # `msltr`
def test_histogram_writes_no_plane_of_256_bins_a_feature(one_chip, features):
    """At a cell's width: no array of chunk x F x 256 elements anywhere
    in the module (the unfactored one-hot, `pred[2048,F,256]` before
    PR 32), and the widest array a product reads is under F x 128
    elements a row (F x 256 before; F x 64, rounded up to whole feature
    groups, with LO_BINS = 64: the `lo` codes broadcast over their 64
    columns)."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.ops import histogram as hist_ops
    chunk = hist_ops.resolve_chunk_size(0, features, 256)
    txt = _histogram_module(one_chip, features, 4 * chunk)
    sizes = {int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"\w+\[(\d+(?:,\d+)+)\]", txt)}
    assert chunk * features * 256 not in sizes
    assert max(sizes) < chunk * features * 128
    per_row = telemetry.hist_plane_elems_per_row(txt)
    assert 0 < per_row < features * 128, per_row


def test_gradient_remainder_survives_default_flags(one_chip):
    """The six-column operand is [head | gh - head] with the head a
    `reduce-precision`, which `--xla_allow_excess_precision=true` (the
    default) may not fold away: written as float32(bfloat16(gh)) the
    head was gh itself and the remainder a constant 0 (PERF.md §7,
    fault 1)."""
    txt = _histogram_module(one_chip, 28, 4096)
    heads = re.findall(r"%([\w.\-]+) = f32\[[\d,]+\]\S* reduce-precision\("
                       r"[^)]*\), exponent_bits=8, mantissa_bits=7", txt)
    assert heads
    assert any(re.search(r" subtract\(%[\w.\-]+, %" + re.escape(h) + r"\)", txt)
               for h in heads), "no gh - head in the module"


def test_ranking_gradient_pass_stores_no_pair_plane_over_its_bound(one_chip):
    """`LambdarankNDCG`'s gradient pass for the described chip, on 3,000
    queries of 1 to 120 documents and one of 1,251 (the 2,048 bucket,
    `msltr`'s longest), every buffer an argument as in the fused step:
    it compiles, and the widest pair plane the compiler stores stays
    under PAIR_SLICE_ELEMS (on the chip it builds the planes inside its
    fusions: what is stored is the L x L tie-break triangle)."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.models.device_learner import swapped_attrs
    from lightgbm_tpu.objectives import objective as objective_mod
    r = np.random.default_rng(7)
    counts = np.concatenate((
        [1251, 1], np.rint(np.exp(r.normal(3.0, 0.8, 3000))).clip(1, 120)
    )).astype(np.int64)
    n = int(counts.sum())

    class Meta:
        label = r.integers(0, 5, n).astype(np.float64)
        weight = None
        query_boundaries = np.concatenate(([0], np.cumsum(counts)))

    obj = objective_mod.create_objective(
        "lambdarank", Config({"objective": "lambdarank", "verbosity": -1}))
    obj.init(Meta, n)
    assert obj.max_bucket_len == 2048
    keys = obj.device_buffer_names()

    def gradients(bufs, score):
        with swapped_attrs(obj, keys, bufs), \
                jax.named_scope("lgbm.gradients"):
            return obj._gradients_impl(score)

    def shaped(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    txt = jax.jit(gradients).lower(
        tuple(shaped(getattr(obj, k)) for k in keys),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    ).compile().as_text()
    assert "rank_bucket_2048" in txt and "rank_bucket_8" in txt
    plane = telemetry.rank_pair_plane_elems(txt)
    assert plane <= objective_mod.PAIR_SLICE_ELEMS, plane
    # by stage: every bucket's instructions fall under `gradients`
    stages = {stage for stage, _ in telemetry.stage_map(txt).values()}
    assert stages == {"gradients"}


COMPILE_LIMIT_S = 240


def _loop_conditions(txt, stage):
    """The ROOT line of the condition of every `while` under a
    `lgbm.<stage>` scope of a module's text."""
    from lightgbm_tpu import telemetry
    comps = telemetry._computations(txt)
    return [next(c for c in comps[re.search(r"condition=%?([\w.\-]+)",
                                            line).group(1)] if "ROOT" in c)
            for lines in comps.values() for line in lines
            if " while(" in line
            and re.search(r'op_name="[^"]*lgbm\.%s[^"]*"' % stage, line)]


@pytest.mark.parametrize("features, d_cols", [(28, 11),     # `higgs`
                                              (67, 21),     # `criteo-share`
                                              (137, 39)])   # `msltr`
def test_packed_table_is_updated_in_place_in_the_split_loop(
        one_chip, monkeypatch, features, d_cols):
    """The compact core's tree program at a cell's row width, 300,000
    rows (eight rungs of the window ladder, three over one scatter
    tile), 15 leaves: no copy of the packed table `u32[2N, d_cols]` in
    any computation the split loop reaches, and the table rows-minor
    wherever it appears. With the table handed through a `conditional`
    (the `lax.switch` over the rungs, before PR 30) the same count read
    6, one copy in each of six of the eight branches. And a rung works
    for the leaf's rows (PR 35): the tile loop and the chunk loop end
    where a carried count says, not at a constant of the rung's width,
    the chunks are decoded one by one (no `s32[half window, F]`, nor the
    chunks stacked, under `lgbm.child_hist`) and the products still read
    a plane under F x 128 elements a row."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.ops import histogram as hist_ops
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models import device_learner as dl
    rows = 300_000
    # a toy learner of the cell's columns, for the per-feature arrays
    # and the statics; the rows are shapes alone
    r = np.random.RandomState(features)
    cfg = Config({"objective": "binary", "num_leaves": 15, "max_bin": 255,
                  "min_data_in_bin": 1, "verbosity": -1})
    lrn = dl.DeviceTreeLearner(
        cfg, Dataset(r.randn(4000, features).astype(np.float32), config=cfg,
                     label=(r.rand(4000) > 0.5).astype(np.float64)),
        strategy="compact")
    grow, kwargs = lrn._grow_fn_kwargs(trivial_weights=True)
    assert grow is dl.grow_tree_compact
    ladder = dl._size_classes(rows)
    assert len(ladder) >= 3 and ladder[-1] > dl.SCATTER_TILE_ROWS

    def shaped(a, lead=None):
        shape = a.shape if lead is None else (lead,) + a.shape[1:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)

    per_row = jnp.zeros((1,), jnp.float32)
    meta = (lrn.f_numbins, lrn.f_missing, lrn.f_default, lrn.f_monotone,
            lrn.f_penalty, lrn.f_categorical, lrn.f_col, lrn.f_base,
            lrn.f_elide, lrn.scan_plan)
    tick = time.perf_counter()
    txt = grow.lower(
        shaped(lrn.codes_pack, rows), shaped(lrn.codes_row, rows),
        shaped(per_row, rows), shaped(per_row, rows), shaped(per_row, rows),
        shaped(jnp.ones(features, bool)), *[jax.tree.map(shaped, m) for m in meta],
        shaped(jax.random.PRNGKey(0)), **kwargs,
        **lrn._statics()).compile().as_text()
    assert time.perf_counter() - tick < COMPILE_LIMIT_S
    table = r"u32\[%d,%d\]" % (2 * rows, d_cols)
    assert any(re.search(table, line.split(" while(")[0])
               for line in txt.splitlines() if " while(" in line), \
        "the packed table is no loop's carry: the count below says nothing"
    copies = telemetry.table_copies_in_split_loop(txt)
    assert sum(copies.values()) == 0, copies
    assert set(re.findall(table + r"(\{[01],[01])", txt)) == {"{0,1"}
    assert 0 < telemetry.hist_plane_elems_per_row(txt) < features * 128
    # one tile loop (and the top rung's ragged step) a tiled rung, one
    # chunk loop a rung whose half window is over a chunk
    chunk = hist_ops.resolve_chunk_size(0, features, 256)
    tiled = sum(w > dl.SCATTER_TILE_ROWS for w in ladder)
    chunked = sum((w + 1) // 2 > chunk for w in ladder)
    for stage, loops in (("partition", tiled + 1), ("child_hist", chunked)):
        roots = _loop_conditions(txt, stage)
        assert len(roots) == loops, (stage, roots)
        for root in roots:
            compared = re.search(r" compare\(([^)]*)\)", root)
            assert " get-tuple-element(" in root or (
                compared and "constant" not in compared.group(1)), root
    decoded = [
        (dims, line) for line in txt.splitlines() if "lgbm.child_hist" in line
        for dims in re.findall(r"= s32\[(\d+(?:,\d+)*),%d\]" % features,
                               line)
        if np.prod([int(d) for d in dims.split(",")]) > chunk]
    assert not decoded, decoded[:3]

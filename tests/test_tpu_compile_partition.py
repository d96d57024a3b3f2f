"""The tiled scan partition, compiled for a described TPU v5e (no chip
attached, nothing runs): what only the TPU's compiler decides and the
chip then pays for (PERF.md §6, PR 27). Past 2**18 indices it takes the
slow row scatter, so no scatter may be wider than one tile; and layout
assignment would hand the tile's words-minor layout (512 B for a 44-byte
row) to every window-sized buffer, so none may have it. Only this file
loads the TPU's library, inside the fixture."""
import os
import re

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows", [1 << 19,      # whole tiles
                                  300_000])     # a ragged last tile
def test_tiled_partition_compiles_to_tile_sized_scatters(one_chip, rows):
    from lightgbm_tpu.models import device_learner as dl
    d_cols = 11
    assert rows > dl.SCATTER_TILE_ROWS
    txt = jax.jit(lambda w, k: dl.partition_window(w, k, "scan")).lower(
        jax.ShapeDtypeStruct((rows, d_cols), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    scatters = [int(m) for m in re.findall(
        r"= u32\[(\d+),%d\]\{[^}]*\} scatter\(" % d_cols, txt)]
    assert scatters and max(scatters) <= dl.SCATTER_TILE_ROWS, scatters
    # {0,1...}: rows minor, the packed table's layout; {1,0...}: words minor
    window = set(re.findall(r"u32\[%d,%d\](\{[01],[01])" % (rows, d_cols),
                            txt))
    assert window == {"{0,1"}, window


@pytest.mark.parametrize("features, d_cols", [(28, 11),     # `higgs`
                                              (67, 21)])    # `criteo-share`
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
    jax.jit(lambda w, k: dl.partition_window(w, k, "scan")).lower(
        shaped((rows, d_cols), jnp.uint32),
        shaped((rows,), jnp.int32)).compile()

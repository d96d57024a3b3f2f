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

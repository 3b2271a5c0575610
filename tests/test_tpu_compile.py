"""The sparse counter drains as the TPU's own compiler builds them.

The chip's compiler is installed where the tests run and compiles for a
chip that is described, not attached (nothing runs, nothing is timed). That
is enough to hold the property PR 29 bought: a sparse drain touches its
rows, not its plane. The eight whole-plane copies it removed were that
compiler's layout changes (a [K,64] u32 plane is kept column-major, and the
row gather made it transpose the whole plane), invisible on the CPU backend,
so only this compile or a chip run can see them come back. All such compiles
live in this one file, behind a fixture: only the worker that runs the file
loads the TPU's library.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import jylis_tpu  # noqa: F401
from jylis_tpu.models.repo_counters import _drain_g, _drain_pn
from jylis_tpu.parallel import drain_sharded_g, drain_sharded_pn

K, R = 1 << 20, 64  # the north star: BASELINE.json, `pncount-1m-r64`


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _plane_traffic(compiled, rows: int):
    """(temporary bytes, ops that copy or transpose an array of `rows` rows)."""
    text = compiled.as_text()
    moved = re.findall(r"= \w+\[%d,\d+\]\S* (?:copy|transpose)\(" % rows, text)
    return compiled.memory_analysis().temp_size_in_bytes, moved


@pytest.mark.parametrize("rows", [16, 1024, 16384])
@pytest.mark.parametrize("kind", ["g", "pn"])
def test_sparse_drain_touches_rows_not_the_plane(topo, kind, rows):
    one = SingleDeviceSharding(topo.devices[0])
    drain, w = (_drain_pn, 4 * R) if kind == "pn" else (_drain_g, 2 * R)
    compiled = drain.lower(
        jax.ShapeDtypeStruct((K, w), jnp.uint32, sharding=one),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((rows, w), jnp.uint32, sharding=one),
    ).compile()
    temp, moved = _plane_traffic(compiled, K)
    assert moved == []
    assert temp < 4 * K * w  # under one plane: in fact 0 at these sizes
    assert compiled.memory_analysis().alias_size_in_bytes == 4 * K * w  # donated


@pytest.mark.parametrize("kind", ["g", "pn"])
def test_mesh_drain_is_the_same_local_program(topo, kind):
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("rep", "keys"))
    drain, w = (drain_sharded_pn, 4 * R) if kind == "pn" else (drain_sharded_g, 2 * R)

    def shaped(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    compiled = drain.lower(
        mesh,
        shaped((K, w), jnp.uint32, P("keys", None)),
        shaped((4 * 1024,), jnp.int32, P("keys")),
        shaped((4 * 1024, w), jnp.uint32, P("keys", None)),
    ).compile()
    temp, moved = _plane_traffic(compiled, K // 4)
    assert moved == [] and temp < K * w  # under one device's block
    assert not re.search(r"all-reduce|all-gather|all-to-all|collective-permute",
                         compiled.as_text())


@pytest.mark.parametrize("keys,depth", [(4, 64), (64, 32), (1024, 4)])
def test_the_resident_ujson_fold_compiles_at_the_shapes_the_boot_pins(topo, keys, depth):
    """`ycsb-ujson-1kx1k-r3`'s store (`UJSON 1024x2048`, `ResidentStore.
    pin_shapes`): the subset fold at each of the three programs a pinned
    store runs (few keys deep, every row shallow), 8 slots a delta, as the
    chip's compiler builds it — it fits the chip by a wide margin (the
    output is new planes: the fold is not donated)."""
    from jylis_tpu.ops import ujson_device as dev
    from jylis_tpu.ops.ujson_resident import ResidentStore, fold_join_subset

    one = SingleDeviceSharding(topo.devices[0])
    rows, slots, cloud, reps = 1024, 2048, ResidentStore.CLOUD_MIN, 8
    w = ResidentStore.MENU_W
    assert (keys, depth) in ResidentStore.MENU_PASSES + ((rows, ResidentStore.MENU_ALL_D),)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    resident = dev.DocBatch(s((rows, slots), jnp.int32), s((rows, slots), jnp.int32),
                            s((rows, reps), jnp.uint32), s((rows, cloud), jnp.int32))
    grid = dev.DocBatch(s((keys, depth, w), jnp.int32), s((keys, depth, w), jnp.int32),
                        s((keys, depth, reps), jnp.uint32), s((keys, depth, w), jnp.int32))
    compiled = fold_join_subset.lower(
        resident, grid, s((keys,), jnp.int32), shift=dev.narrow_shift(reps),
        out_w=slots, out_c=cloud).compile()
    mem = compiled.memory_analysis()
    planes = 4 * (2 * rows * slots + rows * reps + rows * cloud)
    assert mem.output_size_in_bytes >= planes
    assert mem.temp_size_in_bytes < 1 << 30  # 16 GB of HBM: no pressure


def test_the_resident_ujson_read_gathers_one_row_in_one_program(topo):
    """A read of a resident document that has no decoded view (never
    decoded, evicted, or dropped by its key's last fold) is one gather of
    its row from the four planes, `ResidentStore.read`'s one program, at
    `ycsb-ujson-1kx1k-r3`'s shapes."""
    from jylis_tpu.ops import ujson_device as dev
    from jylis_tpu.ops.ujson_resident import ResidentStore, gather_rows

    one = SingleDeviceSharding(topo.devices[0])
    rows, slots, cloud, reps = 1024, 2048, ResidentStore.CLOUD_MIN, 8

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    resident = dev.DocBatch(s((rows, slots), jnp.int32), s((rows, slots), jnp.int32),
                            s((rows, reps), jnp.uint32), s((rows, cloud), jnp.int32))
    compiled = gather_rows.lower(resident, s((1,), jnp.int32)).compile()
    out = compiled.memory_analysis().output_size_in_bytes
    assert 4 * (2 * slots + reps + cloud) <= out < 1 << 16  # one row, tiled

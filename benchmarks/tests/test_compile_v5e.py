"""The fused cycle at each cell's shapes, compiled here for a described
TPU v5e (no chip attached; on-chip-measurement guide, section 2).  A
compile that passes is not a chip run."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest

#: (cell, P, T, H, U): the buckets each cell's cycles land in
SHAPES = [("1pool", 1, 131072, 8192, 256), ("8pool", 8, 65536, 2048, 256)]


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return topo.devices[0], SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell,P,T,H,U", SHAPES)
def test_fused_cycle_compiles_for_v5e(one_chip, cell, P, T, H, U):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from cook_tpu.parallel.mesh import POOL_AXIS
    from cook_tpu.parallel.sharded import (CompactPoolCycleInputs,
                                           make_pool_cycle)
    device, sharding = one_chip
    mesh = Mesh(np.array([device]), (POOL_AXIS,))
    f32, i32, E = jnp.float32, jnp.int32, 8

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    inp = CompactPoolCycleInputs(
        rows=spec((P, T), i32), flags=spec((P, T), jnp.uint8),
        res_base=spec((max(T, 1024), 4), f32),
        disk_base=spec((max(T, 1024),), f32),
        tokens_u=spec((P, U), f32), shares_u=spec((P, U, 3), f32),
        quota_u=spec((P, U, 4), f32), num_considerable=spec((P,), i32),
        pool_quota=spec((P, 4), f32), group_quota=spec((P, 4), f32),
        group_id=spec((P,), i32), host_gpu=spec((P, H), jnp.bool_),
        host_blocked=spec((P, H), jnp.bool_), exc_rows=spec((P, E), i32),
        exc_mask=spec((P, E, H), jnp.bool_), avail=spec((P, H, 4), f32),
        capacity=spec((P, H, 4), f32))
    fn = make_pool_cycle(mesh, considerable_cap=1024, structured=True,
                         compact=True)
    compiled = fn.lower(inp).compile()
    assert compiled.memory_analysis() is not None

"""The paged decode kernel through the TPU's own compiler, without a chip.

``libtpu`` is installed, so a v5e can be described and compiled for though
none is attached: Mosaic refuses here what it would refuse on the chip (a
misaligned slice, too much VMEM), which the interpreter never does. Nothing
runs, so this says nothing about results or times. The topology is described
inside a fixture — never at import — and the compiles happen in the test's own
process; all of them live in this one file (one xdist worker loads the library).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from k_llms_tpu.ops.paged_attention import paged_decode_attention_pallas, table_pages

# (query heads, kv heads, window): qwen2-7b's and mistral-7b's attention at
# the benchmark loop's shapes, and a window that binds inside those rows.
GEOMETRIES = {
    "qwen2-7b": (28, 4, None),
    "mistral-7b": (32, 8, 4096),
    "mistral-7b-window-binds": (32, 8, 96),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_paged_decode_kernel_compiles_for_v5e(name, one_chip):
    QH, KVH, window = GEOMETRIES[name]
    B, D, ps, L, pages = 32, 128, 64, 4, 500
    NP, NG = table_pages(512, 256, ps)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = shape((L, pages * ps, KVH, D), jnp.bfloat16)
    rows = shape((B,), jnp.int32)
    column = shape((B, KVH, D), jnp.bfloat16)
    compiled = jax.jit(
        lambda *a: paged_decode_attention_pallas(
            *a, page_size=ps, sm_scale=D ** -0.5, window=window
        )
    ).lower(
        shape((B, QH, D), jnp.bfloat16), pool, pool, shape((), jnp.int32),
        shape((B, NP), jnp.int32), shape((B, NG), jnp.int32), rows,
        column, column, rows, rows,
    ).compile()
    assert "paged_attention_decode" in compiled.as_text()

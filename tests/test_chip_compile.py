"""The flash attention kernels compiled for the chip, without the chip.

The TPU's compiler is installed in the sandbox and compiles for a described
(not attached) v5e: what Mosaic refuses (a slice off the tiling, more VMEM
than a kernel may use) fails here at no chip time, where interpret mode
passes it. Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never at import: only one process
may load the TPU's library, and every xdist worker imports this file. Keep
such tests in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mmlspark_tpu.ops.pallas_kernels import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache and cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("B,Tq,Tk,H,D,causal,dtype", [
    pytest.param(8, 2048, 2048, 16, 128, True, jnp.bfloat16,
                 id="cgpt-cell"),
    pytest.param(8, 2048, 2048, 8, 256, True, jnp.bfloat16,
                 id="kimilinear-cell-latent-padded-to-256"),
    pytest.param(8, 4096, 4096, 4, 128, True, jnp.bfloat16,
                 id="chip-smoke-D"),
    pytest.param(8, 4096, 4096, 4, 128, False, jnp.bfloat16,
                 id="non-causal-resident-1024"),
    pytest.param(8, 4096, 4096, 8, 64, True, jnp.bfloat16, id="head-dim-64"),
    pytest.param(2, 8192, 8192, 2, 256, True, jnp.bfloat16,
                 id="head-dim-256-four-walked-tiles"),
    pytest.param(2, 5000, 5000, 2, 128, True, jnp.bfloat16,
                 id="padded-two-walked-tiles"),
    pytest.param(2, 200, 1000, 4, 128, False, jnp.bfloat16,
                 id="short-queries-padded-keys"),
    pytest.param(2, 1000, 200, 4, 64, True, jnp.bfloat16,
                 id="more-queries-than-keys"),
    pytest.param(8, 20, 20, 4, 32, True, jnp.bfloat16,
                 id="shorter-than-a-tile"),
    pytest.param(2, 4096, 4096, 4, 128, True, jnp.float32, id="float32"),
])
def test_flash_kernels_compile_for_v5e(one_chip, B, Tq, Tk, H, D, causal,
                                       dtype):
    """Forward and both backward kernels lower to Mosaic and fit VMEM at the
    blocks `_default_blocks` derives."""
    q = jax.ShapeDtypeStruct((B, Tq, H, D), dtype, sharding=one_chip)
    k = jax.ShapeDtypeStruct((B, Tk, H, D), dtype, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None, None, None,
                                       False).astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k)
    assert lowered.as_text().count("tpu_custom_call") == 3
    lowered.compile()

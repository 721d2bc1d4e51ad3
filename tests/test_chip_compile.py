"""Compiles of the check kernel for a described TPU v5e, at real widths.

Nothing here runs on a chip: the TPU compiler, which is installed with
jax, compiles for a ``v5e:2x2`` topology that is described, not attached.
That catches what interpret mode cannot (a Mosaic refusal, an unaligned
block, a kernel the compiler drops) on every test run.  The topology is
described inside a module fixture, so only the process that runs these
tests loads the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import relerr as K

# one TinyLlama-1.1B layer (d_model 2048, 32 heads / 4 KV heads of 64,
# d_ff 5632): fused QKV, output projection, the three MLP matrices and
# the two norms — the parameter section of one layer's trace
TINYLLAMA_LAYER = [2048 * 2560, 2048 * 2048, 2048 * 5632, 2048 * 5632,
                   5632 * 2048, 2048, 2048]


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _packed_layout(sizes, block=K.DEFAULT_BLOCK):
    nblocks = sum(max(1, -(-s // block)) for s in sizes)
    return nblocks * block, nblocks


@pytest.mark.parametrize("sizes", [
    pytest.param(TINYLLAMA_LAYER, id="tinyllama_layer_params"),
    # the whole parameter section of the 2-layer cut the chip smoke run
    # checks: 154M elements, more per-block metadata than one launch's SMEM
    pytest.param([32000 * 2048] + 2 * TINYLLAMA_LAYER + [2048],
                 id="tinyllama_2layer_param_section"),
    pytest.param(list(np.random.default_rng(0).integers(
        1, 3 * 2048 * 64, size=200)), id="ragged_200_pairs"),
])
def test_packed_kernel_compiles_for_v5e(one_chip, sizes):
    n_el, nb = _packed_layout(sizes)
    flat = jax.ShapeDtypeStruct((n_el,), jnp.float32, sharding=one_chip)
    meta = jax.ShapeDtypeStruct((nb,), jnp.int32, sharding=one_chip)
    compiled = K.packed_sq_norms.lower(
        flat, flat, meta, meta, n_segments=len(sizes),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= len(sizes) * 2 * 4


def test_single_pair_wrapper_compiles_for_v5e(one_chip):
    # a bf16 pair near the MLP matrix's size whose element count is no
    # multiple of the wrapper's 64k block, so the kernel masks its tail
    x = jax.ShapeDtypeStruct((5632, 2047), jnp.bfloat16, sharding=one_chip)
    compiled = K.sq_norms.lower(x, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_check_kernel_is_named_in_the_program(one_chip):
    # the kernel's name reaches the compiled program, so a device trace
    # names it (``relerr_kernel``) inside ``jit_relerr_packed``
    n_el, nb = _packed_layout([3 * K.DEFAULT_BLOCK + 5])
    flat = jax.ShapeDtypeStruct((n_el,), jnp.float32, sharding=one_chip)
    meta = jax.ShapeDtypeStruct((nb,), jnp.int32, sharding=one_chip)
    lowered = K.packed_sq_norms.lower(flat, flat, meta, meta, n_segments=1,
                                      interpret=False)
    assert "relerr_kernel" in lowered.as_text()

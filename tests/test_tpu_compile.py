"""Compile the main path for a TPU v5e without a chip.

The TPU compiler is installed with jax and compiles for a described
``v5e:2x2`` topology: nothing runs, but Mosaic and XLA refuse here what
they would refuse on the chip (unsupported kernel ops, too much VMEM, a
program larger than HBM). This is the only test file that describes the
chip. The topology is built inside a module-scoped fixture, never at
import time, and the tests skip where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_dedup import paper_config
from repro.core import DedupConfig
from repro.core.batched import make_templated_step
from repro.core.state import init_state
from repro.kernels.common import VMEM_FILTER_BYTES_LIMIT, fused_resident_bytes
from repro.kernels.fused_template import make_fused_step
from repro.launch.hw import CHIP_HBM_BYTES

# the largest filter the fused step's VMEM guard admits, for both families
FUSED_BITS = 1 << 24


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one, so keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(step, cfg, sharding):
    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    state = jax.tree.map(sds, jax.eval_shape(lambda: init_state(cfg)))
    b = cfg.batch_size
    keys = jax.ShapeDtypeStruct((b,), jnp.uint32, sharding=sharding)
    valid = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=sharding)
    return jax.jit(step, donate_argnums=0).lower(state, keys,
                                                 valid).compile()


@pytest.mark.parametrize("variant,accumulate", [
    ("rlbsbf", False), ("sbf", False), ("sbf", True)],
    ids=["rlbsbf-delta_rows", "sbf-delta_rows", "sbf-accumulate"])
def test_fused_step_compiles_for_v5e(one_chip, no_persistent_cache,
                                     variant, accumulate):
    """The fused step of each family (bitset rlbsbf, counter sbf, the
    latter in both delta forms) compiles with Mosaic at the largest filter
    the 8 MiB guard admits."""
    cfg = DedupConfig.for_variant(variant, memory_bits=FUSED_BITS,
                                  layout="planes", backend="pallas",
                                  kernel_accumulate=accumulate)
    assert fused_resident_bytes(cfg) <= VMEM_FILTER_BYTES_LIMIT
    bigger = DedupConfig.for_variant(variant, memory_bits=2 * FUSED_BITS,
                                     layout="planes", backend="pallas")
    assert fused_resident_bytes(bigger) > VMEM_FILTER_BYTES_LIMIT
    compiled = _compile(make_fused_step(cfg, interpret=False), cfg,
                        one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_jnp_step_compiles_at_paper_512mb(one_chip, no_persistent_cache):
    """The jnp step at the paper's 512 MB RLBSBF filter (s = 2^31 bits per
    filter) compiles for one v5e chip and fits its HBM."""
    cfg = paper_config("rlbsbf", 512, layout="planes")
    compiled = _compile(make_templated_step(cfg), cfg, one_chip)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes >= 512 * 2**20
    assert total < CHIP_HBM_BYTES

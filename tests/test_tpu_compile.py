"""Compile the main path for a TPU v5e without a chip.

The TPU compiler is installed with jax and compiles for a described
``v5e:2x2`` topology: nothing runs, but Mosaic and XLA refuse here what
they would refuse on the chip (unsupported kernel ops, too much VMEM, a
program larger than HBM). This is the only test file that describes the
chip. The topology is built inside a module-scoped fixture, never at
import time, and the tests skip where it cannot be described.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.hlo_lint import filter_sized_passes
from repro.configs.paper_dedup import paper_config
from repro.core import Dedup, DedupConfig
from repro.core.batched import make_templated_step
from repro.core.state import init_state
from repro.kernels.common import VMEM_FILTER_BYTES_LIMIT, fused_resident_bytes
from repro.kernels.fused_template import make_fused_step
from repro.launch.hw import CHIP_HBM_BYTES

# the largest filter the fused step's VMEM guard admits, for both families
FUSED_BITS = 1 << 24


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e:2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


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


def _compile_stream(cfg, sharding, chunk):
    """``Dedup.run_stream``'s donated scan over ``chunk`` batches."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                         jax.eval_shape(lambda: init_state(cfg)))
    b = cfg.batch_size
    return Dedup(cfg)._stream.lower(
        state, sds((chunk, b), jnp.uint32), sds((chunk, b), jnp.bool_)
    ).compile()


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


@pytest.mark.parametrize("entry", ["step", "stream"])
def test_jnp_bitset_update_in_place_on_v5e(one_chip, no_persistent_cache,
                                            entry):
    """At the paper's 512 MB RLBSBF filter, the donated step and the
    donated stream compiled for one v5e make no filter-sized buffer: the
    touched-word scatters run on a flat view that is a bitcast of the
    chip's tiled (k, W) layout, so they update the filter in place (DESIGN
    §3.2)."""
    cfg = paper_config("rlbsbf", 512, layout="planes")
    if entry == "step":
        compiled = _compile(make_templated_step(cfg), cfg, one_chip)
    else:
        compiled = _compile_stream(cfg, one_chip, 2)
    assert filter_sized_passes(compiled.as_text(), cfg.s_words,
                               donated=True) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < cfg.s_words * 4 // 8


# an array type with its layout: u32[16777216,2]{1,0:T(8,128)}
_ARRAY_RE = re.compile(r"[a-z]+\d*\[([\d,]+)\]\{([\d,]+)")


def _narrow_arrays(hlo: str, min_elems: int):
    """Array types of at least ``min_elems`` elements whose minor
    dimension is narrower than the TPU's 128 lanes (padded to 128)."""
    narrow = set()
    for m in _ARRAY_RE.finditer(hlo):
        dims = [int(x) for x in m.group(1).split(",")]
        minor = dims[int(m.group(2).split(",")[0])]
        if len(dims) > 1 and minor < 128 and math.prod(dims) >= min_elems:
            narrow.add(m.group(0))
    return sorted(narrow)


def test_sbf_stream_count_fields_unpadded_on_v5e(one_chip,
                                                 no_persistent_cache):
    """At ``sbf-128mb``'s deployment (2^29 two-bit counters, Max = 3,
    P = 13, chunks of 8 x 8192 keys), the donated SBF stream compiled for
    one v5e makes no filter-sized array with a minor dimension under 128
    lanes: the count-field accumulator is chunk-major and unscrambled from
    contiguous 1-D slices (DESIGN §3.6), so nothing is padded 64x, and the
    stream plans under 1 GiB of temporaries."""
    cfg = DedupConfig(variant="sbf", memory_bits=1 << 30, k=3, fpr_t=0.1,
                      p_star=0.03, seed=24301, sbf_max=3, sbf_p=13,
                      layout="planes", backend="jnp",
                      batch_size=8192).validate()
    compiled = _compile_stream(cfg, one_chip, 8)
    assert _narrow_arrays(compiled.as_text(), cfg.s_words) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_sharded_stream_in_place_on_v5e_2x2(v5e_2x2, no_persistent_cache):
    """The four-chip deployment (``rlbsbf-2gb-4chip``): the donated,
    pipelined, statically routed ``ShardedDedup`` stream with 2 GiB of
    RLBSBF filter over a 4x1 mesh of a described v5e:2x2, 512 MiB per
    chip. Each chip's shard is carried through the scan as its (1, k, W)
    slice and stepped on bitcast views of it: no filter-sized buffer, and
    temporaries under 1/8 of a shard's bytes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh, set_mesh
    from repro.dedup import ShardedDedup, ShardedDedupConfig
    mesh = make_mesh((4, 1), ("data", "model"), devices=v5e_2x2)
    cfg = DedupConfig(variant="rlbsbf", memory_bits=1 << 34, k=2,
                      fpr_t=0.1, p_star=0.03, layout="planes",
                      backend="jnp", batch_size=32768).validate()
    sd = ShardedDedup(ShardedDedupConfig(base=cfg, capacity_factor=2.0,
                                         pipeline=True), mesh)
    shard = NamedSharding(mesh, P(("data", "model")))
    local = jax.eval_shape(lambda: init_state(sd.local_cfg))
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (4, *x.shape), x.dtype, sharding=shard), local)
    assert local.bits.size * 4 == 512 * 2**20
    b = cfg.batch_size
    kb = jax.ShapeDtypeStruct((8, b), jnp.uint32)
    vb = jax.ShapeDtypeStruct((8, b), jnp.bool_)
    with set_mesh(mesh):
        compiled = sd._make_stream(b // 4).lower(state, kb, vb).compile()
    assert filter_sized_passes(compiled.as_text(), sd.local_cfg.s_words,
                               donated=True) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < local.bits.size * 4 // 8

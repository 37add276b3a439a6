"""Compiles for a described TPU v5e (no chip attached): the main-path
kernels and the one-chip decode step at real widths. The TPU compiler
refuses what interpret mode accepts (misaligned slices, too much VMEM, a
program that does not fit HBM), so these guard every change at no chip
time. A compile that passes is not a chip run."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.engram_gather.engram_gather import gather_rows
from repro.kernels.gated_fuse.ops import engram_gated_fuse
from repro.models.model import (abstract_params, build_decode_step,
                                init_decode_state)
from repro.models.transformer import RunFlags

# HBM the TPU compiler gives one v5e program (it reports 15.75G of 16 GiB)
V5E_PROGRAM_HBM = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("n_rows", [256, 4096])
def test_engram_gather_compiles_at_engram27b_rows(one_chip, n_rows):
    """The DMA gather over a one-chip share of an Engram-27B layer: 16
    tables x 2^18 rows, 160 bf16 lanes stored lane-padded to 256. The
    kernel must run on the table in place: no temporary copy of it."""
    e = get_config("deepseek-7b-1chip").engram
    tables = _spec((e.n_tables, e.table_vocab, e.table_lanes), "bfloat16",
                   one_chip)
    gid = _spec((n_rows,), "int32", one_chip)
    compiled = gather_rows.lower(tables, gid).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2**20, mem.temp_size_in_bytes


def test_gated_fuse_compiles_at_deepseek_width(one_chip):
    """gated_fuse at d_model 4096 and F = 2 orders x 1280 = 2560."""
    T, d, F = 256, 4096, 2560
    args = [_spec(s, "bfloat16", one_chip)
            for s in ((T, d), (T, F), (d, d), (F, d))]
    fuse = jax.jit(lambda h, e, wg, wp: engram_gated_fuse(
        h, e, wg, wp, interpret=False))
    compiled = fuse.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compiled_decode_step(one_chip, flags):
    """deepseek-7b-1chip's served decode step as ``Engine`` builds it: 16
    slots x 1,024 positions, Engram rows passed in, state donated."""
    cfg = get_config("deepseek-7b-1chip")
    put = lambda tree: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), tree)
    params = put(abstract_params(cfg))
    state = put(jax.eval_shape(lambda: init_decode_state(cfg, flags, 16,
                                                         1024)))
    tokens = _spec((16,), "int32", one_chip)
    e = cfg.engram
    rows = [_spec((16, 1, len(e.orders) * e.emb_dim), cfg.dtype, one_chip)
            for _ in cfg.engram_layers()]
    step = jax.jit(build_decode_step(cfg, flags, external_rows=True),
                   donate_argnums=(1,))
    return params, step.lower(params, state, tokens, rows).compile()


def test_one_chip_decode_step_fits_hbm(one_chip):
    """deepseek-7b-1chip's served decode step (16 slots x 1,024 positions,
    state donated) compiles, and with the Engram tables it does not read
    but keeps resident it fits one v5e."""
    params, compiled = _compiled_decode_step(
        one_chip, RunFlags(attn_bf16_scores=True))
    mem = compiled.memory_analysis()
    tables = sum(layer["tables"].size * layer["tables"].dtype.itemsize
                 for layer in params["engram"]["layers"])
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes + tables)
    assert mem.alias_size_in_bytes > 0, "decode state was not donated"
    assert need < V5E_PROGRAM_HBM, need


def test_one_chip_decode_step_writes_kv_rows_in_place(one_chip):
    """The layer scan of the served decode step (``RunFlags()``, as
    ``Engine`` builds it) writes each slot's new K/V row into the donated,
    stacked cache and reads its layer there. Its temporaries stay below
    one layer's K slab (16 x 1,024 x 32 x 128 bf16 = 134 MB): slicing a
    layer's whole cache out of the stack and writing it back, as the scan
    once did, needs 404 MB of temporaries and ~60% of the step's modelled
    cycles."""
    _, compiled = _compiled_decode_step(one_chip, RunFlags())
    slab = 16 * 1024 * 32 * 128 * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < slab, temp

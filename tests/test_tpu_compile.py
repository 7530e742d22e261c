"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles for a ``v5e:2x2`` topology that
is described, not attached, so what the TPU compiler refuses (a block shape
off the (8, 128) tiling, a gather Mosaic cannot lower, a buffer that does not
fit VMEM or HBM) fails here instead of on the chip. Shapes are the ones
``chip_smoke.py`` runs.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import local_spgemm as lsp
from repro.core.sparse import SparseCOO
from repro.kernels.col_prune import col_topk_bounds_pallas
from repro.kernels.spgemm_hash import hash_insert


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without the chip: keep the cache off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _coo(one_chip, cap, shape):
    i32 = _spec(one_chip, (cap,), jnp.int32)
    return SparseCOO(i32, i32, _spec(one_chip, (cap,), jnp.float32),
                     _spec(one_chip, (), jnp.int32), shape)


@pytest.mark.parametrize("m,n", [(65536, 256), (4096, 512)])
def test_col_prune_kernel_compiles(one_chip, m, n):
    """The MCL dense-path prune kernel, at the n = 2^16 smoke batch (a whole
    65536-row column block no longer has to fit VMEM)."""
    x = _spec(one_chip, (m, n), jnp.float32)
    compiled = jax.jit(
        lambda x: col_topk_bounds_pallas(x, 64, interpret=False)
    ).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_hash_insert_compiles_in_place(one_chip):
    """The hash path's insert at the forced-hash smoke shape (a 2^26-slot
    table, 4096-entry chunks): plain XLA, and the table is updated in place
    — no per-round copy of the table."""
    table, chunk = 1 << 26, 4096
    compiled = jax.jit(
        lambda tk, tv, k, v, ok: hash_insert(
            tk, tv, k, v, ok, add_kind="sum", max_probes=32
        )
    ).lower(
        _spec(one_chip, (table,), jnp.int32),
        _spec(one_chip, (table,), jnp.float32),
        _spec(one_chip, (chunk,), jnp.int32),
        _spec(one_chip, (chunk,), jnp.float32),
        _spec(one_chip, (chunk,), jnp.bool_),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < table * 4


def test_col_split_memory_is_linear(one_chip):
    """ColSplit into 4 fiber pieces of a 2^22-entry D tile: its temporaries
    stay a few bytes per entry (a (cap, pieces) one-hot would be padded to
    128 lanes on the TPU, 2 GiB here)."""
    cap = 1 << 22
    d = _coo(one_chip, cap, (1 << 19, 1 << 16))
    compiled = jax.jit(lambda d: d.split_col_blocks(4, cap // 2)).lower(
        d
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * cap


def test_spgemm_esc_compiles(one_chip):
    """The jitted ESC local multiply (column sort, expansion, packed-key
    compress) for a 4096 x 4096 tile pair."""
    n, cap = 4096, 1 << 17
    a = _coo(one_chip, cap, (n, n))
    compiled = jax.jit(
        lambda a, b: lsp.spgemm_esc(a, b, out_cap=1 << 20, flops_cap=1 << 22)
    ).lower(a, a).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * (1 << 22)

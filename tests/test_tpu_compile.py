"""Compile the served path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: slices not aligned
to the tiling, kernels over their fast-memory budget, programs that do
not fit the chip's HBM.  These tests compile, at served widths and with
``interpret=False``, the five range_match kernels and the store programs
the epoch driver runs, so such a refusal fails here and not on the chip.
Nothing runs: they say nothing about results or times.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import core as C
from repro.cluster.epoch import PRELOAD_CHUNK, _preload_chunk
from repro.core.store import MERGE, apply_routed, make_store
from repro.kernels.range_match import kernel as KR

# served widths: one epoch batch, the slot pool, nodes, chain headroom
B, SPAD, N, R_MAX = 4096, 128, 8, 4
NPAD = 128          # load registers, lane-padded
SLAB_C = 1024       # range_match_apply's slab length at served widths
N_SWITCHES = 4
# the chip-smoke store: 8 nodes x 1,000,000 slots of 128-byte values
STORE_C, VALUE_DIM = 1_000_000, 32
HBM_BYTES = 16 * 2**30
u32, i32 = jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


def test_range_match_compiles(spec):
    _compile(
        functools.partial(KR.range_match_pallas, num_slots=SPAD,
                          interpret=False),
        spec((B,), u32), spec((B,), i32), spec((SPAD,), u32),
        spec((SPAD,), u32), spec((R_MAX, SPAD), i32), spec((SPAD,), i32),
    )


def test_range_match_spread_compiles(spec):
    _compile(
        functools.partial(KR.range_match_spread_pallas, num_slots=SPAD,
                          interpret=False),
        spec((B,), u32), spec((B,), i32), spec((B,), i32), spec((B,), i32),
        spec((SPAD,), u32), spec((SPAD,), u32), spec((R_MAX, SPAD), i32),
        spec((SPAD,), i32), spec((NPAD,), i32),
    )


def test_range_match_spread_dirty_compiles(spec):
    _compile(
        functools.partial(KR.range_match_spread_dirty_pallas, num_slots=SPAD,
                          interpret=False),
        spec((B,), u32), spec((B,), i32), spec((B,), i32), spec((B,), i32),
        spec((SPAD,), u32), spec((SPAD,), u32), spec((R_MAX, SPAD), i32),
        spec((SPAD,), i32), spec((NPAD,), i32), spec((R_MAX, SPAD), i32),
    )


def test_range_match_stale_compiles(spec):
    W = N_SWITCHES
    _compile(
        functools.partial(KR.range_match_stale_pallas, num_slots=SPAD,
                          r_max=R_MAX, interpret=False),
        spec((B,), u32), spec((B,), i32), spec((B,), i32),
        spec((W, SPAD), u32), spec((W, SPAD), u32),
        spec((W * R_MAX, SPAD), i32), spec((W, SPAD), i32),
        spec((W, SPAD), i32), spec((SPAD,), i32),
    )


@pytest.mark.parametrize("n_nodes,r_max,spad,slab_c", [
    (N, R_MAX, SPAD, SLAB_C),   # served widths
    (32, 5, 256, 512),          # benchmarks/kernel_bench.py widths
])
def test_range_match_apply_compiles(spec, n_nodes, r_max, spad, slab_c):
    _compile(
        functools.partial(KR.range_match_apply_pallas, num_slots=spad,
                          slab_len=slab_c, interpret=False),
        spec((B,), u32), spec((B,), i32), spec((B,), i32), spec((B,), i32),
        spec((B,), u32), spec((spad,), u32), spec((spad,), u32),
        spec((r_max, spad), i32), spec((spad,), i32), spec((NPAD,), i32),
        spec((r_max, spad), i32), spec((n_nodes, slab_c), u32),
    )


def _store_specs(spec):
    store = jax.eval_shape(lambda: make_store(N, STORE_C, VALUE_DIM))
    return jax.tree.map(lambda a: spec(a.shape, a.dtype), store)


def _fits_hbm(compiled):
    m = compiled.memory_analysis()
    print(m)   # shown with pytest -s
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB does not fit the chip"


def test_apply_routed_compiles_at_smoke_size(spec):
    """The served store apply at the chip-smoke size fits one chip."""
    directory = C.make_directory(64, N, 3, r_max=R_MAX, n_slots=SPAD)
    q = jax.eval_shape(lambda: C.make_queries(
        jnp.zeros((B,), u32), jnp.zeros((B,), i32),
        jnp.zeros((B, VALUE_DIM), jnp.float32)))
    q = jax.tree.map(lambda a: spec(a.shape, a.dtype), q)
    dec = jax.eval_shape(lambda d, qq: C.route(d, qq)[0], directory, q)
    dec = jax.tree.map(lambda a: spec(a.shape, a.dtype), dec)
    compiled = jax.jit(apply_routed).lower(_store_specs(spec), q,
                                           dec).compile()
    _fits_hbm(compiled)
    # the TPU compiler keeps the per-shard merge skip a conditional
    assert sum(" conditional(" in line and f'/{MERGE}/cond"' in line
               for line in compiled.as_text().splitlines()) == 1


def test_preload_chunk_compiles_at_smoke_size(spec):
    """One preload chunk at the chip-smoke size fits one chip (the load
    phase's peak does not grow with the record count)."""
    directory = C.make_directory(64, N, 3, r_max=R_MAX, n_slots=SPAD)
    d = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                     jax.eval_shape(lambda: directory))
    compiled = _preload_chunk.lower(
        _store_specs(spec), d, spec((PRELOAD_CHUNK,), u32),
        spec((PRELOAD_CHUNK,), i32),
        spec((PRELOAD_CHUNK, VALUE_DIM), jnp.float32), max_scan_results=8,
    ).compile()
    _fits_hbm(compiled)

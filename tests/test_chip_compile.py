"""The Pallas kernels of the main path compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: block shapes off the (8, 128) tiling, scoped-VMEM overflow, ops
Mosaic cannot legalize.  These tests compile each kernel's public wrapper,
padding included, for a described ``v5e:2x2`` topology with the installed
TPU compiler; no chip is needed and nothing runs.

Widths are the paper's §6 deployment: m=40 machines, t=128 query points,
K=256 Nyström columns (kin40k's 10,000 points over 40 machines) and K=1152
(SARCOS's 44,484), d in {8, 21}, R in {24, 100} bits per sample.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this module.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.comm.accounting import row_bits
from repro.core import jax_scheme
from repro.core import quantizers as Q
from repro.kernels.epilogue.ops import epilogue_moments, epilogue_moments_fleet
from repro.kernels.gram.ops import gram
from repro.kernels.qgram.ops import qgram_packed

M, T_QUERY, N_SHARD = 40, 128, 250
TENANT_SLOTS = 8  # FleetServer's default stack: twice a flush width of 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel, not XLA
    return compiled


F32, U32, I32 = jnp.float32, jnp.uint32, jnp.int32


@pytest.mark.parametrize("d", [8, 21])
def test_gram_compiles(one_chip, d):
    # the center's K x N inner products: its shard against all 10,000 rows
    _compile(functools.partial(gram, interpret=False), one_chip,
             ((N_SHARD, d), F32), ((M * N_SHARD, d), F32))


@pytest.mark.parametrize("d,bits", [(8, 24), (8, 100), (21, 24), (21, 100)])
def test_qgram_packed_compiles(one_chip, d, bits):
    words = -(-row_bits(bits, d, Q.DEFAULT_MAX_BITS) // 32)
    cents = 1 << jax_scheme.codebook_cap(bits, Q.DEFAULT_MAX_BITS)
    fn = functools.partial(qgram_packed, total_bits=bits, interpret=False)
    _compile(lambda w, r, c, y, mk: fn(w, r, c, y, mask=mk), one_chip,
             ((N_SHARD, words), U32), ((d,), I32), ((d, cents), F32),
             ((N_SHARD, d), F32), ((N_SHARD,), F32))


@pytest.mark.parametrize("K", [256, 1152])
@pytest.mark.parametrize("fuse", ["kl", "rbcm"])
def test_epilogue_compiles(one_chip, K, fuse):
    _compile(functools.partial(epilogue_moments, fuse=fuse, interpret=False),
             one_chip, ((M, T_QUERY, K), F32), ((M, K, K), F32),
             ((M, K, K), F32), ((M, K), F32), ((T_QUERY,), F32),
             ((T_QUERY,), F32), ((M,), F32))


@pytest.mark.parametrize("K", [256, 1152])
def test_epilogue_fleet_compiles(one_chip, K):
    T = TENANT_SLOTS
    _compile(functools.partial(epilogue_moments_fleet, fuse="kl",
                               interpret=False),
             one_chip, ((T, M, T_QUERY, K), F32), ((T, M, K, K), F32),
             ((T, M, K, K), F32), ((T, M, K), F32), ((T, T_QUERY), F32),
             ((T, T_QUERY), F32), ((T, M), F32))

"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel body as plain jnp, so it cannot see what
Mosaic refuses: block shapes off the (8, 128) tiling, primitives with no
TPU lowering, loads of packed dtypes. These cases lower and compile each
kernel at real widths against ``v5e:2x2`` — no chip needed, nothing runs.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and under pytest-xdist every
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.assign import assign_pallas
from repro.kernels.distance_topk import (distance_topk_gather_pallas,
                                         distance_topk_pallas)
from repro.kernels.quant_topk import quant_coarse_gather_pallas

BM, BN = 128, 512
N_Q, N_S = 1024, 65536
NR_TILES, NS_TILES = N_Q // BM, N_S // BN


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("alive", [False, True], ids=["host", "megastep"])
@pytest.mark.parametrize("d,k", [(10, 10), (128, 10), (128, 100)])
def test_distance_topk_gather_compiles(one_chip, d, k, alive):
    shapes = [((N_Q, d), jnp.float32), ((N_S, d), jnp.float32),
              ((NR_TILES, NS_TILES), jnp.int32), ((NR_TILES,), jnp.int32)]
    if alive:
        shapes.append(((N_S,), jnp.float32))

    def fn(r, s, sched, cnt, live=None):
        return distance_topk_gather_pallas(r, s, k, sched, cnt, alive=live,
                                           bm=BM, bn=BN)

    merged = _compile(fn, one_chip, *shapes).out_info[2]
    assert (merged.shape, merged.dtype) == ((NR_TILES,), jnp.int32)


@pytest.mark.parametrize("d,k", [(10, 10), (128, 10)])
def test_distance_topk_dense_compiles(one_chip, d, k):
    compiled = _compile(
        lambda r, s: distance_topk_pallas(r, s, k, bm=BM, bn=BN),
        one_chip, ((N_Q, d), jnp.float32), ((N_S, d), jnp.float32))
    merged = compiled.out_info[2]
    assert (merged.shape, merged.dtype) == ((NR_TILES,), jnp.int32)


@pytest.mark.parametrize("d,mp", [(128, 128)])
def test_quant_coarse_gather_compiles(one_chip, d, mp):
    def fn(qi, qscale, qeps, theta, si, sscale, seps, alive, sched, cnt):
        return quant_coarse_gather_pallas(
            qi, qscale, qeps, theta, si, sscale, seps, alive, mp, sched,
            cnt, bm=BM, bn=BN)

    _compile(fn, one_chip,
             ((N_Q, d), jnp.int8), ((N_Q,), jnp.float32),
             ((N_Q,), jnp.float32), ((N_Q,), jnp.float32),
             ((N_S, d), jnp.int8), ((NS_TILES,), jnp.float32),
             ((N_S,), jnp.float16), ((N_S,), jnp.float32),
             ((NR_TILES, NS_TILES), jnp.int32), ((NR_TILES,), jnp.int32))


@pytest.mark.parametrize("d", [128])
def test_assign_compiles(one_chip, d):
    _compile(assign_pallas, one_chip,
             ((N_S, d), jnp.float32), ((1024, d), jnp.float32))

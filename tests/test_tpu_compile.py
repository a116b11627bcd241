"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode cannot see what Mosaic refuses (block shapes off the
(8, 128) tiling, int32 argmax, scalar VMEM stores, i1 relayouts, VMEM
over the scoped limit). These tests compile each kernel with
``interpret=False`` for a described ``v5e:2x2`` topology — no chip is
attached, nothing runs — at the engine buckets the ``pallas_peo`` path
serves, and the ``sharded`` backend's programs over the four described
chips (the ``jax_fast`` one and the fused kernel per shard), which must
hold no collective.

The topology is described inside a module fixture (libtpu is loaded only
by the worker that runs this file) and the persistent compile cache is off
around these compiles: their entries cannot be read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    return compiled


@pytest.mark.parametrize("kernel,n_pad,batch", [
    ("fused", 256, 32),
    ("fused", 2048, 8),            # FUSED_MAX_NPAD
    ("fused_witness", 1024, 4),    # FUSED_WITNESS_MAX_NPAD
    ("fused_packed", 64, 32),      # FUSED_PACK_MAX_NPAD
])
def test_lexbfs_fused_kernels_compile(one_chip, kernel, n_pad, batch):
    from repro.kernels.lexbfs_fused import ops

    fn = {
        "fused": lambda a: ops._fused(a, interpret=False),
        "fused_witness": lambda a: ops._fused_witness(a, interpret=False),
        "fused_packed": lambda a: ops._fused_packed(
            a, pack=8, interpret=False),
    }[kernel]
    _compile(fn, jax.ShapeDtypeStruct(
        (batch, n_pad, n_pad), jnp.bool_, sharding=one_chip))


@pytest.mark.parametrize("kernel", ["parents", "violations"])
def test_peo_check_kernels_compile_at_largest_bucket(one_chip, kernel):
    from repro.kernels.peo_check.peo_check import (
        peo_parents_pallas,
        peo_violations_pallas,
    )

    n = 8192
    mat = jax.ShapeDtypeStruct((n, n), jnp.int8, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    if kernel == "parents":
        _compile(lambda a, pos: peo_parents_pallas(a, pos, interpret=False),
                 mat, vec)
    else:
        _compile(lambda a, ap, pos, p: peo_violations_pallas(
            a, ap, pos, p, interpret=False), mat, mat, vec, vec)


COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "all-to-all", "reduce-scatter")


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    from repro.engine.mesh import MESH_AXIS

    return Mesh(np.asarray(topo.devices), (MESH_AXIS,))


@pytest.mark.parametrize("n_pad,batch", [(256, 32), (2048, 8)])
def test_sharded_verdicts_compile_without_collectives(mesh4, n_pad, batch):
    from repro.engine.mesh import batch_sharding, make_mesh_verdicts

    x = jax.ShapeDtypeStruct((batch, n_pad, n_pad), jnp.bool_,
                             sharding=batch_sharding(mesh4))
    hlo = make_mesh_verdicts(mesh4).lower(x).compile().as_text()
    for op in COLLECTIVES:
        assert op not in hlo, f"sharded program holds {op}"


@pytest.mark.parametrize("n_pad,batch", [(2048, 128), (2048, 4)])
def test_sharded_fused_compiles_per_shard_without_collectives(
        mesh4, n_pad, batch):
    """The fused kernel once per chip: a Mosaic kernel in the program,
    no collective, and one 32-graph (or 1-graph) shard per chip."""
    from repro.engine.mesh import batch_sharding, make_mesh_fused

    x = jax.ShapeDtypeStruct((batch, n_pad, n_pad), jnp.int8,
                             sharding=batch_sharding(mesh4))
    compiled = make_mesh_fused(mesh4, interpret=False).lower(x).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    for op in COLLECTIVES:
        assert op not in hlo, f"sharded fused program holds {op}"
    per_chip = batch // 4 * n_pad * n_pad
    assert compiled.memory_analysis().argument_size_in_bytes == per_chip

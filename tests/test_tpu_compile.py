"""Compile the chip's main-path programs for a described TPU v5e.

Nothing runs: the TPU compiler, installed with jaxlib, compiles for a v5e
that is described and not attached, so a kernel the chip would refuse
(a misaligned slice, too much VMEM, an unlowerable primitive) fails here,
where interpret-mode tests cannot see it. The topology is described inside
a module fixture, never at import: only one process may load the TPU
library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.kernels.partition_score.ops import kernel_tiling
from repro.kernels.partition_score.partition_score import (
    fennel_scores_sharded_pallas,
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# batch 512 is the engine's default chunk; widths < 128 are read in one
# static load, wider ones in 128-column chunks; 40 rows take 8-row blocks
@pytest.mark.parametrize(
    "shards,batch,width",
    [(s, 512, w) for s in (1, 4) for w in (8, 64, 1024)] + [(4, 40, 16)],
)
def test_partition_score_compiles_for_v5e(one_chip, shards, batch, width):
    k = 8
    block_b, d_chunk, cp, dp = kernel_tiling(batch, width)
    nbr = jax.ShapeDtypeStruct((shards, cp, dp), jnp.int32, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((shards, k), jnp.float32, sharding=one_chip)
    compiled = fennel_scores_sharded_pallas.lower(
        nbr, sizes, alpha=0.0, gamma=1.5, block_b=block_b, d_chunk=d_chunk
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_pagerank_compiles_for_v5e_mesh(topo):
    from repro.analytics import GraphEngine, localize, pagerank_program
    from repro.core import get_partitioner
    from repro.graph import rmat_graph

    k = 4
    g = rmat_graph(2000, avg_degree=8, seed=5)
    lg = localize(g, get_partitioner("fennel")(g, k, seed=0), k)
    mesh = Mesh(np.array(topo.devices[:k]), ("w",))
    compiled = GraphEngine(lg, pagerank_program()).lower_sharded(
        mesh, iters=3
    ).compile()
    assert "all-to-all" in compiled.as_text()

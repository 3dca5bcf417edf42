"""Compile-only checks against a described TPU v5e chip.

Each test lowers one program of the sweep's jax path (or a Pallas kernel) at
the shapes the chip runs and compiles it for one chip of a described
`v5e:2x2` topology: nothing runs, so these say nothing about results or
times, but they catch what the chip's compiler refuses (misaligned blocks,
memory it cannot fit) without a chip.  The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.placement import auto_mesh_for_parts
from repro.experiments.batched import _jax_contract_fn
from repro.experiments.placement_batch import _jax_descend_fn
from repro.graph.algorithms import bfs_program, pagerank_program
from repro.graph.vertex_program import traced_step
from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.segment_spmm.kernel import ell_spmm_pallas
from repro.nocsim.batch import _jax_step_fn
from repro.nocsim.credit import _jax_credit_fn
from repro.nocsim.model import NocSimParams

# soc-pokec at scale 0.25, the largest graph the sweep runs (+1 sentinel row).
NODES, EDGES = 400_001, 7_650_000
# The paper grid's shape group: 24 configs of 16 engines, 64 routers each.
CONFIGS, ROUTERS = 24, 64
# The contention grid: 24 configs, links padded to the largest fabric
# (torus3d's 384), one flow per ordered shard pair at most.
WINDOWS = NocSimParams().windows
FLOWS = ROUTERS * (ROUTERS - 1)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip compile can be written to the persistent cache but not
    # read back without a chip: keep the cache off around these compiles.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype in specs)


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("program", [pagerank_program, bfs_program], ids=["pagerank", "bfs"])
def test_traced_step_at_soc_pokec_quarter_scale(one_chip, program):
    src, dst, valid, weight, props, active, inv_outdeg, base = _shapes(
        one_chip,
        ((EDGES,), jnp.int32),
        ((EDGES,), jnp.int32),
        ((EDGES,), jnp.bool_),
        ((EDGES,), jnp.float32),
        ((NODES,), jnp.float32),
        ((NODES,), jnp.bool_),
        ((NODES,), jnp.float32),
        ((), jnp.float32),
    )
    aux = {"inv_outdeg": inv_outdeg, "base": base} if program is pagerank_program else {}
    compiled = _compile(traced_step, program(), (src, dst, valid, weight), props, active, aux)
    # The edge arrays are arguments of the program, not constants in it.
    assert compiled.memory_analysis().argument_size_in_bytes >= EDGES * 9


def test_simulate_batch_contraction(one_chip):
    links = auto_mesh_for_parts(16, "mesh2d").num_links()
    args = _shapes(
        one_chip,
        ((CONFIGS, ROUTERS, ROUTERS), jnp.float32),
        ((ROUTERS, ROUTERS), jnp.float32),
        ((links, ROUTERS * ROUTERS), jnp.float32),
    )
    _compile(_jax_contract_fn(True), *args)


def test_descent_while_loop(one_chip):
    w, d, sites, occ, tol = _shapes(
        one_chip,
        ((CONFIGS, ROUTERS, ROUTERS), jnp.float32),
        ((CONFIGS, ROUTERS, ROUTERS), jnp.float32),
        ((CONFIGS, ROUTERS), jnp.int32),
        ((CONFIGS, ROUTERS), jnp.bool_),
        ((), jnp.float32),
    )
    _compile(_jax_descend_fn(), w, d, sites, occ, max_steps=4 * ROUTERS, tol=tol)


def test_open_nocsim_scan(one_chip):
    links = auto_mesh_for_parts(16, "torus3d").num_links()
    inj, init = _shapes(
        one_chip, ((WINDOWS, CONFIGS, links), jnp.float32), ((CONFIGS, links), jnp.float32)
    )
    _compile(_jax_step_fn(), inj, init)


def test_credit_nocsim_scan(one_chip):
    links = auto_mesh_for_parts(16, "torus3d").num_links()
    pairs = CONFIGS * FLOWS * 6  # route incidences: flows × hops (≤ 6 on 4×4×4)
    args = _shapes(
        one_chip,
        ((WINDOWS, CONFIGS, links), jnp.float32),
        ((WINDOWS, CONFIGS, FLOWS), jnp.float32),
        ((CONFIGS, FLOWS), jnp.float32),
        ((CONFIGS, links), jnp.float32),
        ((CONFIGS, links, FLOWS), jnp.float32),
        ((pairs,), jnp.int32),
        ((pairs,), jnp.int32),
        ((pairs,), jnp.int32),
        ((), jnp.float32),
    )
    _compile(_jax_credit_fn(), *args)


def test_segment_spmm_kernel(one_chip):
    # One ELL bucket (1024 rows × 64 slots) gathering gin-tu's 64-wide
    # features over the soc-pokec@0.25 vertex set.
    args = _shapes(
        one_chip,
        ((NODES - 1, 64), jnp.float32),
        ((1024, 64), jnp.int32),
        ((1024, 64), jnp.float32),
    )
    compiled = _compile(ell_spmm_pallas, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_embedding_bag_kernel(one_chip):
    # dcn-v2: 26 tables of 16-wide embeddings; 8-id bags, 16 rows per call.
    args = _shapes(
        one_chip,
        ((26, 100_000, 16), jnp.float32),
        ((16, 26, 8), jnp.int32),
        ((16, 26, 8), jnp.float32),
    )
    compiled = _compile(embedding_bag_pallas, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel(one_chip):
    # 32 query heads over 8 KV heads of 128 lanes at a 4096-token context.
    q, k, v = _shapes(
        one_chip,
        ((1, 4096, 32, 128), jnp.bfloat16),
        ((1, 4096, 8, 128), jnp.bfloat16),
        ((1, 4096, 8, 128), jnp.bfloat16),
    )
    compiled = _compile(flash_attention_pallas, q, k, v)
    assert "tpu_custom_call" in compiled.as_text()

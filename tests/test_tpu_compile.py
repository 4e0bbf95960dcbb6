"""TPU compile rehearsals for the served path's device programs.

Each test compiles one program for a described (not attached) TPU v5e chip
at the shapes the served path uses, so a kernel or program the chip's
compiler would refuse fails here, on the CPU, before any chip run.  Nothing
runs: these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every pytest worker imports every test file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import greedy, jobs as J
from repro.kernels.minplus import (minplus_matmul_pallas,
                                   minplus_matmul_pallas_batched)
from repro.scenarios import make_scenario

SCENARIO = "us-backbone:lm"   # the largest catalog deployment (V=24)
JOBS = 32                     # jobs per served window


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: cannot describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    """The tree's leaves as ShapeDtypeStructs placed on ``sharding``."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _served_window():
    """One served window's network and job batch at the scenario's real
    shapes."""
    sc = make_scenario(SCENARIO, seed=0)
    jobs = sc.sample_jobs(np.random.default_rng(0), JOBS)
    return sc.topology.view(), J.batch_jobs(jobs, pad_to=sc.max_layers)


def test_minplus_2d_compiles_for_tpu(one_chip):
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32, sharding=one_chip)
    compiled = minplus_matmul_pallas.lower(a, a).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_minplus_batched_compiles_for_tpu(one_chip):
    a = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32, sharding=one_chip)
    compiled = minplus_matmul_pallas_batched.lower(a, a).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def fused_solve(one_chip):
    """``_fused_solve``'s operands at the served shapes and the program
    compiled from them for the described chip."""
    net, batch = _served_window()
    operands = (net,) + greedy._stage_window(batch)
    compiled = greedy._fused_solve.lower(
        *_shapes(operands, one_chip)).compile()
    return operands, compiled


def test_fused_solve_compiles_for_tpu(fused_solve):
    _, compiled = fused_solve
    assert compiled.as_text()


def test_fused_solve_emits_hops_and_no_snapshot_for_tpu(fused_solve):
    """The compiled solve's round outputs carry each round's hops
    ``[P, Lmax+1, V, 2]`` int32 and no ``[V, V]`` snapshot: ``plan.paths``
    need neither a second program nor a closure crossing the host link."""
    operands, compiled = fused_solve
    net, batch = operands[0], operands[1]
    assert compiled.as_text()
    rounds, _, _ = jax.eval_shape(greedy._fused_solve, *operands)
    v, p = net.num_nodes, batch.num_jobs
    hops = rounds[-1]
    assert (hops.shape, hops.dtype) == ((p, batch.max_layers + 1, v, 2),
                                        jnp.int32)
    assert all(leaf.shape[-2:] != (v, v)
               for leaf in jax.tree_util.tree_leaves(rounds))


def test_fused_solve_many_compiles_for_tpu(one_chip):
    net, batch = _served_window()
    # two queued windows, staged as greedy_route_windows stages them
    _, *operands = greedy._stage_windows([batch, batch])
    compiled = greedy._fused_solve_many.lower(
        *_shapes((net, *operands), one_chip)).compile()
    assert compiled.as_text()

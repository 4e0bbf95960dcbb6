"""Sharding-rule legality across all architectures, input-spec shapes, and
HLO collective parsing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import registry
from repro.configs.shapes import SHAPES, shape_applicable
from repro.distributed import sharding as sh
from repro.launch import hlo_analysis
from repro.models import model as M

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
@pytest.mark.parametrize("mesh", [MESH1, MESH2], ids=["single", "multi"])
def test_param_specs_legal_for_all_archs(arch, mesh):
    """Every sharded dim divides by its mesh-axis size (GSPMD legality)."""
    cfg = registry.config(arch)
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = sh.param_specs(shapes, mesh)

    def check(path, leaf, spec):
        for i, axis in enumerate(spec):
            if axis is None:
                continue
            parts = axis if isinstance(axis, tuple) else (axis,)
            size = int(np.prod([mesh.shape[a] for a in parts]))
            assert leaf.shape[i] % size == 0, (arch, path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), shapes, specs)


def test_some_params_are_sharded():
    """The rules must actually shard the big matrices (not everything P())."""
    cfg = registry.config("olmo_1b")
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = sh.param_specs(shapes, MESH1)
    n_sharded = sum(1 for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
        if any(a is not None for a in s))
    assert n_sharded >= 5


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_shapes(arch, shape):
    cfg = registry.config(arch)
    spec = SHAPES[shape]
    ok, reason = shape_applicable(cfg, spec)
    if not ok:
        assert "sub-quadratic" in reason or "full-attention" in reason
        return
    batch = registry.get(arch).input_specs(spec, cfg)
    if spec.kind == "decode":
        assert batch["tokens"].shape == (spec.global_batch, 1)
        assert batch["pos"].shape == ()
    else:
        assert batch["tokens"].shape == (spec.global_batch, spec.seq_len)
    for leaf in jax.tree.leaves(batch):
        assert isinstance(leaf, jax.ShapeDtypeStruct)  # no allocation


def test_long_500k_runs_only_for_subquadratic():
    runs = [a for a in registry.ARCH_IDS
            if shape_applicable(registry.config(a), SHAPES["long_500k"])[0]]
    assert sorted(runs) == ["xlstm_125m", "zamba2_2_7b"]


def test_hlo_collective_parsing():
    hlo = """
  %ag = f32[256,256]{1,0} all-gather(%x), replica_groups=[2,4]<=[4,2]T(1,0), dimensions={0}
  %fused = f32[256,256]{1,0} fusion(%ag), kind=kLoop
  %ar = bf16[128]{0} all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%add
  %rs = f32[32,16]{1,0} reduce-scatter(%z), replica_groups=[8,2]<=[16], dimensions={0}
  %cp = f32[64]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %ars = (f32[8]{0}, f32[8]{0}) all-reduce-start(%a, %b), replica_groups=[1,8]<=[8]
"""
    stats = hlo_analysis.collective_stats(hlo)
    per = stats["per_op"]
    assert per["all-gather"]["count"] == 1
    assert per["all-gather"]["bytes"] == 256 * 256 * 4
    assert per["all-reduce"]["count"] == 2          # incl. -start form
    assert per["all-reduce"]["bytes"] == 128 * 2 + 2 * 8 * 4
    assert per["reduce-scatter"]["bytes"] == 32 * 16 * 4
    assert per["collective-permute"]["effective_bytes"] == 64 * 4
    # all-gather over groups of 4: factor 3/4
    np.testing.assert_allclose(per["all-gather"]["effective_bytes"],
                               256 * 256 * 4 * 0.75)
    assert stats["total_bytes"] > 0


def test_batch_specs_shard_batch_dim():
    batch = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32),
             "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    specs = sh.batch_specs(batch, MESH2)
    assert specs["tokens"] == P(("pod", "data"), None)
    assert specs["pos"] == P()
    odd = {"tokens": jax.ShapeDtypeStruct((1, 128), jnp.int32)}
    assert sh.batch_specs(odd, MESH2)["tokens"] == P(None, None)

"""Dry-run machinery integration test on a tiny 1-device mesh.

The full 256/512-device dry-runs run via launch/dryrun.py (results in
results/dryrun_*.jsonl); here we verify the cell-building + lowering +
analysis machinery end-to-end where CI can afford it: reduced LM config,
real lower().compile(), roofline term extraction, HLO collective parsing.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import policy_for_mesh
from repro.distributed import make_mesh
from repro.launch.hlo_analysis import RooflineTerms, analyze_compiled, collective_bytes_from_hlo


def test_collective_parser_counts_psum():
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    def f(x):
        return jax.lax.psum(x, "data")

    fn = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=P(None), out_specs=P(None), check_vma=False)
    )
    compiled = fn.lower(jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
    coll = collective_bytes_from_hlo(compiled.as_text())
    assert coll["all-reduce"] == 1024 * 4
    assert coll["total"] == 1024 * 4


def test_collective_parser_shape_regex():
    text = """
  %ar = bf16[256,1024]{1,0} all-reduce(bf16[256,1024]{1,0} %x), replica_groups={}
  %ag.1 = f32[512]{0} all-gather(f32[256]{0} %y), dimensions={0}
  %plain = f32[8,8]{1,0} add(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b)
"""
    coll = collective_bytes_from_hlo(text)
    assert coll["all-reduce"] == 256 * 1024 * 2
    assert coll["all-gather"] == 512 * 4
    assert coll["count"] == 2


def test_roofline_terms_math():
    t = RooflineTerms(
        flops_per_device=197e12,  # exactly 1s of compute
        bytes_per_device=819e9,  # exactly 1s of HBM
        collective_bytes_per_device=100e9,  # 2s of ICI
        n_devices=4,
        model_flops_total=4 * 197e12 / 2,
    )
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(1.0)
    assert t.collective_s == pytest.approx(2.0)
    assert t.dominant == "collective"
    assert t.useful_flops_ratio == pytest.approx(0.5)
    assert t.roofline_fraction == pytest.approx(0.25)


def test_reduced_lm_cell_lowers_and_compiles():
    """End-to-end: tiny LM train cell on a (1,1) mesh, full analysis path."""
    from repro.configs.lm_common import LMArchParams, make_train_cell
    from repro.models.transformer import TransformerConfig

    tiny = TransformerConfig(
        name="tiny_dry", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_ff=64, vocab=128, compute_dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=64,
    )
    mesh = make_mesh((1, 1), ("data", "model"))
    policy = policy_for_mesh(mesh)
    import repro.configs.lm_common as lmc

    # shrink the assigned shape BEFORE cell creation (captured at build)
    orig = lmc.TRAIN_SHAPE.copy()
    lmc.TRAIN_SHAPE.update(seq_len=64, global_batch=2)
    cell = make_train_cell("tiny_dry", LMArchParams(cfg=tiny))
    try:
        built = cell.build(mesh, policy)
        with mesh:
            compiled = (
                jax.jit(built.fn, in_shardings=built.in_shardings)
                .lower(*built.input_specs)
                .compile()
            )
            corr_flops = 0.0
            for sc in built.scan_corrections:
                bc = jax.jit(sc.fn, in_shardings=sc.in_shardings).lower(*sc.input_specs).compile()
                c = bc.cost_analysis()
                c = c[0] if isinstance(c, list) else c
                corr_flops += sc.multiplier * float(c.get("flops", 0))
        terms, extra = analyze_compiled(compiled, 1, built.model_flops_per_step, extra_flops=corr_flops)
        assert terms.flops_per_device > 0
        assert terms.bytes_per_device > 0
        assert extra["memory"]["temp_bytes"] is not None
        # 6ND should be within 20x of corrected HLO flops for this tiny model
        assert 0.05 < terms.useful_flops_ratio < 20.0
    finally:
        lmc.TRAIN_SHAPE.update(orig)


def test_mesh_function_does_not_touch_devices_on_import():
    """make_production_mesh must be a function, not module state."""
    import repro.distributed.mesh_utils as m

    assert callable(m.make_production_mesh)
    assert not any(
        isinstance(getattr(m, n), jax.sharding.Mesh) for n in dir(m) if not n.startswith("_")
    )


def test_dryrun_script_header_sets_xla_flags_first():
    """The first two lines of dryrun.py must set XLA_FLAGS before any import."""
    import repro.launch.dryrun as d

    with open(d.__file__) as f:
        lines = f.read().splitlines()
    assert lines[0] == "import os"
    assert lines[1] == 'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"'

"""Logical sharding rules: divisibility fallback, FSDP+TP, cache policy."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel import sharding as shd


@pytest.fixture(scope="module")
def mesh2x2():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (run under dryrun flags)")
    return jax.make_mesh((2, 2), ("data", "model"))


def test_logical_spec_basic():
    mesh = jax.make_mesh((1,), ("data",))
    spec = shd.logical_spec((8, 16), ("batch", None), mesh)
    assert spec == P("data", None)


def test_divisibility_fallback_replicates():
    mesh = jax.make_mesh((1,), ("data",))
    # batch=3 not divisible by data? data=1 divides everything;
    # simulate with a fake-rules axis that is absent from the mesh
    spec = shd.logical_spec((3, 4), ("heads", None), mesh)
    assert spec == P(None, None)  # "model" not in mesh -> replicated


def test_used_axis_not_reused():
    mesh = jax.make_mesh((1,), ("model",))
    spec = shd.logical_spec(
        (4, 4), ("heads", "ffn"), mesh
    )  # both map to model; second must fall back
    assert spec[0] == "model" and spec[1] is None


def test_lm_act_axes_without_context_is_local():
    assert shd.lm_act_axes(56) == ("batch", None, None)
    assert shd.attn_q_axes(56) == ("batch", None, "heads", None)


def test_fix_cache_axes_seq_fallback():
    from repro.configs import registry
    from repro.launch.steps import fix_cache_axes
    from repro.models import build_model

    mesh = jax.make_mesh((1,), ("model",))

    class FakeMesh:
        shape = {"model": 16}

    cfg = registry.get("command-r-35b")  # kv=8 < 16
    model = build_model(cfg)
    specs = model.cache_specs(8, 128)
    fixed = fix_cache_axes(specs, cfg, FakeMesh())
    for k, (shape, axes, _) in fixed.items():
        assert axes[2] == "seq_tp", (k, axes)  # seq-sharded cache
        assert "head_dim" not in axes

    cfg2 = registry.get("zamba2-2.7b")  # kv=32 divides 16
    model2 = build_model(cfg2)
    fixed2 = fix_cache_axes(model2.cache_specs(8, 128), cfg2, FakeMesh())
    assert fixed2["sa_k"][1][3] == "kv_heads"


def test_population_rule_exists():
    assert shd.LOGICAL_RULES["population"] == ("data",)


def test_island_rules_extend_population_rules():
    rules = shd.island_rules()
    assert rules["island"] == ("island",)
    assert rules["population"] == ("data",)
    # nothing inside a chromosome's training loop may be partitioned
    assert rules["batch"] is None and rules["embed"] is None
    assert shd.LOGICAL_RULES["island"] == ("island",)


def test_island_mesh_single_device_fallback():
    # 1 CPU device: cannot factor into 4 island groups -> (1, n) mesh;
    # the island axis degrades to replicated and IslandNSGA2 runs the
    # islands sequentially with identical semantics
    mesh = shd.island_mesh(4)
    assert mesh.axis_names == ("island", "data")
    assert dict(mesh.shape)["island"] == 1
    spec = shd.logical_spec(
        (4, 8), ("island", "population"), mesh, shd.island_rules()
    )
    assert spec == P("island", "data")  # both axes size 1 == replicated


def test_island_mesh_rejects_bad_island_count():
    with pytest.raises(ValueError):
        shd.island_mesh(0)


def test_mesh_builders_use_auto_axes():
    """Every mesh in src/ comes from shd.make_mesh, with Auto axis types."""
    from jax.sharding import AxisType

    from repro.launch import mesh as launch_mesh

    meshes = [
        shd.population_mesh(),
        shd.island_mesh(4),
        shd.island_mesh(1),
        launch_mesh.make_mesh((1, 1)),
        launch_mesh.make_mesh((1, 1, 1)),
    ]
    for m in meshes:
        assert m.axis_types == (AxisType.Auto,) * len(m.axis_names), m


def test_population_evaluator_same_rows_on_caller_explicit_mesh():
    """Rows a caller placed on its own default (Explicit-axes) mesh score
    exactly as host rows do on the evaluator's own population mesh."""
    import numpy as np
    from jax.sharding import AxisType, NamedSharding

    from repro.core import qat, trainer
    from repro.data import uci_synth

    X, y, spec = uci_synth.load("seeds")
    Xtr, ytr, Xte, yte = uci_synth.stratified_split(X, y)
    cfg = qat.MLPConfig((spec.n_features, spec.hidden, spec.n_classes))
    ev = trainer.make_population_evaluator(
        Xtr, ytr, Xte, yte, cfg, trainer.EvalConfig(max_steps=20, step_scale=0.2)
    )
    n = 4
    rng = np.random.default_rng(0)
    rows = [
        rng.uniform(size=(n, spec.n_features, 16)) < 0.7,
        np.full(n, 8.0, np.float32), np.full(n, 4.0, np.float32),
        np.asarray([16, 32, 64, 128], np.int32), np.full(n, 40, np.int32),
        np.full(n, 0.05, np.float32), np.arange(n, dtype=np.int32),
    ]
    acc_host = np.asarray(ev(*rows))

    caller_mesh = jax.make_mesh((1,), ("data",))
    assert caller_mesh.axis_types == (AxisType.Explicit,)
    placed = [
        jax.device_put(r, NamedSharding(caller_mesh, P("data", *[None] * (r.ndim - 1))))
        for r in rows
    ]
    np.testing.assert_array_equal(np.asarray(ev(*placed)), acc_host)

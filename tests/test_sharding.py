"""Sharding rules + multi-device lowering (subprocess with virtual devices)."""

import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


def _run_subprocess(code: str):
    """Run code under 8 virtual CPU devices (XLA_FLAGS must be set before
    jax import, so a subprocess is required)."""
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=560,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo")
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


def test_param_spec_rules():
    """Rule checks on a trivial 1x1 mesh (axis sizes 1 divide everything)."""
    from repro.launch.mesh import make_mesh
    from repro.sharding.specs import param_specs
    mesh = make_mesh((1, 1), ("data", "model"))
    params = {
        "layers": {
            "attn": {"wq": np.zeros((4, 8, 16)), "wo": np.zeros((4, 16, 8))},
            "mlp": {"w_up": np.zeros((4, 32, 16)),
                    "w_down": np.zeros((4, 16, 32))},
            "ln1": np.zeros((4, 16)),
            "moe": {"w_gate": np.zeros((4, 2, 32, 16))},
        },
        "embed": {"tok": np.zeros((128, 16))},
    }
    specs = param_specs(params, mesh)
    assert specs["layers"]["attn"]["wq"] == P(None, "model", "data")
    assert specs["layers"]["attn"]["wo"] == P(None, "data", "model")
    assert specs["layers"]["mlp"]["w_up"] == P(None, "model", "data")
    assert specs["layers"]["mlp"]["w_down"] == P(None, "data", "model")
    # stacked per-layer vector (L, D): default rule shards the stack dim
    assert specs["layers"]["ln1"] == P("data", None)
    assert specs["embed"]["tok"] == P("model", "data")
    # MoE experts: E=2 divides axis size 1 -> EP on expert dim
    assert specs["layers"]["moe"]["w_gate"][1] == "model"


def _abstract_mesh(**axes):
    """Rule tests only need mesh.shape/axis_names — AbstractMesh lets us
    exercise 8-way layouts without 8 devices."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(axes.values()), tuple(axes))


def test_param_specs_serving_tp_only():
    """serving=True keeps weights TP-sharded, replicated over data axes
    (decode re-reads weights every step — FSDP would force per-step
    gathers). Previously dead code; now the ServeEngine mesh path."""
    from repro.sharding.specs import param_specs
    mesh = _abstract_mesh(data=4, model=8)
    params = {
        "layers": {"attn": {"wq": np.zeros((4, 64, 32)),
                            "wo": np.zeros((4, 32, 64))},
                   "ln1": np.zeros((4, 32))},
        "embed": {"tok": np.zeros((512, 32))},
    }
    specs = param_specs(params, mesh, serving=True)
    assert specs["layers"]["attn"]["wq"] == P(None, "model", None)
    assert specs["layers"]["attn"]["wo"] == P(None, None, "model")
    assert specs["embed"]["tok"] == P("model", None)
    assert specs["layers"]["ln1"] == P(None, None)
    # no leaf references the data axes
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all("data" not in s and "pod" not in s for s in flat)
    # contrast: training specs do use the data axis
    train = param_specs(params, mesh)
    assert train["layers"]["attn"]["wq"] == P(None, "model", "data")


def test_param_specs_serving_qtensor_leaves():
    """QTensor payload inherits the weight rule; per-group scales inherit
    dims that still divide (the group axis usually does not)."""
    from repro.quant.quantize import quantize
    from repro.sharding.specs import param_specs
    mesh = _abstract_mesh(data=2, model=8)
    wq = quantize(np.float32(np.random.RandomState(0).randn(256, 256)),
                  "int8", group=128)
    specs = param_specs({"layers": {"attn": {"wq": wq}}}, mesh, serving=True)
    qspec = specs["layers"]["attn"]["wq"]
    assert qspec.data == P("model", None)
    # scale (256, 2): out dim inherits "model", 2 groups don't divide 8
    assert qspec.scale == P("model", None)


def test_specs_tolerate_mesh_without_model_axis():
    """Pure-DP serving mesh: no KeyError, everything model-wise replicated
    (regression: mesh.shape["model"] used to raise)."""
    from repro.sharding.specs import cache_specs, param_specs
    mesh = _abstract_mesh(data=8)
    params = {"layers": {"attn": {"wq": np.zeros((4, 64, 32)),
                                  "wo": np.zeros((4, 32, 64))},
                         "moe": {"w_gate": np.zeros((4, 8, 64, 32))}},
              "embed": {"tok": np.zeros((512, 32))}}
    specs = param_specs(params, mesh, serving=True)
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all(ax is None for s in flat for ax in s)
    cache = {"k": np.zeros((2, 8, 32, 4, 16)), "v": np.zeros((2, 8, 32, 4, 16))}
    cspecs = cache_specs(cache, mesh)
    assert cspecs["k"] == P(None, "data", None, None, None)


def test_activation_ctx_tolerates_mesh_without_model_axis():
    """activation_sharding / model_shards on a data-only mesh (regression:
    KeyError: 'model'). Runs on the real 1-device mesh."""
    from repro.launch.mesh import make_mesh
    from repro.sharding.ctx import (activation_sharding, constrain,
                                    data_shards, model_shards)
    mesh = make_mesh((1,), ("data",))
    with mesh, activation_sharding(mesh):
        assert model_shards() == 1
        assert data_shards() == 1
        x = jax.numpy.zeros((2, 4, 8))
        y = constrain(x, ("batch", None, "model"))
        assert y.shape == x.shape


def test_cache_specs_gqa_fallback():
    """KV-head sharding when heads divide the model axis; sequence-dim
    fallback when they don't (replicating a deep cache 8x is what blew
    decode memory in the baseline sweep); full replication when neither
    divides."""
    from repro.sharding.specs import cache_specs
    mesh = _abstract_mesh(data=1, model=8)

    def kv(h, s):
        z = np.zeros((2, 4, s, h, 16))
        return {"k": z, "v": z}

    head = cache_specs(kv(h=8, s=30), mesh)
    assert head["k"] == P(None, "data", None, "model", None)
    fallback = cache_specs(kv(h=2, s=32), mesh)
    assert fallback["k"] == P(None, "data", "model", None, None)
    assert fallback["v"] == fallback["k"]
    neither = cache_specs(kv(h=2, s=30), mesh)
    assert neither["k"] == P(None, "data", None, None, None)
    # SSM fields: conv (L,B,W-1,C) channels over model, state heads over model
    ssm = cache_specs({"conv": np.zeros((4, 2, 3, 64)),
                       "state": np.zeros((4, 2, 8, 16, 16))}, mesh)
    assert ssm["conv"] == P(None, "data", None, "model")
    assert ssm["state"] == P(None, "data", "model", None, None)


def test_small_mesh_train_lowering():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs.base import ShapeConfig, RunConfig
        from repro.configs.registry import get_config
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import step_for_shape
        from repro.launch.dryrun import input_shardings_for
        from repro.sharding.specs import to_shardings
        from repro.sharding.ctx import activation_sharding
        from repro.models.model import build
        from repro.launch.roofline import collective_bytes_from_hlo

        cfg = get_config("olmo-1b", smoke=True)
        model = build(cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = ShapeConfig("t", 128, 8, "train")
        fn, inputs = step_for_shape(model, shape, RunConfig(remat=False))
        sh = to_shardings(input_shardings_for(model, shape, inputs, mesh),
                          mesh)
        with mesh, activation_sharding(mesh):
            compiled = jax.jit(fn, in_shardings=sh).lower(*inputs).compile()
        coll = collective_bytes_from_hlo(compiled.as_text())
        assert coll["total"] > 0, "expected collectives on a 2x4 mesh"
        print("OK", int(coll["total"]))
    """)
    assert "OK" in out


def test_small_mesh_execution_matches_single_device():
    """Sharded loss == single-device loss (8 virtual devices, real exec)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ShapeConfig, RunConfig
        from repro.configs.registry import get_config
        from repro.launch.mesh import make_mesh
        from repro.data.synthetic import synthetic_batch
        from repro.models.model import build
        from repro.sharding.specs import param_specs, batch_specs, to_shardings
        from repro.sharding.ctx import activation_sharding
        from repro.train.step import make_loss_fn
        import dataclasses

        cfg = dataclasses.replace(get_config("llama3.2-3b", smoke=True),
                                  dtype="float32")
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = synthetic_batch(cfg, batch=8, seq=32, step=0)
        loss_fn = make_loss_fn(model, remat=False)
        ref = float(loss_fn(params, batch)[0])

        mesh = make_mesh((2, 4), ("data", "model"))
        sh_p = to_shardings(param_specs(params, mesh), mesh)
        sh_b = to_shardings(batch_specs(batch, mesh), mesh)
        with mesh, activation_sharding(mesh):
            sharded = jax.jit(lambda p, b: loss_fn(p, b)[0],
                              in_shardings=(sh_p, sh_b))(params, batch)
        got = float(sharded)
        assert abs(got - ref) < 1e-3, (got, ref)
        print("OK", got, ref)
    """)
    assert "OK" in out


def test_compressed_psum_matches_mean():
    """int8 EF gradient all-reduce approximates the true mean; error
    feedback keeps the bias bounded across steps."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_mesh
        from repro.optim.compress import compressed_psum_mean, init_error
        from jax import shard_map

        mesh = make_mesh((8,), ("data",))
        g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.1
        true_mean = jnp.mean(g_global, axis=0)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P("data", None), P("data", None)),
                 out_specs=(P("data", None), P("data", None)))
        def sync(g, e):
            m, e2 = compressed_psum_mean({"g": g}, {"g": e}, ("data",))
            return m["g"], e2["g"]

        err = jnp.zeros((8, 64))
        mean, err2 = sync(g_global, err)
        # every replica holds ~the mean
        got = np.asarray(mean)
        want = np.asarray(true_mean)
        rel = np.abs(got - want[None]).max() / (np.abs(want).max() + 1e-9)
        assert rel < 0.05, rel
        print("OK", rel)
    """)
    assert "OK" in out

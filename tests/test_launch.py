"""The serving launcher's no-training path, the compile-cache helper, and
the checks ``chip_smoke.py`` applies to what the launcher returns."""

import importlib.util
import pathlib
import types

import jax
import numpy as np
import pytest

from repro.launch import compile_cache, serve

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


STREAM = ["--smoke", "--train-steps", "0", "--kv-precision", "int8",
          "--prompt-len", "16", "--num-requests", "4", "--num-slots", "2",
          "--chunk", "4", "--max-new", "6"]


@pytest.fixture(scope="module")
def served():
    """One random-weight stream through the launcher, depth cut to 1."""
    return serve.main(["--arch", "yi-9b", "--num-layers", "1", "--fast",
                       "--variant", "4bit/8bit", *STREAM])


def test_random_init_stream_finishes(served):
    cfg = served["model"].cfg
    assert cfg.num_layers == 1 and cfg.d_model == 128   # widths untouched
    assert len(served["outputs"]) == len(served["requests"]) == 4
    want = {r.rid: r.max_new_tokens for r in served["requests"]}
    assert all(len(o.generated) == want[o.rid] for o in served["outputs"])
    assert served["plan"] is not None


def test_random_init_is_seeded():
    """The same seed gives the same weights; another seed does not."""
    from repro.configs.registry import get_config
    from repro.models.model import build
    model = build(get_config("llama3.2-3b", smoke=True))
    a, b, c = (jax.tree.leaves(serve.init_params(model, s))[0]
               for s in (0, 0, 1))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_random_init_on_a_mesh_lands_in_serving_shardings():
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.model import build
    from repro.sharding.specs import serving_param_shardings
    model = build(get_config("llama3.2-3b", smoke=True))
    mesh = make_mesh((1, 1), ("data", "model"))
    params = serve.init_params(model, 0, mesh)
    want = serving_param_shardings(params, mesh)
    for leaf, sh in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert leaf.sharding == sh


def test_num_layers_out_of_range_is_refused():
    with pytest.raises(SystemExit, match="--num-layers"):
        serve.main(["--arch", "yi-9b", "--num-layers", "99", *STREAM])


def test_teacher_forced_check_agrees_with_engine(served):
    check = _chip_smoke().teacher_forced_check(served)
    assert check["tokens"] == sum(len(o.generated)
                                  for o in served["outputs"])
    assert check["mean"] < 0.05 and check["max"] < 0.5


def _out(rid, generated, logprobs):
    return types.SimpleNamespace(rid=rid, generated=np.array(generated),
                                 logprobs=np.array(logprobs, np.float32))


def test_compare_runs_stops_at_first_divergence():
    """Steps after the first different greedy token continue another text
    and are not compared; the step where they part still is."""
    ref = [_out(0, [5, 6, 7], [-1.0, -2.0, -3.0]),
           _out(1, [1, 2], [-0.5, -0.5])]
    got = [_out(0, [5, 9, 8], [-1.0, -2.25, -9.0]),
           _out(1, [1, 2], [-0.5, -0.5])]
    check = _chip_smoke().compare_runs(got, ref)
    assert check["tokens"] == 2 + 2
    assert check["diverged"] == 1
    assert check["max"] == pytest.approx(0.25)


def test_compile_cache_follows_env_var(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_off_on_cpu_without_env_var(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"


def test_submesh_programs_keep_the_cache_off_cpu():
    was = jax.config.jax_enable_compilation_cache
    assert compile_cache.keep_submesh_programs_out() is False
    assert jax.config.jax_enable_compilation_cache == was


def test_submesh_programs_turn_the_cache_off_on_tpu(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    try:
        assert compile_cache.keep_submesh_programs_out() is True
        assert jax.config.jax_enable_compilation_cache is False
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()

"""Small cells for CPU rehearsals: the program's tiny test presets of the
two architectures, with mixes sized for a few seconds."""

import contextlib
import json
import pathlib
import types

import pytest

from bench.harness import spec, system

DATA = pathlib.Path(__file__).with_name("data")
PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}
LIMITS = {"missing": 0, "plan_mismatch": 0, "entropy_gap": 1e-5,
          "logit_gap": 0.05}


def load(name: str) -> dict:
    return json.loads((DATA / f"{name}.json").read_text())


def cell(config: str, mix: str, like: str) -> spec.Cell:
    """A smoke cell reporting the metrics of the real cell ``like``."""
    real = spec.load_cell(like)
    return spec.Cell(name=like, chips=1, config=load(config),
                     traffic=load(mix), limits=dict(LIMITS),
                     end_to_end=real.end_to_end, per_layer=real.per_layer)


def args(seed: int, seconds: float, trace: int = 0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)


def build_model(conf: dict):
    """The program's tiny test preset of ``conf``'s architecture."""
    from repro.configs.registry import get_config
    return system.model_for(get_config(conf["arch"], smoke=True), conf)


@contextlib.contextmanager
def presets():
    """The harness builds the tiny presets in place of the published
    sizes while this is open."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(system, "build_model", build_model)
        yield

"""Feed-forward blocks: SwiGLU (llama family) and GeLU (whisper)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.qmatmul.ops import fused_mlp
from repro.models.common import dense_init, qdot


def swiglu(p, x):
    # one Pallas launch on TPU (the (S, FF) hidden never reaches HBM);
    # bit-identical qdot sequence elsewhere — kernels/qmatmul/ops.fused_mlp
    return fused_mlp(x, p["w_gate"], p["w_up"], p["w_down"], act="swiglu")


def gelu_mlp(p, x):
    return fused_mlp(x, None, p["w_up"], p["w_down"], act="gelu")


@obs.scoped("mlp")
def mlp(p, x, act: str):
    return swiglu(p, x) if act == "swiglu" else gelu_mlp(p, x)


def init_mlp_params(key, d_model: int, d_ff: int, num_layers: int, dtype,
                    act: str = "swiglu"):
    ks = jax.random.split(key, 3)
    down_scale = 1.0 / np.sqrt(2 * max(num_layers, 1))
    if act == "swiglu":
        return {
            "w_gate": dense_init(ks[0], d_ff, d_model, dtype),
            "w_up": dense_init(ks[1], d_ff, d_model, dtype),
            "w_down": dense_init(ks[2], d_model, d_ff, dtype, scale=down_scale),
        }
    return {
        "w_up": dense_init(ks[0], d_ff, d_model, dtype),
        "w_down": dense_init(ks[1], d_model, d_ff, dtype, scale=down_scale),
    }

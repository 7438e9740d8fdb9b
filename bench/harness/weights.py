"""Random weights made by the benchmark, on the device, in one jitted call.

The program receives them as its raw (bfloat16) parameters and plans and
quantizes them itself; the plain reference is handed the same raw arrays,
made again from the same seed, and quantizes them by its own code. So the
reference takes nothing the program has made.

The tree follows the program's parameter layout (``abstract`` is its
shape tree); each leaf is filled by its name:

* ``tok`` (embedding table): N(0, 0.02);
* norm scales (``ln1``, ``ln2``, ``norm``): ones;
* matrices stored (out, in): N(0, 1/in), and ``w_down`` further scaled by
  1/sqrt(2 * num_layers).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "norm", "q_norm", "k_norm")


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def make(abstract, num_layers: int, seed: int, dtype=jnp.bfloat16):
    """Raw weights for the shape tree ``abstract`` from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def build(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, leaf) in zip(keys, flat):
            name = _leaf_name(path)
            if name in NORMS:
                out.append(jnp.ones(leaf.shape, dtype))
                continue
            std = 0.02 if name == "tok" else 1.0 / math.sqrt(leaf.shape[-1])
            if name == "w_down":
                std /= math.sqrt(2 * max(num_layers, 1))
            out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                        * std).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.PRNGKey(seed))

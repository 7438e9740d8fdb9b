"""How ``correct`` is decided: the program's plan and served tokens against
the plain reference, after the window, with the program's state freed.

Numbers compared, each against its limit from ``bench/limits/<cell>.json``:

* ``missing``: requests due in the window that never finished, or
  finished with another number of tokens than asked (limit 0);
* ``entropy_gap`` (full EWQ only): the largest |H_program - H_reference|
  over the plan's blocks, in nats;
* ``plan_mismatch``: blocks whose precision differs from the reference's
  decision, counting only blocks whose reference entropy lies further
  from the mean than the entropy limit (closer ones are ties that
  rounding decides; limit 0);
* ``logit_gap``: over a seeded sample of finished requests (the longest
  among them), the largest amount by which a served token's reference
  logit lies below the reference's best logit at that position.

The control (``Checker.control``) puts the reference in the program's
place one precision step lower (entropies in bfloat16, weights raw ->
int8 -> int4) and reads the same numbers, at the same served positions,
for the tokens the lower precision would put first.
"""

from __future__ import annotations

import importlib
import math

import numpy as np

BUCKET = 512
# what a gap that is not a finite number reads: a NaN would otherwise lose
# every comparison and pass as no gap at all
NOT_FINITE = 1e30


def sample(reqs: list, seed: int, min_tokens: int, max_requests: int) -> list:
    """Finished requests to compare: the one with the most tokens, then
    others drawn from ``seed`` until ``min_tokens`` served tokens."""
    done = sorted((r for r in reqs if r.tokens is not None),
                  key=lambda r: (-len(r.tokens), r.rid))
    if not done:
        return []
    rng = np.random.default_rng(seed)
    rest = [done[i] for i in rng.permutation(np.arange(1, len(done)))]
    out = [done[0]]
    served = len(done[0].tokens) - done[0].prompt_len
    for r in rest:
        if served >= min_tokens or len(out) >= max_requests:
            break
        out.append(r)
        served += len(r.tokens) - r.prompt_len
    return out


def _padded(tokens: np.ndarray) -> np.ndarray:
    t = len(tokens)
    n = int(math.ceil(t / BUCKET) * BUCKET)
    return np.concatenate([tokens, np.zeros(n - t, np.int32)])


def reference_module(conf: dict):
    return importlib.import_module(f"bench.reference.{conf['reference']}")


def plan_numbers(ref_plan: dict, prog: dict, entropy_limit: float) -> dict:
    out = {}
    clear = [True] * len(ref_plan["precisions"])
    if ref_plan["entropies"] is not None:
        h_ref, h_prog = ref_plan["entropies"], prog["entropies"]
        gaps = [abs(a - b) for a, b in zip(h_ref, h_prog)]
        out["entropy_gap"] = (max(gaps) if all(map(math.isfinite, gaps))
                              else NOT_FINITE)
        clear = [abs(h - ref_plan["mu"]) > entropy_limit for h in h_ref]
    out["plan_mismatch"] = sum(
        1 for a, b, c in zip(ref_plan["precisions"], prog["precisions"], clear)
        if c and a != b)
    return out


def logit_gaps(ref, samples: list, other=None) -> float:
    """Largest gap over the served positions of the sampled requests.
    Without ``other`` the token compared at each position is the served
    one; with ``other`` (a second model in the program's place, fed the
    same prompt and served tokens) it is the token ``other`` puts first."""
    import jax.numpy as jnp
    worst = 0.0
    for r in samples:
        toks = np.asarray(r.tokens, np.int32)
        t = len(toks)
        p = r.prompt_len
        padded = _padded(toks)
        lg = ref.logits(padded)[p - 1:t - 1]
        if other is None:
            pick = jnp.asarray(toks[p:])
        else:
            pick = jnp.argmax(other.logits(padded)[p - 1:t - 1], axis=-1)
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
        gap = float(jnp.max(best - got))
        worst = max(worst, gap if math.isfinite(gap) else NOT_FINITE)
    return worst


def decide(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in numbers)


class Checker:
    """The reference for one configuration, on raw weights made again
    after the program's state is freed."""

    def __init__(self, conf: dict, raw, limits: dict):
        self.mod = reference_module(conf)
        self.conf, self.raw, self.limits = conf, raw, limits
        self.plan = self.mod.plan(raw, conf)
        self.ref = self.mod.Reference(raw, conf, self.plan["precisions"])

    def program(self, prog_plan: dict, samples: list, missing: int) -> dict:
        """The numbers compared for the program's plan and served tokens."""
        out = {"missing": missing}
        out.update(plan_numbers(self.plan, prog_plan,
                                self.limits.get("entropy_gap", 0.0)))
        out["logit_gap"] = logit_gaps(self.ref, samples)
        return out

    def control(self, samples: list) -> dict:
        """The same numbers for the reference one precision step lower:
        its plan made with bfloat16 entropies, its weights one step below
        that plan's decisions."""
        import jax.numpy as jnp
        low = self.mod.plan(self.raw, self.conf, dtype=jnp.bfloat16)
        out = {"missing": 0}
        out.update(plan_numbers(self.plan, low,
                                self.limits.get("entropy_gap", 0.0)))
        lower = [self.mod.LOWER[p] for p in low["precisions"]]
        out["logit_gap"] = logit_gaps(
            self.ref, samples,
            other=self.mod.Reference(self.raw, self.conf, lower))
        return out

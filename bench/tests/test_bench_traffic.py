"""The seeded request generator."""

import json
import pathlib

import numpy as np
import pytest

from bench.harness import traffic as T

ROOT = pathlib.Path(__file__).resolve().parents[2]
MIXES = sorted((ROOT / "bench" / "traffic").glob("*.json")) + [
    ROOT / "bench" / "tests" / "data" / f"{m}.json"
    for m in ("smoke-backlog", "smoke-poisson")]


def _mix(path):
    return json.loads(pathlib.Path(path).read_text())


def _take(spec, seed, n, vocab=64000, seconds=40.0):
    gen = T.generate(spec, seed, vocab, seconds)
    return [next(gen) for _ in range(n)] if spec["arrival"] == "backlog" \
        else list(gen)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    spec = _mix(path)
    a, b = _take(spec, 2**31 + 17, 50), _take(spec, 2**31 + 17, 50)
    assert [(x.prompt.tolist(), x.max_new, x.offset_s) for x in a] == \
        [(x.prompt.tolist(), x.max_new, x.offset_s) for x in b]
    c = _take(spec, 2**31 + 18, 50)
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_only_declared_lengths(path):
    spec = _mix(path)
    items = _take(spec, 5, 200)
    declared = {int(p) for p, _ in spec["prompt_lengths"]}
    assert {len(x.prompt) for x in items} <= declared
    levels = set(T.output_levels(spec["output"]))
    assert {x.max_new for x in items} <= levels
    for x in items:
        assert len(x.prompt) + x.max_new <= spec["max_seq"]
    assert {(len(x.prompt), x.max_new) for x in items} <= set(T.shapes(spec))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_does_the_same_work(path):
    spec = _mix(path)
    n = 2 * spec.get("block", 40)      # whole blocks of a backlog mix
    a, b = _take(spec, 1, n), _take(spec, 99, n)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    if spec["arrival"] == "poisson":
        gaps = lambda xs: sorted(np.round(np.diff([x.offset_s for x in xs]
                                                  + [40.0]), 9))
        assert gaps(a) == gaps(b)


def test_prompts_share_no_first_token():
    spec = _mix(ROOT / "bench" / "traffic" / "offline-backlog.json")
    items = _take(spec, 3, 500, vocab=64000)
    firsts = [int(x.prompt[0]) for x in items]
    assert len(set(firsts)) == len(firsts)
    assert min(firsts) >= T.WARM_TOKENS
    warm = {int(T.warm_prompt(i, 8, 64000)[0]) for i in range(3)}
    assert not warm & set(firsts)


def test_poisson_due_times_are_wall_clock_offsets():
    spec = {"arrival": "poisson", "rate": 5.0, "slots": 4, "max_seq": 64,
            "chunk": 4, "prompt_lengths": [[8, 1.0]],
            "output": {"dist": "uniform", "min": 8, "max": 8, "step": 8}}
    items = _take(spec, 11, 0, vocab=512, seconds=40.0)
    assert len(items) == T.num_requests(spec, 40.0) == 200
    offs = np.array([x.offset_s for x in items])
    assert offs[0] == 0.0 and np.all(np.diff(offs) > 0) and offs[-1] < 40.0
    gaps = np.diff(np.append(offs, 40.0))
    assert gaps.mean() == pytest.approx(1 / 5.0, rel=1e-9)
    # exponential: the median gap is ln 2 of the mean
    assert np.median(gaps) == pytest.approx(np.log(2) / 5.0, rel=0.05)


def test_length_split_follows_weights():
    spec = _mix(ROOT / "bench" / "traffic" / "docqa.json")
    n = T.num_requests(spec, 40.0)
    items = _take(spec, 4, 0, seconds=40.0)
    counts = {p: sum(1 for x in items if len(x.prompt) == p)
              for p, _ in spec["prompt_lengths"]}
    for p, w in spec["prompt_lengths"]:
        assert abs(counts[p] - w * n) <= 1

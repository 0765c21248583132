"""The traffic generator: seeds, sizes and arrival times."""
import json
import pathlib

import numpy as np
import pytest

from bench import traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
MIXES = sorted((ROOT / "bench" / "traffic").glob("*.json"))


def load(p):
    return json.loads(p.read_text())


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests_other_seed_others(path):
    mix = load(path)
    a = traffic.generate(mix, 2**33 + 5, 12.0, 65024)
    b = traffic.generate(mix, 2**33 + 5, 12.0, 65024)
    c = traffic.generate(mix, 2**33 + 6, 12.0, 65024)
    assert [(r.due_s, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.due_s, r.max_new, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_offers_the_same_work_inside_the_window(path):
    mix = load(path)
    a = traffic.generate(mix, 1, 20.0, 151936)
    b = traffic.generate(mix, 2, 20.0, 151936)
    assert len(a) == len(b) == int(mix["rate_per_s"] * 20.0)
    # the same prompt tokens in all; each part of a prompt (system prompt,
    # user turn) comes from the same multiset of sizes
    assert sum(r.prompt.size for r in a) == sum(r.prompt.size for r in b)
    assert sorted(map(str, (r.prefix_id for r in a))) == \
        sorted(map(str, (r.prefix_id for r in b)))
    if "shared_prefix" not in mix:
        assert sorted(r.prompt.size for r in a) == \
            sorted(r.prompt.size for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    for reqs in (a, b):
        due = [r.due_s for r in reqs]
        assert due == sorted(due) and 0 < due[0] and due[-1] == \
            pytest.approx(20.0)
        assert all(r.prompt.size + r.max_new <= traffic.max_sequence(mix)
                   for r in reqs)
        assert all(0 <= r.prompt.min() and r.prompt.max() < 151936
                   for r in reqs)


def test_shared_prefixes_follow_zipf_and_prefix_the_prompt():
    mix = load(ROOT / "bench" / "traffic" / "persona.json")
    reqs = traffic.generate(mix, 9, 30.0, 151936)
    counts = np.bincount([r.prefix_id for r in reqs], minlength=16)
    assert counts[0] == counts.max() and counts[0] > 3 * counts[-1]
    by_id = {}
    for r in reqs:
        by_id.setdefault(r.prefix_id, []).append(r.prompt)
    for prompts in by_id.values():
        n = min(p.size for p in prompts) - mix["prompt"]["max"]
        assert all(np.array_equal(p[:n], prompts[0][:n]) for p in prompts)


def test_length_quantiles_hand_worked():
    spec = {"dist": "loguniform", "min": 100, "max": 10000}
    # quantiles 1/8, 3/8, 5/8, 7/8 of log-uniform(100, 10^4): 10^(2 + 2u)
    assert traffic.lengths(spec, traffic.grid(4)).tolist() == \
        [178, 562, 1778, 5623]
    spec = {"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 1,
            "max": 1000}
    # the median of the grid's middle, and a clipped tail
    assert traffic.lengths(spec, np.array([0.5, 0.999])).tolist() == \
        [100, 1000]


@pytest.mark.parametrize("n", [36, 112, 8, 5])
def test_balanced_order_holds_one_of_each_rank_class_per_run(n):
    values = np.arange(n) * 10
    rng = traffic.rng_for(7, 0)
    got = traffic.balanced(rng, values)
    assert sorted(got.tolist()) == values.tolist()
    klass = (got // 10) * traffic.BLOCK // n
    for b in range(0, n, traffic.BLOCK):
        run = klass[b:b + traffic.BLOCK].tolist()
        assert len(set(run)) == len(run)
    other = traffic.balanced(traffic.rng_for(8, 0), values)
    assert n < 8 or other.tolist() != got.tolist()


def test_the_first_requests_carry_the_same_work_for_every_seed():
    # a cell above its knee serves only the first requests of the window
    mix = load(ROOT / "bench" / "traffic" / "docs.json")
    first = [traffic.generate(mix, s, 45.0, 65024)[:24] for s in (1, 2, 3)]
    tot = [sum(r.prompt.size for r in f) for f in first]
    assert max(tot) - min(tot) < 0.1 * min(tot)
    out = [sum(r.max_new for r in f) for f in first]
    assert max(out) - min(out) < 0.1 * min(out)

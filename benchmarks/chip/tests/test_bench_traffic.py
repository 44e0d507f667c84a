"""The traffic generator: the same work for every seed."""

import hashlib
import json
import pathlib

import numpy as np
import pytest

import traffic

CHIP = pathlib.Path(__file__).resolve().parents[1]
MIXES = sorted((CHIP / "traffic").glob("*.json"))
RECORDED = json.loads((CHIP / "tests" / "data"
                       / "dense_before_families.json").read_text())["plans"]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_gets_the_same_sizes(path):
    mix = json.loads(path.read_text())
    a = traffic.plan(mix, 11, 1000, 40)
    b = traffic.plan(mix, 2**31 + 11, 1000, 40)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    if mix["loop"] == "open":
        # The same gaps in another order: the last arrival moves only by
        # the first gap, which is not waited for.
        assert a[-1].at == pytest.approx(b[-1].at, rel=0.05)
    fixed = mix.get("order") == "fixed"
    same_order = [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert same_order == fixed


def test_stratified_blocks_hold_one_value_per_stratum():
    values = np.arange(64)
    out = traffic._stratified(values, np.random.default_rng(3))
    assert sorted(out) == list(values)
    for b in range(4):
        block = out[16 * b:16 * (b + 1)]
        assert sorted(v // 4 for v in block) == list(range(16))


CLOSED = [p for p in MIXES if json.loads(p.read_text())["loop"] == "closed"]


@pytest.mark.parametrize("path", CLOSED, ids=lambda p: p.stem)
def test_a_closed_loop_plan_grows_by_rounds_of_the_same_sizes(path):
    """However fast its clients come back, a closed loop never runs out
    of requests: each new round is the same set in another order."""
    mix = json.loads(path.read_text())
    plan = traffic.plan(mix, 2**31 + 7, 1000, 40)
    n = len(plan)
    assert plan.upto(3 * n - 1).idx == 3 * n - 1 and len(plan) == 3 * n
    rounds = [[len(r.prompt) for r in plan[k * n:(k + 1) * n]]
              for k in range(3)]
    assert sorted(rounds[0]) == sorted(rounds[1]) == sorted(rounds[2])
    assert rounds[0] != rounds[1]


def _digest(reqs) -> str:
    h = hashlib.sha256()
    for r in reqs:
        h.update(repr((r.idx, r.tenant, r.priority, r.max_new,
                       float(r.at))).encode())
        h.update(np.asarray(r.prompt, np.int32).tobytes())
    return h.hexdigest()


def _plan(mix, seed):
    """A 40 s plan over a 151936-token vocabulary; a closed loop's grown
    to three rounds."""
    plan = traffic.plan(mix, seed, 151936, 40)
    if mix["loop"] == "closed":
        plan.upto(3 * len(plan) - 1)
    return plan


@pytest.mark.parametrize("key", sorted(RECORDED))
def test_plans_are_the_recorded_ones(key):
    """Each mix's plan, bit for bit, as the generator gave it before a
    mix could name its order's seed (``data/dense_before_families.json``)."""
    mix, seed = key.split("/")
    plan = _plan(json.loads((CHIP / "traffic" / f"{mix}.json").read_text()),
                 int(seed))
    assert _digest(plan) == RECORDED[key]


def test_an_order_seed_gives_its_own_fixed_order():
    """``order_seed`` 601 in a fixed-order mix: another order than the
    default's, the same for every seed, of the same sizes."""
    mix = json.loads((CHIP / "traffic" / "tenants-oversub.json").read_text())
    thrash = dict(mix, order_seed=601)
    sizes = [[(len(r.prompt), r.max_new) for r in _plan(m, seed)]
             for m in (mix, thrash) for seed in (11, 2**31 + 11)]
    assert sizes[0] == sizes[1] and sizes[2] == sizes[3]
    assert sizes[0] != sizes[2]
    for i in (0, 1):
        assert sorted(s[i] for s in sizes[0]) == sorted(s[i] for s in sizes[2])
    assert _digest(_plan(dict(mix, order_seed=0), 11)) \
        == _digest(_plan(mix, 11))

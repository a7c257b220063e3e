"""The generator's mixes: arrivals, recurring and new questions, and the
same work for every seed in another order."""
import collections
import json
import math

import numpy as np
import pytest

from bench import generator as G
from bench import spec as S

SYNTH = json.loads((S.BENCH / "configs" / "synth-micro.json").read_text())
BLAST = json.loads((S.BENCH / "configs" / "blast-sc1.json").read_text())
BLAST = dict(BLAST, **BLAST["rehearse"])


def mix(**kw):
    m = {"driver": "advisor", "arrivals": {"process": "closed"},
         "question": "split", "verify_top_k": 0, "size_jitter_kb": 1023}
    m.update(kw)
    return m


def take(gen, n):
    return [gen.next() for _ in range(n)]


def test_closed_loop_has_no_due_times():
    gen = G.Generator(SYNTH, mix(), 3, 10.0)
    assert all(d is None for d, _ in take(gen, 25))


def test_even_bursts():
    m = mix(arrivals={"process": "even", "rate_per_s": 4.0, "burst": 2})
    dues = [d for d, _ in take(G.Generator(SYNTH, m, 3, 1.5), 7)]
    assert dues == [0.0, 0.0, 0.5, 0.5, 1.0, 1.0, math.inf]


def test_poisson_gaps_are_one_set_per_seed():
    m = mix(arrivals={"process": "poisson", "rate_per_s": 2.0})
    runs = []
    for seed in (1, 2**31 + 5):
        dues = [d for d, _ in take(G.Generator(SYNTH, m, seed, 30.0), 61)]
        assert dues[-1] == math.inf and max(dues[:-1]) < 30.0
        gaps = [b - a for a, b in zip(dues, dues[1:-1])] + [30.0 - dues[-2]]
        assert sum(gaps) == pytest.approx(30.0)
        runs.append(gaps)
    assert runs[0] != runs[1]
    assert sorted(runs[0]) == pytest.approx(sorted(runs[1]))


@pytest.mark.parametrize("population", [0, 8])
def test_every_seed_gets_one_window_of_work(population):
    """An open loop's window: the same questions' classes (or recurring
    questions) for every seed, in another order."""
    m = mix(arrivals={"process": "even", "rate_per_s": 0.5},
            population=population, zipf_s=1.0)
    seen = []
    for seed in (2, 2**31 + 7):
        gen = G.Generator(SYNTH, m, seed, 51.0)
        reqs = [r for d, r in take(gen, 26)]
        assert len(gen.dues) == 26
        key = (lambda r: id(r)) if population else \
            (lambda r: (r.workflows[0]["name"], len(r.layouts[0]["client_hosts"])))
        seen.append([key(r) for r in reqs])
    if population:
        # recurring questions: the same weights, the k-th drawn as often
        counts = [sorted(collections.Counter(s).values()) for s in seen]
        assert counts[0] == counts[1]
    else:
        assert sorted(seen[0]) == sorted(seen[1])
    assert seen[0] != seen[1]


def test_new_questions_never_repeat():
    gen = G.Generator(SYNTH, mix(), 11, 10.0)
    keys = [json.dumps(r.workflows[0], sort_keys=True)
            for r in gen.warmup() + [r for _, r in take(gen, 40)]]
    assert len(set(keys)) == len(keys)


def test_recurring_questions_are_zipf_skewed():
    m = mix(population=8, zipf_s=1.0)
    counts = []
    for seed in (4, 2**31 + 9):
        gen = G.Generator(SYNTH, m, seed, 10.0)
        ids = collections.Counter(id(r) for _, r in take(gen, 4 * 8 * 10))
        assert len(ids) <= 8
        counts.append(sorted(ids.values(), reverse=True))
        # warm-up questions are none of the window's
        assert not {id(r) for r in gen.warmup()} & set(ids)
    assert counts[0] == counts[1]
    assert counts[0][0] >= 5 * counts[0][-1]


def test_apportion():
    assert G.apportion([1, 1, 1, 1], 10) == [3, 3, 2, 2]
    assert sum(G.apportion(1 / (1 + np.arange(8)), 25)) == 25


def test_a_new_grid_question_is_a_new_dag():
    """New questions of the grid kind: every request compiles anew."""
    m = mix(driver="sweep", question="grid", size_jitter_kb=200)
    reqs = [r for _, r in take(G.Generator(BLAST, m, 5, 10.0), 4)]
    dbs = {r.workflows[0]["preloaded"][0][1] for r in reqs}
    assert len(dbs) == 4
    assert all(len(r.layouts) == len(reqs[0].layouts) for r in reqs)


def test_what_if_draws_service_times_per_question():
    w = {"profiles": ["ramdisk", "hdd"], "net_scale": [0.5, 2.0],
         "storage_scale": [0.5, 2.0]}
    m = mix(question="grid", size_jitter_kb=0, what_if=w)
    sts = [r.st["storage"] for _, r in take(G.Generator(BLAST, m, 5, 10.0), 6)]
    assert len(set(sts)) == 6

"""The traffic generators: the same seed gives the same requests; every
seed gets the same set of sizes and gaps in another order; the sets
match the distributions the mixes declare."""
import math

import numpy as np
import pytest

from benchmarks.chip import sampling, spec

SEED = 2 ** 31 + 977            # larger than 32 signed bits hold
ENGINE = {"batch_slots": 8}
VOCAB = 1000


def _mix(name, seed, seconds=51.0):
    traffic = spec.load_json(spec.HERE / "traffic" / f"{name}.json")
    kind = spec.load_module(
        spec.HERE / "traffic" / "kinds" / f"{traffic['kind']}.py",
        traffic["kind"])
    return traffic, kind.make(traffic, seed=seed, vocab=VOCAB,
                              seconds=seconds, cell_params={"rate_hz": 2.0},
                              engine=ENGINE)


def _chat_sizes(mix):
    n = len(mix.due_s)
    reqs = [mix.request(k) for k in range(n)]
    return ([len(r.prompt) for r in reqs],
            [r.max_new_tokens for r in reqs], reqs)


@pytest.mark.parametrize("name", ["chat", "longctx"])
def test_same_seed_same_requests(name):
    _, a = _mix(name, SEED)
    _, b = _mix(name, SEED)
    for k in range(40):
        ra, rb = a.request(k), b.request(k)
        assert np.array_equal(ra.prompt, rb.prompt)
        assert ra.max_new_tokens == rb.max_new_tokens
        assert ra.temperature == rb.temperature
    if a.due_s is not None:
        assert np.array_equal(a.due_s, b.due_s)


def test_chat_seeds_share_sizes_in_their_own_order():
    _, a = _mix("chat", SEED)
    _, b = _mix("chat", SEED + 1)
    pa, oa, ra = _chat_sizes(a)
    pb, ob, rb = _chat_sizes(b)
    assert sorted(pa) == sorted(pb) and sorted(oa) == sorted(ob)
    assert pa != pb and oa != ob
    ga, gb = np.diff(a.due_s, prepend=0), np.diff(b.due_s, prepend=0)
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    assert sum(r.temperature == 0.0 for r in ra) == \
        sum(r.temperature == 0.0 for r in rb)
    assert not np.array_equal(ra[0].prompt[:4], rb[0].prompt[:4])


@pytest.mark.parametrize("seed", [SEED, 7, 2 ** 33 + 1])
def test_chat_blocks_hold_one_length_of_each_stratum(seed):
    """Every block of requests holds one prompt from each eighth of the
    set of prompt lengths, so the longest never bunch."""
    from benchmarks.chip.traffic.kinds import poisson
    _, mix = _mix("chat", seed)
    plens, olens, _ = _chat_sizes(mix)
    n, B = len(plens), poisson.BLOCK
    assert n % B == 0
    ranks = np.argsort(np.argsort(plens, kind="stable"), kind="stable")
    strata = (ranks // (n // B)).reshape(-1, B)
    assert all(sorted(row) == list(range(B)) for row in strata)
    top = sorted(olens)[-(n // B):]
    assert all(max(olens[i:i + B]) >= top[0] for i in range(0, n, B))


def test_chat_matches_its_declared_distributions():
    traffic, mix = _mix("chat", SEED, seconds=400.0)
    plens, olens, reqs = _chat_sizes(mix)
    n = len(plens)
    assert n == -(-(math.ceil(2.0 * 400.0 * 1.1) + 2) // 8) * 8
    p, o = traffic["prompt"], traffic["output"]
    assert np.median(plens) == pytest.approx(p["median"], rel=0.02)
    assert np.median(olens) == pytest.approx(o["median"], rel=0.03)
    assert min(plens) >= p["min"] and max(plens) <= p["max"]
    assert min(olens) >= o["min"] and max(olens) <= o["max"]
    # lognormal: the log-lengths' spread between the quartiles is
    # 2 x 0.6745 sigma (clipping touches only the tails)
    q1, q3 = np.percentile(np.log(plens), [25, 75])
    assert q3 - q1 == pytest.approx(2 * 0.6745 * p["sigma"], rel=0.03)
    # Poisson arrivals: exponential gaps with mean 1/rate, CV 1
    gaps = np.diff(mix.due_s, prepend=0)
    assert gaps.mean() == pytest.approx(0.5, rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.05)
    greedy = sum(r.temperature == 0.0 for r in reqs)
    assert greedy == round(traffic["greedy_share"] * n)
    assert all(r.shared_prefix == 0 for r in reqs)


def test_longctx_documents_questions_and_outputs():
    traffic, mix = _mix("longctx", SEED)
    m = traffic["cycle"]
    reqs = [mix.request(k) for k in range(2 * m)]
    docs = {tuple(r.prompt[:r.shared_prefix]) for r in reqs}
    assert len(docs) == traffic["docs"]
    lo, hi = traffic["doc_len"]
    for d in docs:
        assert lo <= len(d) <= hi + 1 and len(d) % 2 == 1
    qlo, qhi = traffic["question_len"]
    qlens = [len(r.prompt) - r.shared_prefix for r in reqs[:m]]
    assert min(qlens) >= qlo and max(qlens) <= qhi
    o = traffic["output"]
    olens = [r.max_new_tokens for r in reqs[:m]]
    assert np.median(olens) == pytest.approx(o["median"], rel=0.05)
    assert min(olens) >= o["min"] and max(olens) <= o["max"]
    # the cycle repeats sizes, never a question
    assert reqs[0].max_new_tokens == reqs[m].max_new_tokens
    assert not np.array_equal(reqs[0].prompt, reqs[m].prompt)
    assert mix.backlog == ENGINE["batch_slots"]
    # set-up: each document once, then one that attaches a document
    assert [len(r.prompt) > r.shared_prefix for r in mix.setup] == \
        [True] * (traffic["docs"] + 1)
    assert mix.setup[-1].shared_prefix > 0


def test_longctx_seeds_share_sizes():
    _, a = _mix("longctx", SEED)
    _, b = _mix("longctx", SEED + 5)
    sa = sorted((len(a.request(k).prompt), a.request(k).max_new_tokens)
                for k in range(64))
    sb = sorted((len(b.request(k).prompt), b.request(k).max_new_tokens)
                for k in range(64))
    assert sorted(o for _, o in sa) == sorted(o for _, o in sb)
    n = spec.load_json(spec.HERE / "traffic" / "longctx.json")["docs"]
    assert sorted(len(d.prompt) for d in a.setup[:n]) == \
        sorted(len(d.prompt) for d in b.setup[:n])


def test_stratified_sets():
    v = sampling.lognormal_set(1001, 96, 1.0, 1, 10 ** 9)
    assert np.median(v) == 96
    g = sampling.exponential_set(10000, 4.0)
    assert g.mean() == pytest.approx(0.25, rel=0.01)
    v = np.arange(24)
    a = sampling.stratified(v, SEED, 1, 4)
    assert sorted(a) == list(v)
    assert all(sorted(x // 6) == [0, 1, 2, 3] for x in a.reshape(-1, 4))
    assert np.array_equal(a, sampling.stratified(v, SEED, 1, 4))
    assert not np.array_equal(a, sampling.stratified(v, SEED + 1, 1, 4))
    with pytest.raises(ValueError):
        sampling.stratified(np.arange(10), SEED, 1, 4)
    assert sampling.odd(10) == 11 and sampling.odd(11) == 11

"""Speculative decoding: exactness, rollback, termination, metrics.

The load-bearing property: the emitted token stream is **bit-identical**
to PR 3's per-token decode — at any temperature, on fp and int8 paged
caches — because acceptance compares a draft against the token the
deterministic sampler would emit from the verified logits.  Drafters can
only change how many jitted steps the stream takes, never its content.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models import get_model
from repro.serve import (FixedDrafter, NgramDrafter, Request,
                         ServingEngine, derive_kv_spec)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-1.5b", reduced=True)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def int8_spec(setup):
    cfg, model, params = setup
    return derive_kv_spec(model, params)


def _mixed_requests(cfg, temperature=0.0):
    """Mixed queue: repetitive prompts (drafter accepts) + random ones
    (drafter mostly rejects), varying lengths and budgets."""
    rng = np.random.default_rng(3)
    pat = rng.integers(0, cfg.vocab, size=(4,)).astype(np.int32)
    prompts = [np.tile(pat, 3),
               rng.integers(0, cfg.vocab, size=(5,)).astype(np.int32),
               np.tile(pat, 2),
               rng.integers(0, cfg.vocab, size=(9,)).astype(np.int32),
               np.tile(rng.integers(0, cfg.vocab, size=(3,)), 4),
               rng.integers(0, cfg.vocab, size=(2,)).astype(np.int32)]
    budgets = (10, 7, 8, 5, 9, 6)
    return [Request(prompt=p.copy(), max_new_tokens=m,
                    temperature=temperature)
            for p, m in zip(prompts, budgets)]


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------

def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter(max_ngram=3)
    # suffix [1, 2] reoccurs at the start; what followed is [3, 1]
    assert d.propose([1, 2, 3, 1, 2], k=2) == [3, 1]
    # longest suffix wins: [2, 3] matched over plain [3]
    assert d.propose([1, 2, 3, 9, 2, 3], k=1) == [9]
    # no history → nothing proposed
    assert d.propose([7], k=4) == []
    assert d.propose([1, 2, 3], k=0) == []
    with pytest.raises(ValueError):
        NgramDrafter(max_ngram=0)


def test_drafter_registry():
    from repro.serve import get_drafter
    assert isinstance(get_drafter("ngram"), NgramDrafter)
    with pytest.raises(ValueError, match="unknown drafter"):
        get_drafter("tiny-model")


# ---------------------------------------------------------------------------
# exactness: speculative == per-token, greedy and sampled, fp and int8
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_spec_greedy_identical_with_midstream_arrivals(setup, int8_spec, kv):
    """Queue deeper than the slot count, mixed repetitive/random prompts,
    requests arriving mid-stream: greedy speculative output must be
    bit-identical to the per-token engine on both cache dtypes."""
    cfg, model, params = setup
    spec = int8_spec if kv == "int8" else "fp"

    def serve(**kw):
        eng = ServingEngine(model, params, batch_slots=2, max_seq=64,
                            kv_cache=spec, **kw)
        reqs = _mixed_requests(cfg)
        handles = [eng.submit(r) for r in reqs[:4]]
        for _ in range(3):
            eng.step()                      # mid-stream...
        handles += [eng.submit(r) for r in reqs[4:]]   # ...late arrivals
        eng.run()
        return [eng.scheduler.outputs[h] for h in handles], eng

    base, _ = serve()
    outs, eng = serve(spec_decode="ngram", spec_k=4)
    assert outs == base
    m = eng.metrics.summary()
    assert m["spec_proposed"] > 0
    assert m["spec_accepted"] > 0, "repetitive prompts must accept"
    # speculation actually saved jitted steps on this workload
    assert m["tokens_per_decode_step"] > 1.0


def test_spec_sampled_identical(setup):
    """Deterministic sampling makes verification exact at temperature:
    the sampled stream (not just greedy) is bit-identical."""
    cfg, model, params = setup
    reqs = lambda: _mixed_requests(cfg, temperature=30.0)[:4]
    base = ServingEngine(model, params, batch_slots=2, max_seq=64,
                         seed=7).generate(reqs())
    outs = ServingEngine(model, params, batch_slots=2, max_seq=64,
                         seed=7, spec_decode="ngram",
                         spec_k=3).generate(reqs())
    assert outs == base
    assert any(len(set(o)) > 1 for o in base), "temperature visible"


def test_spec_under_page_pressure(setup):
    """A pool too small for full verify windows: proposals are dropped
    (never preempting a victim just to speculate) and, when the pool is
    dry outright, the newest request is preempted and replayed — output
    still bit-identical to the per-token engine."""
    cfg, model, params = setup
    rng = np.random.default_rng(11)
    pat = rng.integers(0, cfg.vocab, size=(3,))
    reqs = lambda: [Request(prompt=np.tile(pat, 3), max_new_tokens=10),
                    Request(prompt=np.tile(pat, 2), max_new_tokens=10)]
    kw = dict(batch_slots=2, max_seq=24, page_size=4, num_pages=7)
    base = ServingEngine(model, params, **kw).generate(reqs())
    eng = ServingEngine(model, params, spec_decode="ngram", spec_k=4, **kw)
    outs = eng.generate(reqs())
    assert outs == base
    assert eng.cache.used_pages == 0


# ---------------------------------------------------------------------------
# rollback
# ---------------------------------------------------------------------------

def test_rejected_window_leaves_cache_state_exact(setup):
    """Write-then-reject: a speculative window scattered into the page
    pool and rolled back must leave the next decode's logits bit-equal,
    and must not churn the page pool (reserved pages stay owned)."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, batch_slots=2, max_seq=64)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab, size=(6,))
    eng.submit(Request(prompt=prompt, max_new_tokens=8))
    eng.step()                              # prefill + one decode step
    st = eng.scheduler.slots[0]
    B, L = eng.B, st.length

    def probe_logits():
        toks = np.zeros((B, 1), np.int32)
        toks[0, 0] = st.entry.seq[-1]
        lens = np.zeros((B,), np.int32)
        lens[0] = L
        logits, _pages = eng._decode_fn(    # discard pages: no commit
            eng.params, jnp.asarray(toks), eng.cache.pages,
            eng.cache.device_table(), jnp.asarray(lens))
        return np.asarray(logits[0, 0].astype(jnp.float32))

    before = probe_logits()
    # speculative window of garbage tokens at [L, L+4), then reject all
    assert eng.cache.reserve(0, L + 4)
    free_after_reserve = len(eng.cache.free)
    toks = np.zeros((B, 4), np.int32)
    toks[0] = (np.asarray(st.entry.seq[-1]) + np.arange(4) + 1) % cfg.vocab
    lens = np.zeros((B,), np.int32)
    lens[0] = L
    _, pages = eng._verify_fn(eng.params, jnp.asarray(toks),
                              eng.cache.pages, eng.cache.device_table(),
                              jnp.asarray(lens))
    eng.cache.pages = pages                 # garbage committed to pool...
    eng.cache.rollback(0, L)                # ...then rolled back
    assert len(eng.cache.free) == free_after_reserve, "no pool churn"
    after = probe_logits()
    np.testing.assert_array_equal(before, after)
    eng.run()                               # engine still completes


# ---------------------------------------------------------------------------
# termination inside the window
# ---------------------------------------------------------------------------

class _OracleDrafter(FixedDrafter):
    """Proposes the exact continuation stream — guarantees every draft
    is accepted, pinning EOS inside an accepted window."""

    def __init__(self, prompt_len: int, stream):
        super().__init__(stream)
        self.prompt_len = prompt_len

    def propose(self, seq, k, request_id=0):
        n_gen = len(seq) - self.prompt_len
        return self.tokens[n_gen:n_gen + k]


def test_eos_inside_accepted_window_terminates_and_frees(setup):
    """EOS accepted mid-window ends the request right there: later
    emitted tokens are discarded, the slot and its pages free.

    Greedy random-weight streams collapse to a constant token (EOS would
    land on the prefill-emitted index 0), so this uses a temperature
    stream — still exact under speculative decoding — with an oracle
    drafter so the EOS position is provably an accepted draft."""
    cfg, model, params = setup
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, size=(6,))
    mk = lambda eos=None: [Request(prompt=prompt, max_new_tokens=10,
                                   temperature=25.0, eos_id=eos)]
    base = ServingEngine(model, params, batch_slots=1, max_seq=64,
                         seed=7).generate(mk())[0]
    # an eos first emitted at a draft position of the first verify
    # window: window indices 1..4 are drafts, 5 is the bonus token
    idx, eos = next((i, t) for i, t in enumerate(base)
                    if 1 <= i <= 4 and t not in base[:i])
    eng = ServingEngine(
        model, params, batch_slots=1, max_seq=64, seed=7,
        spec_decode=_OracleDrafter(len(prompt), base), spec_k=4)
    outs = eng.generate(mk(eos))
    assert outs[0] == base[:idx + 1]        # stopped at EOS, EOS included
    assert eng.metrics.spec_accepted >= idx, "EOS was an accepted draft"
    assert eng.cache.used_pages == 0        # pages freed
    assert eng.scheduler.active_slots() == []
    assert not eng.scheduler.has_work()


def test_zero_proposals_degrade_to_per_token_path(setup):
    """A drafter that proposes nothing must reproduce PR 3 exactly —
    same tokens from the same number of T=1 decode steps, no spec
    metrics recorded."""
    cfg, model, params = setup
    reqs = lambda: _mixed_requests(cfg)[:3]
    base_eng = ServingEngine(model, params, batch_slots=2, max_seq=64)
    base = base_eng.generate(reqs())
    eng = ServingEngine(model, params, batch_slots=2, max_seq=64,
                        spec_decode=FixedDrafter([]), spec_k=4)
    outs = eng.generate(reqs())
    assert outs == base
    m = eng.metrics.summary()
    assert m["spec_steps"] == 0 and m["spec_proposed"] == 0
    assert m["decode_steps"] == base_eng.metrics.summary()["decode_steps"]
    assert m["tokens_per_decode_step"] == 1.0


# ---------------------------------------------------------------------------
# metrics + guards
# ---------------------------------------------------------------------------

def test_acceptance_metrics_sanity(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(13)
    pat = rng.integers(0, cfg.vocab, size=(4,))
    eng = ServingEngine(model, params, batch_slots=2, max_seq=64,
                        spec_decode="ngram", spec_k=3)
    outs = eng.generate([Request(prompt=np.tile(pat, 3), max_new_tokens=9),
                         Request(prompt=np.tile(pat, 2), max_new_tokens=7)])
    m = eng.metrics.summary()
    assert m["total_tokens"] == sum(len(o) for o in outs) == 16
    assert m["spec_steps"] >= 1
    assert m["spec_accepted"] <= m["spec_proposed"]
    assert 0.0 <= m["acceptance_rate"] <= 1.0
    assert 1.0 <= m["tokens_per_decode_step"] <= 1.0 + eng.spec_k
    # non-speculative engines report the metrics as nan, not garbage
    plain = ServingEngine(model, params, batch_slots=1, max_seq=32)
    plain.generate([Request(prompt=pat, max_new_tokens=2)])
    s = plain.metrics.summary()
    assert s["spec_steps"] == 0 and np.isnan(s["acceptance_rate"])
    assert s["tokens_per_decode_step"] == 1.0


def test_spec_decode_guards(setup):
    cfg, model, params = setup
    with pytest.raises(NotImplementedError, match="paged"):
        ServingEngine(model, params, batch_slots=1, max_seq=32,
                      mode="static", spec_decode="ngram")
    with pytest.raises(ValueError, match="spec_k"):
        ServingEngine(model, params, batch_slots=1, max_seq=32,
                      spec_decode="ngram", spec_k=0)

"""The plain reference against the program's paged path at a size the
CPU holds: chunked prefill and batched decode through
``Model.decode_paged`` give the reference's logits; the float8 control,
decoding in the program's place, is not correct by ``check.judge``."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import check, spec, weights
from benchmarks.chip.reference import dense_gqa

import bench_tiny

#: the tiny configuration as a cell of the committed benchmark would
#: see it: its kind's module is found under the benchmark's own base
CELL = types.SimpleNamespace(config=bench_tiny.CONFIG, base=spec.HERE)
KIND = spec.arch_kind(CELL)

PAGE, CHUNK = 8, 8


@pytest.fixture(scope="module")
def served():
    """Two prompts prefilled in chunks into a shared page pool, then
    decoded together greedily; logits of every served position."""
    from repro.models import get_model
    from repro.serve import KVCacheSpec, PagedKVCache

    cfg = bench_tiny.CONFIG
    arch = KIND.arch_config(cfg)
    model = get_model(arch)
    w = weights.make(KIND, cfg, arch.vocab_padded, 2 ** 31 + 7, arch.dtype)
    params = KIND.to_program(w, model)
    cache = PagedKVCache(arch, KVCacheSpec.all_fp(arch.n_layers), 2, 160,
                         page_size=PAGE)
    step = jax.jit(lambda p, t, pg, tb, ln: model.decode_paged(
        p, t, pg, tb, ln, page_size=PAGE))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, arch.vocab, n) for n in (61, 45)]
    V = arch.vocab
    rows = [[], []]
    last = []
    for s, prompt in enumerate(prompts):
        assert cache.grow(s, len(prompt) + 48)
        table = cache.device_table()[s:s + 1]
        for start in range(0, len(prompt), CHUNK):
            toks = np.zeros((1, CHUNK), np.int32)
            part = prompt[start:start + CHUNK]
            toks[0, :len(part)] = part
            logits, cache.pages = step(params, jnp.asarray(toks),
                                       cache.pages, table,
                                       jnp.full((1,), start, jnp.int32))
        rows[s].append(np.asarray(logits)[0, len(part) - 1, :V])
        last.append(int(rows[s][-1].argmax()))
    out = [[t] for t in last]
    lens = np.array([len(p) for p in prompts], np.int32)
    for _ in range(40):
        logits, cache.pages = step(params, jnp.asarray(last)[:, None],
                                   cache.pages, cache.device_table(),
                                   jnp.array(lens))
        lens += 1
        for s in range(2):
            rows[s].append(np.asarray(logits)[s, 0, :V])
        last = [int(np.asarray(logits)[s, 0, :V].argmax()) for s in range(2)]
        for s in range(2):
            out[s].append(last[s])
    return cfg, w, prompts, out, [np.stack(r) for r in rows]


def test_reference_logits_match_the_paged_path(served):
    cfg, w, prompts, out, rows = served
    for prompt, toks, got in zip(prompts, out, rows):
        seq = np.concatenate([prompt, toks[:-1]])
        want = KIND.logits_at(cfg, w, seq,
                              len(prompt) - 1 + np.arange(len(toks)))
        scale = np.abs(want).max()
        assert np.abs(got - want).max() <= 1e-4 * scale


@dataclasses.dataclass
class _R:
    prompt: np.ndarray
    tokens: list
    temperature: float = 0.0
    in_window: bool = True
    finished: bool = True

    @property
    def max_new_tokens(self):
        return len(self.tokens)


def _judge(cfg, w, reqs):
    cell = types.SimpleNamespace(
        config=cfg, base=spec.HERE,
        params={"check": {"max_logit_gap": bench_tiny.LIMIT}})
    return check.judge(types.SimpleNamespace(cell=cell, requests=reqs), w,
                       seed=5)


def _decode(cfg, w, prompt, n, mode):
    """``n`` tokens decoded greedily by the reference itself, one full
    forward per token."""
    seq = list(prompt)
    for _ in range(n):
        z = KIND.logits_at(cfg, w, np.array(seq), [len(seq) - 1],
                           mode=mode)[0]
        seq.append(int(z.argmax()))
    return seq[len(prompt):]


def test_sound_tokens_pass_and_the_control_fails(served):
    cfg, w, prompts, out, _ = served
    sound = [check.served_gaps(CELL, w, _R(p, t)).max()
             for p, t in zip(prompts, out)]
    assert max(sound) <= bench_tiny.LIMIT
    control = [check.control_gaps(CELL, w, _R(p, t)).max()
               for p, t in zip(prompts, out)]
    assert max(control) > 3 * bench_tiny.LIMIT


def test_the_control_in_the_programs_place_is_not_correct(served):
    """The float8 reference decodes the same prompts greedily, and its
    tokens go through the harness's own comparison as the served ones."""
    cfg, w, prompts, out, _ = served
    reqs = [_R(p, _decode(cfg, w, p, len(t), mode="fp8"))
            for p, t in zip(prompts, out)]
    assert any(r.tokens != t for r, t in zip(reqs, out))
    verdict = _judge(cfg, w, reqs)
    assert verdict["correct"] is False
    gap = verdict["numbers"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    sound = _judge(cfg, w, [_R(p, t) for p, t in zip(prompts, out)])
    assert sound["correct"] is True


def test_sampled_requests_are_left_out_of_the_sample(served):
    cfg, w, prompts, out, _ = served
    reqs = [_R(p, t, temperature=tmp)
            for p, t, tmp in zip(prompts, out, (0.0, 0.8))]
    picked = check.sample(reqs, seed=5)
    assert picked == reqs[:1]
    reqs[1].tokens = [(t + 1) % bench_tiny.MODEL["vocab_size"]
                      for t in reqs[1].tokens]
    assert _judge(cfg, w, reqs)["correct"] is True


def test_bucket():
    assert dense_gqa.bucket(1) == 512
    assert dense_gqa.bucket(513) == 1024
    assert dense_gqa.bucket(3, 64) == 64

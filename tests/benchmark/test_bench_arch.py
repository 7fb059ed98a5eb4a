"""Architecture kinds (``arch/<kind>.py``): found by the configuration's
``kind``, drawing the same weights and giving the same reference as
before the kind was looked up by name, and enough, as new files alone,
for a benchmark to run a configuration of another architecture."""
import hashlib
import json
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness, spec, weights

import bench_tiny

SEED = 2 ** 31 + 7

#: sha256 of the seeded weights (name, shape, dtype and bytes of every
#: array, by sorted name), recorded when ``weights.py`` drew the dense
#: tensors itself: the tiny configuration at its own size and type, and
#: the published qwen2 and granite shape sets at 2 layers and 512 rows
#: of the vocabulary, in bfloat16
WEIGHT_DIGESTS = {
    "tiny": "725ff8070292f13c4401e6a34ff6fb93ff04cec423977ad52bc7c4c78696e218",
    "qwen2-1.5b":
        "0f6434918d55f35357539da2db0483863741a5df0536536b5e6ba161fae36e5f",
    "granite-3-2b":
        "caefd6bde43ecbf583f3a12bf3f876ae9d2b5c5c0b9ce8f2682549390134a497",
}
#: sha256 of the dense reference's float32 logits on the tiny
#: configuration (rows 60-76 of a 77-token sequence), recorded when
#: ``check.py`` called the reference by its module's name; float32 sums
#: depend on how XLA's CPU client splits them, and these are a
#: multi-core host's
LOGIT_DIGESTS = {
    "f32": "5ce14a08cd3c1972a6bb1c4191ca82496f8b8b0c5801c24e4abeaaa70212cf07",
    "fp8": "bb8a35f3ece1531079471509f9b135783e0004c73e683794d20bab0390d9bbe3",
}


def _kind(config, base=spec.HERE):
    return spec.arch_kind(types.SimpleNamespace(config=config, base=base))


def _digest(w) -> str:
    h = hashlib.sha256()
    for n in sorted(w):
        a = np.asarray(w[n])
        h.update(f"{n}{a.shape}{a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _tiny_weights():
    cfg = bench_tiny.CONFIG
    kind = _kind(cfg)
    arch = kind.arch_config(cfg)
    return kind, weights.make(kind, cfg, arch.vocab_padded, SEED, arch.dtype)


@pytest.mark.parametrize("name", sorted(WEIGHT_DIGESTS))
def test_seeded_weights_are_bit_identical(name):
    if name == "tiny":
        _, w = _tiny_weights()
    else:
        cfg = spec.load_json(spec.HERE / "configs" / f"{name}.json")
        cfg["model"] = dict(cfg["model"], num_hidden_layers=2)
        w = weights.make(_kind(cfg), cfg, 512, SEED, jnp.bfloat16)
    assert _digest(w) == WEIGHT_DIGESTS[name]


@pytest.mark.parametrize("mode", sorted(LOGIT_DIGESTS))
def test_reference_logits_are_bit_identical(mode):
    kind, w = _tiny_weights()
    seq = np.random.default_rng(11).integers(0, 256, 77)
    z = kind.logits_at(bench_tiny.CONFIG, w, seq, np.arange(60, 77),
                       mode=mode)
    assert z.shape == (17, 256) and z.dtype == np.float32
    assert hashlib.sha256(z.tobytes()).hexdigest() == LOGIT_DIGESTS[mode]


#: what the copy appends to ``dense_gqa.py``: every function a kind
#: gives writes its name to ``toy_gqa.calls`` beside the module when it
#: is called
_MARKS = '''

def _marked(f):
    import functools
    from pathlib import Path

    @functools.wraps(f)
    def g(*args, **kwargs):
        with open(Path(__file__).with_name("toy_gqa.calls"), "a") as out:
            out.write(f.__name__ + "\\n")
        return f(*args, **kwargs)
    return g


for _name in ("arch_config", "shapes", "std", "to_program", "logits_at",
              "token_flops", "prefill_flops", "decode_step_bytes"):
    globals()[_name] = _marked(globals()[_name])
'''


def _committed_files():
    return {str(p.relative_to(spec.HERE)): p.read_bytes()
            for p in sorted(spec.HERE.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_architecture_joins_by_files_alone(tmp_path):
    """A kind written as a new file, named by a new configuration, runs a
    chat cell to ``correct`` through its own weights, program tree and
    reference; nothing committed under the benchmark is edited."""
    before = _committed_files()
    bench = bench_tiny.build(tmp_path)
    dense = tmp_path / "arch" / "dense_gqa.py"
    (tmp_path / "arch" / "toy_gqa.py").write_text(dense.read_text() + _MARKS)
    dense.unlink()
    cfg = dict(bench_tiny.CONFIG, kind="toy_gqa")
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(cfg))
    cell = bench_tiny.cell(tmp_path, "tiny.chat", bench)
    assert cell.config["kind"] == "toy_gqa"
    res = harness.run_cell(cell, seed=SEED + 1, seconds=2.0, trace=False,
                           process_start=time.perf_counter())
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    called = set((tmp_path / "arch" / "toy_gqa.calls").read_text().split())
    assert {"arch_config", "shapes", "std", "to_program",
            "logits_at"} <= called
    assert _committed_files() == before


@pytest.mark.parametrize("kind", [None, "no_such_kind"])
def test_a_configuration_without_a_known_kind_fails_to_load(tmp_path, kind):
    bench = bench_tiny.build(tmp_path)
    cfg = {k: v for k, v in bench_tiny.CONFIG.items() if k != "kind"}
    if kind is not None:
        cfg["kind"] = kind
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(cfg))
    with pytest.raises((ValueError, FileNotFoundError)) as e:
        bench_tiny.cell(tmp_path, "tiny.chat", bench)
    msg = str(e.value)
    assert str(tmp_path / "configs" / "tiny.json") in msg
    assert str(tmp_path / "arch" / f"{kind or '<kind>'}.py") in msg

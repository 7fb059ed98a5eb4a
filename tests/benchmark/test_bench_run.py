"""The harness end to end at a size the CPU runs in seconds: the look for
a chip, sound runs, a benchmark extended by files alone, and runs with
the timed path broken underneath, which must come out not correct."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from benchmarks.chip import harness, spec

import bench_tiny

SEED = 2 ** 31 + 4242
RUN_PY = spec.HERE / "run.py"


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_py_refuses_a_machine_without_a_tpu():
    p = subprocess.run(
        [sys.executable, str(RUN_PY), "--workload", "qwen2-1.5b.chat",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_run_py_fails_without_the_program(tmp_path):
    """A checkout that holds only the benchmark's own files."""
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(spec.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable] + bench["command"][1:] +
        ["--workload", "qwen2-1.5b.chat", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.fixture
def tiny(tmp_path):
    bench = bench_tiny.build(tmp_path)
    return tmp_path, bench


def _run(base, bench, name, trace=False, seconds=2.0):
    cell = bench_tiny.cell(base, name, bench)
    return harness.run_cell(cell, seed=SEED, seconds=seconds, trace=trace,
                            process_start=time.perf_counter())


def test_sound_chat_run(tiny):
    res = _run(*tiny, "tiny.chat")
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "check"
    gap = res["check"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    json.dumps(res)


@pytest.mark.parametrize("window_end, deadline", [(100.0, 270.0),
                                                   (300.0, 360.0)])
def test_the_drain_deadline(window_end, deadline):
    """A minute past the close at least, and on while the run's time
    allows (a run that compiled in set-up closes late)."""
    assert harness.drain_deadline(window_end, 0.0) == deadline


@pytest.mark.parametrize("until_s, correct", [(600.0, True), (0.0, False)])
def test_late_requests_are_waited_for(tiny, monkeypatch, until_s, correct):
    """With no minute past the close, the drain still waits while the
    run has time; a request it stops waiting for fails the run.  The
    closed loop keeps every slot busy, so requests admitted in the window
    are in flight at the close however fast the host serves them."""
    monkeypatch.setattr(harness, "DRAIN_S", 0.0)
    monkeypatch.setattr(harness, "DRAIN_UNTIL_S", until_s)
    res = _run(*tiny, "tiny.longctx")
    assert res["correct"] is correct
    assert (res["failed"] == 0) is correct


def test_a_file_added_benchmark_runs_traced(tiny):
    """A throwaway configuration, mix, cell and per-layer metric, each a
    new file plus a new entry: nothing committed is edited."""
    base, bench = tiny
    cfg = dict(bench_tiny.CONFIG, name="tiny2")
    cfg["engine"] = dict(cfg["engine"], batch_slots=2)
    (base / "configs" / "tiny2.json").write_text(json.dumps(cfg))
    (base / "traffic" / "docs3.json").write_text(json.dumps(
        dict(bench_tiny.LONGCTX, docs=3)))
    (base / "cells" / "tiny2.docs3.json").write_text(json.dumps(
        {"check": {"max_logit_gap": bench_tiny.LIMIT}}))
    (base / "metrics" / "prefix_hit_share.docs3.py").write_text(
        "def read(rec):\n"
        "    c = rec.counters\n"
        "    return 100.0 * c['prefix_hit_tokens'] / "
        "c['prefix_lookup_tokens']\n")
    bench["configs"].append({"name": "tiny2", "source": "-", "reduced": [],
                             "file": "configs/tiny2.json", "why": "-"})
    bench["workloads"].append({"name": "tiny2.docs3", "config": "tiny2",
                               "traffic": "docs3", "chips": 1, "why": "-"})
    for e in bench["end_to_end"]:
        if e["name"] == "output_tokens_per_s":
            e["workloads"].append("tiny2.docs3")
    bench["per_layer"].append(
        {"name": "prefix_hit_share.docs3", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "serving engine",
         "moves": "output_tokens_per_s", "workloads": ["tiny2.docs3"]})
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if not m["name"].endswith(".longctx")]
    res = _run(base, bench, "tiny2.docs3", trace=True)
    assert res["correct"] is True
    share = res["metrics"]["prefix_hit_share.docs3"]["value"]
    assert 50.0 < share < 100.0
    # a CPU trace holds no device plane: device metrics stay silent
    assert "busy_s" not in res["device"]


def _keep_state(orig):
    def step(self, params, tokens, kv_pages, *a, **k):
        logits, _ = orig(self, params, tokens, kv_pages, *a, **k)
        return logits, kv_pages
    return step


def _half_batch(orig):
    def step(self, params, tokens, *a, **k):
        logits, pages = orig(self, params, tokens, *a, **k)
        B = logits.shape[0]
        if B > 1:
            mean = logits[:B // 2].mean(axis=0, keepdims=True)
            logits = jnp.concatenate(
                [logits[:B // 2],
                 jnp.broadcast_to(mean, (B - B // 2,) + logits.shape[1:])])
        return logits, pages
    return step


def _alter_token(orig):
    def record(self, slot, token):
        return orig(self, slot, (token + 1) % bench_tiny.MODEL["vocab_size"])
    return record


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_a_broken_timed_path_is_not_correct(tiny, monkeypatch, fault):
    from repro.models.transformer import Model
    from repro.serve.scheduler import Scheduler
    if fault == "token_altered":
        monkeypatch.setattr(Scheduler, "record_token",
                            _alter_token(Scheduler.record_token))
    else:
        wrap = _keep_state if fault == "state_unchanged" else _half_batch
        monkeypatch.setattr(Model, "decode_paged",
                            wrap(Model.decode_paged))
    res = _run(*tiny, "tiny.longctx")
    assert res["correct"] is False
    gap = res["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]

"""A throwaway benchmark at a size the CPU runs in seconds, built in a
temporary directory from the committed generators and readers."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from benchmarks.chip import spec

MODEL = {"hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
         "tie_word_embeddings": True, "attention_bias": True,
         "rms_norm_eps": 1e-6}
#: what the program computes for MODEL in float32 (see the reference)
SERVED = {"embedding_multiplier": 8.0, "attention_multiplier": 16 ** -0.5,
          "residual_multiplier": 1.0, "logits_scaling": 1.0,
          "rms_norm_eps": 1e-6}
CONFIG = {"name": "tiny", "arch": "qwen2-1.5b", "kind": "dense_gqa",
          "dtype": "float32",
          "chips": 1,
          "engine": {"batch_slots": 4, "max_seq": 256, "kv_cache": "fp",
                     "prefix_cache": True},
          "model": MODEL, "served": SERVED}
CHAT = {"kind": "poisson",
        "prompt": {"median": 12, "sigma": 1.0, "min": 2, "max": 64},
        "output": {"median": 8, "sigma": 1.0, "min": 2, "max": 32},
        "greedy_share": 0.5, "temperature": 0.8}
LONGCTX = {"kind": "shared_docs", "docs": 2, "doc_len": [60, 80],
           "question_len": [4, 12],
           "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 32},
           "cycle": 16}
#: float32 program against the float32 reference: any token the sound
#: program serves is the reference's best or within rounding of it
LIMIT = 0.01


def build(base: Path, limit: float = LIMIT) -> dict:
    """Write the tiny benchmark's files under ``base``; returns its
    ``BENCHMARK.json`` content (cells ``tiny.chat``, ``tiny.longctx``)."""
    for d in ("arch", "traffic/kinds", "cells", "metrics", "configs"):
        (base / d).mkdir(parents=True, exist_ok=True)
    for a in spec.HERE.glob("arch/*.py"):
        shutil.copy(a, base / "arch")
    for k in spec.HERE.glob("traffic/kinds/*.py"):
        shutil.copy(k, base / "traffic" / "kinds")
    for m in spec.HERE.glob("metrics/*.py"):
        shutil.copy(m, base / "metrics")
    files = {"configs/tiny.json": CONFIG, "traffic/chat.json": CHAT,
             "traffic/longctx.json": LONGCTX,
             "cells/tiny.chat.json": {"rate_hz": 4.0,
                                      "check": {"max_logit_gap": limit}},
             "cells/tiny.longctx.json": {"check": {"max_logit_gap": limit}}}
    for name, obj in files.items():
        (base / name).write_text(json.dumps(obj))
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    bench["configs"] = [{"name": "tiny", "source": "-", "reduced": [],
                         "file": "configs/tiny.json", "why": "-"}]
    bench["workloads"] = [
        {"name": f"tiny.{t}", "config": "tiny", "traffic": t, "chips": 1,
         "why": "-"} for t in ("chat", "longctx")]
    for e in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in e:
            e["workloads"] = sorted({"tiny." + w.split(".")[-1]
                                     for w in e["workloads"]})
    return bench


def cell(base: Path, name: str, bench: dict):
    return spec.load_cell(name, bench=bench, base=base, root=base)

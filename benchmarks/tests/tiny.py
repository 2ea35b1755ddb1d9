"""Tiny files that the tests drop into a temporary copy of the benchmark: a
configuration, two cells with their traffic, and a per-layer metric with a
reader of its own. Nothing here is read by a real run."""

import json
import os
import shutil

from conftest import BENCH_DIR, ROOT

CONFIG = {
    "name": "tiny", "preset": "tiny", "source": "tests only",
    "vocab_size": 512, "n_positions": 128, "n_embd": 64, "n_head": 4,
    "n_layer": 2, "layer_norm_epsilon": 1e-5, "reduced": [],
    "assumed": {"padded_vocab": 512},
    "dtypes": {"param": "float32", "compute": "bfloat16",
               "logits": "float32", "kv": "bfloat16"}}

TRAIN_MIX = {"kind": "packed_steps", "batch": 2, "seq_len": 128,
             "doc_tokens": {"dist": "pareto", "min": 8, "max": 128,
                            "shape": 1.2},
             "docs_per_cycle": 16, "tokens": {"dist": "zipf", "exponent": 1.0}}
TRAIN_CELL = {"name": "train-tiny", "config": "tiny", "traffic": "tiny-steps",
              "chips": 1, "driver": "miner_steps",
              "driver_args": {"remat": True, "check_steps": 3,
                              "expect_mosaic_min": 0},
              "limits": {"first_loss_gap": 0.0006, "later_loss_gap": 0.0006,
                         "grad_norm_gap": 0.006,
                         "change_norm_gap": 0.42},
              "why": "tests"}

SERVE_MIX = {"kind": "open_loop", "rate_rps": 8.0,
             "prompt_tokens": {"dist": "pareto", "min": 8, "max": 64,
                               "shape": 1.2},
             "output_tokens": {"dist": "pareto", "min": 8, "max": 48,
                               "shape": 1.5},
             "max_total": 128, "tokens": {"dist": "uniform"},
             "sharing": "none", "order_seed": 5}
SERVE_CELL = {"name": "serve-tiny", "config": "tiny", "traffic": "tiny-chat",
              "chips": 1, "driver": "open_loop",
              "engine": {"max_slots": 4, "page_size": 16, "max_seq_len": 128,
                         "max_new_tokens": 48, "prefix_cache": True,
                         "expect_paged_kernel": False},
              "warmup": {"prefill_tokens": [32, 64], "decode_slots": [2, 4],
                         "decode_pages": [2, 4, 8]},
              "check": {"sample_requests": 8, "min_tokens": 8},
              "drain_s": 20.0,
              "limits": {"served_logit_gap": 0.0003, "served_mean_gap": 0.0003},
              "why": "tests"}

NEW_METRIC = {"name": "tiny.steps", "unit": "count", "layer": "train engine",
              "moves": "train_tokens_per_s",
              "reader": "tiny_steps", "args": {}}
NEW_READER = '''"""A reader dropped in by a test."""


def read(rec):
    return float(rec.run.stats["steps"])
'''


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def copy_with_tiny(tmp_path) -> str:
    """A copy of BENCHMARK.json and benchmarks/ with the tiny files and
    their entries added. Returns the copy's root."""
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH_DIR, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmarks")
    _dump(os.path.join(b, "configs", "tiny.json"), CONFIG)
    _dump(os.path.join(b, "traffic", "tiny-steps.json"), TRAIN_MIX)
    _dump(os.path.join(b, "traffic", "tiny-chat.json"), SERVE_MIX)
    _dump(os.path.join(b, "workloads", "train-tiny.json"), TRAIN_CELL)
    _dump(os.path.join(b, "workloads", "serve-tiny.json"), SERVE_CELL)
    _dump(os.path.join(b, "layer_metrics", "tiny.steps.json"), NEW_METRIC)
    with open(os.path.join(b, "readers", "tiny_steps.py"), "w") as f:
        f.write(NEW_READER)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "source": "tests only",
                             "file": "benchmarks/configs/tiny.json",
                             "reduced": [], "why": "tests"})
    for cell in (TRAIN_CELL, SERVE_CELL):
        bench["workloads"].append({k: cell[k] for k in (
            "name", "config", "traffic", "chips", "why")})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            if m["name"] == "train_tokens_per_s":
                m["workloads"].append("train-tiny")
            else:
                m["workloads"].append("serve-tiny")
    for m in bench["per_layer"]:
        if "workloads" in m:
            if "serve-large-chat" in m["workloads"]:
                m["workloads"].append("serve-tiny")
            if "train-large-t1024" in m["workloads"]:
                m["workloads"].append("train-tiny")
    bench["per_layer"].append({
        "name": "tiny.steps", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "train engine",
        "moves": "train_tokens_per_s", "workloads": ["train-tiny"]})
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root

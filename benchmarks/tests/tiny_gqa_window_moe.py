"""Tiny files of the AFMoE family that the tests drop into a temporary copy
of the benchmark: the program's `tiny-trinity` preset as a configuration,
one mixed open-loop cell with its traffic. Nothing here is read by a real
run."""

import dataclasses
import json
import os

import tiny


def config() -> dict:
    from distributedtraining_tpu.models import afmoe
    from drivers import open_loop_gqa_window_moe as driver
    pc = afmoe.PRESETS["tiny-trinity"]
    published = {f.name: driver._plain(getattr(pc, f.name))
                 for f in dataclasses.fields(pc)
                 if f.name not in driver._PROGRAM_KEYS}
    return dict(published, name="tiny-trinity", preset="tiny-trinity",
                source="tests only", reduced=[],
                experts_held=list(pc.experts_held),
                # 0.11 * sqrt(64) = 0.02 * sqrt(2048): the signal sizes of
                # the published widths
                assumed={"padded_vocab": 512, "matrix_std": 0.11},
                dtypes={"param": "float32", "compute": "float32",
                        "logits": "float32", "kv": "float32"})


# prompts of 6-100 tokens against a window of 8 and chunks of 16: short and
# many-windows-long in one queue, the published cell's shape at a
# three-hundredth of its size
MIX = {"kind": "open_loop", "rate_rps": 3.0,
       "prompt_tokens": {"dist": "pareto", "min": 6, "max": 100,
                         "shape": 0.7},
       "output_tokens": {"dist": "pareto", "min": 4, "max": 16,
                         "shape": 1.2},
       "max_total": 128, "tokens": {"dist": "uniform"},
       "sharing": "none", "order_seed": 5}
# float32 parameters and compute: what is left between program and
# reference is the order of float32 sums (the sorted grouped product
# against the dense masked sum, paged blocks over a shifted table against
# dense masked scores): gaps of 1e-5. The float8 control and the faults
# read 1e-3 and more.
CELL = {"name": "serve-tiny-trinity", "config": "tiny-trinity",
        "traffic": "tiny-mixed-trinity", "chips": 1,
        "driver": "open_loop_gqa_window_moe",
        "engine": {"max_slots": 4, "page_size": 4, "max_seq_len": 128,
                   "max_new_tokens": 16, "prefix_cache": False,
                   "prefill_chunk": 16, "pool_pages": 129,
                   "window_pool_pages": 33,
                   "expect_paths": {"paged_window_decode_attention": 0,
                                    "paged_decode_attention": 0, "gmm": 0}},
        "warmup": {"prefill_tokens": [8, 16], "suffix_tokens": [8, 16],
                   "table_pages": 32, "decode_slots": [2, 4]},
        "check": {"sample_requests": 3, "min_tokens": 8,
                  "min_longest_context": 40, "margin_floor": 1e-5},
        "drain_s": 30.0,
        "limits": {"served_logit_gap": 0.0005, "served_mean_gap": 0.0003,
                   "near_tie_share": 0.05, "window_edge_gap": 0.001},
        "why": "tests"}


def copy_with_tiny(tmp_path) -> str:
    """`tiny.copy_with_tiny`'s copy, with this family's files and entries
    added. Returns the copy's root."""
    root = tiny.copy_with_tiny(tmp_path)
    b = os.path.join(root, "benchmarks")
    tiny._dump(os.path.join(b, "configs", "tiny-trinity.json"), config())
    tiny._dump(os.path.join(b, "traffic", "tiny-mixed-trinity.json"), MIX)
    tiny._dump(os.path.join(b, "workloads", "serve-tiny-trinity.json"), CELL)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-trinity", "source": "tests only",
                             "file": "benchmarks/configs/tiny-trinity.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({k: CELL[k] for k in (
        "name", "config", "traffic", "chips", "why")})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "serve-trinity-mixed" in m.get("workloads", ()):
                m["workloads"].append(CELL["name"])
    tiny._dump(path, bench)
    return root

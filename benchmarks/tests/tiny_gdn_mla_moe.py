"""Tiny files of the GigaChat-3.5 family that the tests drop into a
temporary copy of the benchmark: the program's `tiny-gigachat` preset as a
configuration, one serve cell with its traffic. Nothing here is read by a
real run."""

import dataclasses
import json
import os

import tiny


def config() -> dict:
    from distributedtraining_tpu.models import gigachat3_5
    from drivers import open_loop_gdn_mla_moe as driver
    pc = gigachat3_5.PRESETS["tiny-gigachat"]
    published = {f.name: driver._plain(getattr(pc, f.name))
                 for f in dataclasses.fields(pc)}
    return dict(published, name="tiny-gigachat", preset="tiny-gigachat",
                source="tests only", reduced=[],
                n_routed_experts=pc.experts_held[1],
                published={"n_routed_experts": pc.n_routed_experts},
                experts_held=list(pc.experts_held),
                # 0.21 * sqrt(64) = 0.02 * sqrt(7168): the signal sizes of
                # the published widths, so that the gates and the decay's
                # argument spread as there
                assumed={"padded_vocab": 512, "matrix_std": 0.21},
                dtypes={"param": "float32", "compute": "float32",
                        "logits": "float32", "kv": "float32"})


MIX = {"kind": "open_loop", "rate_rps": 8.0,
       "prompt_tokens": {"dist": "pareto", "min": 8, "max": 32, "shape": 1.2},
       "output_tokens": {"dist": "pareto", "min": 8, "max": 80, "shape": 1.2},
       "max_total": 112, "tokens": {"dist": "uniform"},
       "sharing": "none", "order_seed": 5}
# float32 parameters and compute: what is left between program and
# reference is the order of float32 sums (the chunked WY form against the
# recurrence, the sorted grouped product against the dense masked sum, the
# absorbed against the expanded attention): gaps of 1e-5. The float8
# control and the faults read 1e-3 and more.
CELL = {"name": "serve-tiny-gigachat", "config": "tiny-gigachat",
        "traffic": "tiny-reason-gigachat", "chips": 1,
        "driver": "open_loop_gdn_mla_moe",
        "engine": {"max_slots": 4, "page_size": 16, "max_seq_len": 128,
                   "max_new_tokens": 80, "prefix_cache": False,
                   "expect_paths": {"gdn_decode_update": 0,
                                    "mla_decode_attention": 0, "gmm": 0}},
        # the 8-page rung lies two doublings past the longest prompt (32
        # tokens = 2 pages): warmed by decoding into it
        "warmup": {"prefill_tokens": [16, 32], "decode_slots": [2, 4],
                   "decode_pages": [2], "decode_pages_grown": [4],
                   "decode_pages_far": [8]},
        "check": {"sample_requests": 8, "min_tokens": 8,
                  "margin_floor": 1e-5},
        "drain_s": 30.0,
        "limits": {"served_logit_gap": 0.0005, "served_mean_gap": 0.0003,
                   "near_tie_share": 0.05},
        "why": "tests"}


def copy_with_tiny(tmp_path) -> str:
    """`tiny.copy_with_tiny`'s copy, with this family's files and entries
    added. Returns the copy's root."""
    root = tiny.copy_with_tiny(tmp_path)
    b = os.path.join(root, "benchmarks")
    tiny._dump(os.path.join(b, "configs", "tiny-gigachat.json"), config())
    tiny._dump(os.path.join(b, "traffic", "tiny-reason-gigachat.json"), MIX)
    tiny._dump(os.path.join(b, "workloads", "serve-tiny-gigachat.json"),
               CELL)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-gigachat",
                             "source": "tests only",
                             "file": "benchmarks/configs/tiny-gigachat.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({k: CELL[k] for k in (
        "name", "config", "traffic", "chips", "why")})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "serve-gigachat-reason" in m.get("workloads", ()):
                m["workloads"].append(CELL["name"])
    tiny._dump(path, bench)
    return root

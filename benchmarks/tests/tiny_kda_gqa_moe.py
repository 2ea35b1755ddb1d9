"""Tiny files of the Solar-Open2 family that the tests drop into a
temporary copy of the benchmark: the program's `tiny-solar` preset as a
configuration, one sessions cell with its traffic. Nothing here is read by
a real run."""

import dataclasses
import json
import os

import tiny


def config() -> dict:
    from distributedtraining_tpu.models import solar_open2
    from drivers import sessions_kda_gqa_moe as driver
    pc = solar_open2.PRESETS["tiny-solar"]
    published = {f.name: driver._plain(getattr(pc, f.name))
                 for f in dataclasses.fields(pc)
                 if f.name not in driver._PROGRAM_KEYS}
    return dict(published, name="tiny-solar", preset="tiny-solar",
                source="tests only", reduced=[],
                n_routed_experts=pc.experts_held[1],
                published={"n_routed_experts": pc.n_routed_experts},
                experts_held=list(pc.experts_held),
                # 0.16 * sqrt(64) = 0.02 * sqrt(4096): the signal sizes of
                # the published widths, so that the gates and the decay's
                # argument spread as there
                assumed={"padded_vocab": 512, "kda_low_rank": 16,
                         "matrix_std": 0.16},
                dtypes={"param": "float32", "compute": "float32",
                        "logits": "float32", "kv": "float32"})


# 4 sessions of 40-120 tokens of history, turns of 6-20 on top, at most
# 256 positions: the published cell's shape at a thousandth of its size
MIX = {"kind": "sessions", "sessions": 4, "rate_rps": 2.0,
       "history_tokens": {"dist": "pareto", "min": 40, "max": 120,
                          "shape": 1.2},
       "message_tokens": {"dist": "pareto", "min": 6, "max": 20,
                          "shape": 1.2},
       "output_tokens": {"dist": "pareto", "min": 6, "max": 16,
                         "shape": 1.5},
       "max_total": 256, "tokens": {"dist": "uniform"},
       "sharing": "none", "order_seed": 5}
# float32 parameters and compute: what is left between program and
# reference is the order of float32 sums (the chunked WY form and its
# continuation from a snapshot against the recurrence, the sorted grouped
# product against the dense masked sum, paged blocks against dense
# scores): gaps of 1e-5. The float8 control and the faults read 1e-3 and
# more.
CELL = {"name": "serve-tiny-solar", "config": "tiny-solar",
        "traffic": "tiny-sessions-solar", "chips": 1,
        "driver": "sessions_kda_gqa_moe",
        "engine": {"max_slots": 4, "page_size": 16, "max_seq_len": 256,
                   "max_new_tokens": 16, "prefix_cache": True,
                   "snapshot_rows": 8, "prefill_chunk": 32,
                   "pool_pages": 129,
                   "expect_paths": {"gdn_decode_update": 0,
                                    "paged_decode_attention": 0, "gmm": 0}},
        "warmup": {"suffix_tokens": [16, 32], "table_pages": 16,
                   "decode_slots": [2, 4]},
        "check": {"sample_requests": 3, "min_tokens": 8,
                  "min_longest_context": 60, "margin_floor": 1e-5},
        "drain_s": 30.0,
        "limits": {"served_logit_gap": 0.0005, "served_mean_gap": 0.0003,
                   "near_tie_share": 0.05},
        "why": "tests"}


def copy_with_tiny(tmp_path) -> str:
    """`tiny.copy_with_tiny`'s copy, with this family's files and entries
    added. Returns the copy's root."""
    root = tiny.copy_with_tiny(tmp_path)
    b = os.path.join(root, "benchmarks")
    tiny._dump(os.path.join(b, "configs", "tiny-solar.json"), config())
    tiny._dump(os.path.join(b, "traffic", "tiny-sessions-solar.json"), MIX)
    tiny._dump(os.path.join(b, "workloads", "serve-tiny-solar.json"), CELL)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-solar", "source": "tests only",
                             "file": "benchmarks/configs/tiny-solar.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({k: CELL[k] for k in (
        "name", "config", "traffic", "chips", "why")})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "serve-solar-sessions" in m.get("workloads", ()):
                m["workloads"].append(CELL["name"])
    tiny._dump(path, bench)
    return root

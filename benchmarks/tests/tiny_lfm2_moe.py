"""Tiny files of the LFM2-MoE family that the tests drop into a temporary
copy of the benchmark: the program's `tiny-lfm2` preset as a configuration,
one train cell with its traffic. Nothing here is read by a real run."""

import dataclasses
import json
import os

import tiny


def config() -> dict:
    from distributedtraining_tpu.models import lfm2_moe
    pc = lfm2_moe.PRESETS["tiny-lfm2"]
    published = {f.name: getattr(pc, f.name) for f in dataclasses.fields(pc)}
    return dict(published, name="tiny-lfm2", preset="tiny-lfm2",
                source="tests only", reduced=[],
                layer_types=list(pc.layer_types),
                num_experts=pc.experts_held[1],
                published={"num_experts": pc.num_experts},
                experts_held=list(pc.experts_held),
                vocab_held=list(pc.vocab_held),
                assumed={"padded_vocab": 512, "route_norm_eps": 1e-6},
                dtypes={"param": "float32", "compute": "float32",
                        "logits": "float32"})


MIX = {"kind": "packed_steps", "batch": 2, "seq_len": 128,
       "doc_tokens": {"dist": "pareto", "min": 8, "max": 128, "shape": 1.2},
       "docs_per_cycle": 16, "tokens": {"dist": "zipf", "exponent": 1.0}}
# float32 parameters and compute: what is left between program and
# reference is the order of float32 sums (sorted grouped products against
# a dense masked sum): gaps of 1e-6 in the losses and 1e-4 in a leaf's
# norms. The float8 control and the faults read 1e-3 and more.
CELL = {"name": "train-tiny-lfm2", "config": "tiny-lfm2",
        "traffic": "tiny-steps-lfm2", "chips": 1,
        "driver": "miner_steps_lfm2_moe",
        "driver_args": {"remat": True, "check_steps": 3,
                        "expect_kernels": {"flash_mha_fwd": 0,
                                           "call @gmm": 0}},
        "limits": {"first_loss_gap": 0.0001, "later_loss_gap": 0.0001,
                   "grad_norm_gap": 0.001, "grad_direction_gap": 0.001,
                   "change_norm_gap": 0.02},
        "why": "tests"}


def copy_with_tiny(tmp_path) -> str:
    """`tiny.copy_with_tiny`'s copy, with this family's files and entries
    added. Returns the copy's root."""
    root = tiny.copy_with_tiny(tmp_path)
    b = os.path.join(root, "benchmarks")
    tiny._dump(os.path.join(b, "configs", "tiny-lfm2.json"), config())
    tiny._dump(os.path.join(b, "traffic", "tiny-steps-lfm2.json"), MIX)
    tiny._dump(os.path.join(b, "workloads", "train-tiny-lfm2.json"), CELL)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-lfm2", "source": "tests only",
                             "file": "benchmarks/configs/tiny-lfm2.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({k: CELL[k] for k in (
        "name", "config", "traffic", "chips", "why")})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "train-lfm2-t8192" in m.get("workloads", ()):
                m["workloads"].append(CELL["name"])
    tiny._dump(path, bench)
    return root

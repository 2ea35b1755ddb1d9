"""The few places where the benchmark has to know the program's names: its
model presets and the layout of its parameter tree. Everything else the
drivers touch is the program's public surface."""

from __future__ import annotations

import dataclasses


def make_model(config: dict, *, remat: bool | None = None):
    """The program's model for a configuration file. Every published size
    in the file must be the preset's own: a file that says one thing while
    the preset runs another is refused."""
    from distributedtraining_tpu.models import gpt2

    pc = gpt2.PRESETS[config["preset"]]
    assumed = config.get("assumed", {})
    want = {"n_embd": pc.n_embd, "n_layer": pc.n_layer, "n_head": pc.n_head,
            "vocab_size": pc.vocab_size, "n_positions": pc.n_positions,
            "layer_norm_epsilon": pc.layer_norm_epsilon}
    for key, val in want.items():
        if config[key] != val:
            raise SystemExit(f"bench: FAIL: {config['name']}.{key} = "
                             f"{config[key]!r} but preset "
                             f"{config['preset']} runs {val!r}")
    if assumed.get("padded_vocab", pc.padded_vocab) != pc.padded_vocab:
        raise SystemExit("bench: FAIL: padded_vocab differs from the preset")
    dt = config["dtypes"]
    if (dt["param"], dt["compute"], dt["logits"]) != (
            pc.param_dtype, pc.dtype, pc.logits_dtype):
        raise SystemExit("bench: FAIL: dtypes differ from the preset")
    if remat is not None:
        pc = dataclasses.replace(pc, remat=remat)
    return gpt2.make_model(pc)


def to_program_tree(weights: dict) -> dict:
    """Reference layout (the GPT-2 release's names) -> the program's Flax
    tree. The arrays are handed over, not copied."""
    def ln(p):
        return {"scale": p["g"], "bias": p["b"]}

    def dense(p):
        return {"kernel": p["w"], "bias": p["b"]}

    tree = {"wte": weights["wte"], "wpe": weights["wpe"],
            "ln_f": ln(weights["ln_f"])}
    for i, blk in enumerate(weights["h"]):
        tree[f"h_{i}"] = {k: (ln(v) if k.startswith("ln_") else dense(v))
                          for k, v in blk.items()}
    return tree


def release_name(path) -> str:
    """A key path of the program's tree -> the release's name of that
    leaf (`h.3.c_attn.w`)."""
    keys = [k.key for k in path if isinstance(getattr(k, "key", None), str)]
    leaf = {"kernel": "w", "bias": "b", "scale": "g"}
    if keys[0] in ("wte", "wpe"):
        return keys[0]
    if keys[0] == "ln_f":
        return f"ln_f.{leaf[keys[1]]}"
    return f"h.{keys[0][2:]}.{keys[1]}.{leaf[keys[2]]}"

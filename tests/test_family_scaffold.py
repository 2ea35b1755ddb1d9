"""What models/family.py may not move: every preset's parameter tree (path,
shape, dtype of each leaf, under ``jax.eval_shape``, so the published sizes
cost nothing), the tiny presets' logits from ``PRNGKey(0)`` weights (flax
draws a leaf from its scope's path AND its turn among the scope's own
parameters, so a helper that declares them in another order shows here),
and what a family states to the engine: one base, read plainly.

The digests and logits were recorded at the parent of the PR that wrote
models/family.py (PR 44), three times under ``-n 6``: the CPU backend
repeated itself bit for bit, so the comparison is ``array_equal``.
``python tests/test_family_scaffold.py`` prints both tables anew."""

import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.models import (
    deepseek_v3, family, family_of, gigachat3_5, gpt2, lfm2_moe, llama,
    nemotron_h, solar_open2)

FAMILIES = (gpt2, llama, deepseek_v3, nemotron_h, lfm2_moe, gigachat3_5,
            solar_open2)
PRESETS = [name for fam in FAMILIES for name in fam.PRESETS]
TINY = ("tiny", "tiny-llama", "tiny-kanana", "tiny-nemotron-h", "tiny-lfm2",
        "tiny-gigachat", "tiny-solar")
# what a family may state to the engine (family.FamilyConfig's docstring)
STATED = ("layer_caches", "cache_row_widths", "state_name", "n_kv_head",
          "rounds_first", "serving_head", "is_buffer")

TREES = {
    "gpt2-124m": "a2b20830bc708f4a",
    "gpt2-355m": "53f6171f41f55853",
    "gpt2-774m": "6c4ff85b19752f4b",
    "gpt2-1.5b": "3776027ef1f8573d",
    "tiny": "ad5682140573d565",
    "mini": "991b74e94580930f",
    "llama2-7b": "b217a167c317b89d",
    "llama3-8b": "dcab6ce39bc8b52a",
    "tiny-llama": "a7cff20d3011176a",
    "kanana-2-30b-a3b": "6d61f34cbbd359b5",
    "kanana-2-30b-a3b-l8": "2249afa9ad989196",
    "tiny-kanana": "69369020de1d9ad0",
    "nemotron-3-super-120b-a12b": "c99d6a3b91b4f4bc",
    "nemotron-3-super-120b-a12b-l11-e128": "c5f4fb08cada4f12",
    "tiny-nemotron-h": "eeba17ed89d874e9",
    "lfm2-8b-a1b": "85ae79814c6c8b8b",
    "lfm2-8b-a1b-l5-e8-v16k": "6e2c3c6f159500d1",
    "tiny-lfm2": "fc63005b08302c26",
    "gigachat3.5-432b-a28b": "a435c4a076518f8e",
    "gigachat3.5-432b-a28b-l5-e16-v16k": "5dff94077aa5b763",
    "tiny-gigachat": "7be70ec9567929d7",
    "solar-open2-250b": "78fe15ddcd5fc85c",
    "solar-open2-250b-l4-e40-v24k": "b39c1d9df730979a",
    "tiny-solar": "35389c5ceb0400e7",
}

LOGITS = {
    "tiny": (
        (0.04404452443122864, -0.01554221659898758, -0.09216030687093735,
         -0.14584052562713623, -0.08659368753433228, -0.2519586384296417,
         0.10734621435403824, 0.15736746788024902),
        (-0.07332634925842285, 0.18600612878799438, -0.10879361629486084,
         0.038850538432598114, 0.08244584500789642, 0.019051313400268555,
         0.10667625069618225, 0.08975204080343246),
    ),
    "tiny-llama": (
        (0.16558751463890076, 0.016744188964366913, 0.07768514007329941,
         -0.011134564876556396, 0.05939195305109024, 0.030511200428009033,
         0.1494162231683731, 0.11820182204246521),
        (0.16310815513134003, -0.13977159559726715, 0.11127270758152008,
         -0.15397831797599792, 0.38601624965667725, 0.02058243751525879,
         -0.06802377104759216, -0.03384000062942505),
    ),
    "tiny-kanana": (
        (0.22741934657096863, -0.02710178680717945, 0.06560647487640381,
         -0.15126162767410278, 0.0468519888818264, -0.062270358204841614,
         0.01521142665296793, 0.09037861973047256),
        (-0.08162090182304382, -0.10559386759996414, 0.014907900243997574,
         -0.10802232474088669, 0.2925036549568176, 0.31872716546058655,
         -0.20810841023921967, -0.006059692241251469),
    ),
    "tiny-nemotron-h": (
        (-0.14033788442611694, 0.09407084435224533, 0.3994291126728058,
         -0.03762533515691757, 0.035530172288417816, 0.21573509275913239,
         0.17028063535690308, 0.028898587450385094),
        (-0.09973758459091187, -0.021790215745568275, 0.07620459794998169,
         -0.18207374215126038, 0.1099773570895195, -0.11649490892887115,
         -0.08311238884925842, 0.18839046359062195),
    ),
    "tiny-lfm2": (
        (-0.03207121044397354, -0.020131893455982208, 0.30475032329559326,
         -0.21074742078781128, -0.13053077459335327, -0.033038388937711716,
         0.1374940574169159, 0.09006814658641815),
        (-0.043853290379047394, -0.20067760348320007, -0.08007007092237473,
         0.09846682846546173, -0.00361211271956563, -0.20272739231586456,
         0.11297443509101868, 0.004135213792324066),
    ),
    "tiny-gigachat": (
        (0.03946008160710335, 0.15876701474189758, -0.13607944548130035,
         0.20002281665802002, 0.01824687421321869, 0.15357203781604767,
         -0.15980693697929382, -0.1683003008365631),
        (0.10293988883495331, 0.13680173456668854, 0.1992875337600708,
         -0.11594155430793762, -0.1851573884487152, 0.1015273854136467,
         -0.10685697942972183, -0.05623181164264679),
    ),
    "tiny-solar": (
        (-0.02018570713698864, 0.13270094990730286, 0.054411835968494415,
         -0.1797950565814972, 0.03743838518857956, 0.2455952912569046,
         0.0664917379617691, -0.0741698369383812),
        (-0.015087398700416088, 0.036030128598213196, 0.023054903373122215,
         -0.06508605182170868, 0.015082796104252338, 0.25721320509910583,
         0.05269619822502136, -0.07104359567165375),
    ),
}


def tree_digest(preset: str) -> str:
    model, _ = family_of(preset).make_model(preset)
    tree = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves = sorted(
        ("/".join(k.key for k in path), tuple(x.shape), str(x.dtype))
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0])
    return hashlib.sha256(repr(leaves).encode()).hexdigest()[:16]


def tiny_logits(preset: str) -> np.ndarray:
    """[2, 8] float32: the first 8 logits at the last position of 2 rows
    of 16 ids, from ``PRNGKey(0)`` weights."""
    model, cfg = family_of(preset).make_model(preset)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = (np.arange(32).reshape(2, 16) * 7 + 3) % cfg.vocab_size
    logits = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
    return np.asarray(logits[:, -1, :8], np.float32)


@pytest.mark.parametrize("preset", PRESETS)
def test_parameter_tree_is_the_recorded_one(preset):
    assert tree_digest(preset) == TREES[preset]


@pytest.mark.parametrize("preset", TINY)
def test_tiny_logits_are_the_recorded_ones(preset):
    want = np.asarray(LOGITS[preset], np.float32)
    np.testing.assert_array_equal(tiny_logits(preset), want)


@pytest.mark.parametrize("preset", PRESETS)
def test_config_is_a_family_config(preset):
    cfg = family_of(preset).PRESETS[preset]
    assert isinstance(cfg, family.FamilyConfig)
    for name in STATED:
        getattr(cfg, name)              # stated or defaulted: never absent


def test_engine_reads_what_a_family_states_plainly():
    """No ``getattr(cfg, "<stated name>", default)`` under engine/ or
    neurons/: a misspelt statement must fail, not fall back."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    probe = re.compile(r"getattr\(\s*\w*cfg\w*\s*,\s*[\"'](%s)[\"']"
                       % "|".join(STATED))
    found = []
    for top in ("distributedtraining_tpu/engine", "neurons"):
        for dirpath, _, names in os.walk(os.path.join(root, top)):
            for name in names:
                if name.endswith(".py"):
                    path = os.path.join(dirpath, name)
                    with open(path) as f:
                        found += [f"{path}: {m.group(0)}"
                                  for m in probe.finditer(f.read())]
    assert not found, found


if __name__ == "__main__":
    print("TREES = {")
    for p in PRESETS:
        print(f"    {p!r}: {tree_digest(p)!r},")
    print("}\n\nLOGITS = {")
    for p in TINY:
        rows = tiny_logits(p)
        print(f"    {p!r}: (")
        for row in rows:
            print("        (" + ", ".join(repr(float(v)) for v in row)
                  + "),")
        print("    ),")
    print("}")

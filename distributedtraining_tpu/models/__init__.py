"""Model zoo: TPU-first Flax implementations.

- family: what every family HAS, once: the config base (the statements
  the engine reads), norm, dense, rotation, the gated FFN bodies, the routed
  half, the attention and per-slot-state layers' cache protocol, the
  served model's shell.
- gpt2: the reference's training target (openai-community/gpt2,
  neurons/miner.py:60), in 124M and 355M presets plus tiny test configs.
- llama: Llama-2-7B / Llama-3-8B presets (no cell runs them yet).
- deepseek_v3: latent attention + routed/shared experts (the kanana-2
  row), on the serving path.
- nemotron_h: Mamba-2 layers beside attention and latent routed experts,
  of which a chip holds its share (the Nemotron-3-Super row), on the
  serving path.
- lfm2_moe: gated short convolutions beside grouped-query attention and
  routed experts of which a chip holds its share (the LFM2-8B-A1B row), on
  the TRAINING path: the first family but GPT-2 that ``TrainEngine`` and
  ``MinerLoop`` run on the chip.
- gigachat3_5: gated delta-rule layers beside latent attention (a
  per-slot state and a latent page pool in one engine), routed + shared
  SwiGLU experts of which a chip holds its share (the
  GigaChat3.5-432B-A28B row), on the serving path.
- solar_open2: per-channel gated delta-rule layers (Kimi Delta Attention)
  beside gated grouped-query attention with no position term, every FFN
  routed + shared SwiGLU experts of which a chip holds its share (the
  Solar-Open2-250B row), on the serving path.
- afmoe: sliding-window rotary attention layers beside global ones with no
  position term, every one gated and with a norm a head of q and k; a
  leading dense FFN, then routed + shared SwiGLU experts, all held; four
  norms a block and a muP multiplier on the lookup (the Trinity-Mini row),
  on the serving path: the window layers' pages are a second group that
  gives pages back behind the window.
- lora: low-rank adapter trees whose *parameters are the delta*.

Adding a family (docs/architecture.md, "Model families"): one file with the
config (published key names on ``family.FamilyConfig``, plus
``experts_held`` and the like), the per-layer statement (``layer_caches``:
``"kv"``, ``"kv_window"`` with ``sliding_window`` beside it, ``"ssm"``),
the mixers (a block called as ``block(x, step)``), the model
(``family.ServedDecoder`` with ``block(i)``), the presets and
``make_model = family.make_model(Model, PRESETS)``; then its module in
``family_of`` below.
"""

from .gpt2 import GPT2, GPT2Config
from .llama import Llama, LlamaConfig
from .toy import FeedforwardNet, SimpleCNN, ToyConfig
from . import lora


def family_of(preset: str):
    """The family (its module: ``PRESETS``, ``make_model``) that owns a
    preset's name; GPT-2's, whose lookup then names the unknown preset,
    where none does."""
    from . import (afmoe, deepseek_v3, gigachat3_5, gpt2, lfm2_moe, llama,
                   nemotron_h, solar_open2)
    for family in (llama, deepseek_v3, nemotron_h, lfm2_moe, gigachat3_5,
                   solar_open2, afmoe):
        if preset in family.PRESETS:
            return family
    return gpt2


__all__ = ["GPT2", "GPT2Config", "Llama", "LlamaConfig",
           "FeedforwardNet", "SimpleCNN", "ToyConfig", "lora", "family_of"]

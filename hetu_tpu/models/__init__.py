"""Model zoo: GPT-2, Llama, Command A+ (cohere2_moe) and latent-attention
(MLA) expert decoders, MiniCPM-SALA, delta-rule / latent hybrids,
SDAR-MoE, which generates by diffusion over blocks, Brumby, whose
every layer is power retention, Jamba, whose layers are Mamba
selective scans beside a few attention layers, and Qwen3-Next, Gated
DeltaNet layers beside gated attention over a wide softmax router
(``mla_moe``, ``minicpm_sala``, ``kda_mla_moe``, ``sdar_moe``,
``brumby``, ``jamba``, ``qwen3_next``: loaded on first use,
so that the cells that never build one do not pay for its import).

The four drawn decoders share one shell (``decoder.DecoderLM``); the
three hybrids are a ``Config``, a ``make_block(cfg, kind, dense)`` and
the list of their layers' kinds over ``nn.parallel.LayerStack`` and
``nn.parallel.PreNormBlock`` — a new hybrid of mixers the repo has is
a configuration and a list, not a container.

Parity targets: ``python/hetu/models/gpt`` and
``python/hetu/models/llama/llama_model.py`` (LlamaModel :385,
LlamaLMHeadModel :446).
"""

from hetu_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.models.cohere2_moe import (
    Cohere2MoEConfig, Cohere2MoEForCausalLM,
)
from hetu_tpu.models.bert import BertConfig, BertModel
from hetu_tpu.models.vision import (
    CNNConfig, MLPClassifier, RNNConfig, SimpleCNN, SimpleRNN,
)
from hetu_tpu.models.generation import generate, decode, init_kv_caches

_LAZY = {"MLAMoEConfig": "hetu_tpu.models.mla_moe",
         "MLAMoEForCausalLM": "hetu_tpu.models.mla_moe",
         "MiniCPMSALAConfig": "hetu_tpu.models.minicpm_sala",
         "MiniCPMSALAForCausalLM": "hetu_tpu.models.minicpm_sala",
         "KDAMLAMoEConfig": "hetu_tpu.models.kda_mla_moe",
         "KDAMLAMoEForCausalLM": "hetu_tpu.models.kda_mla_moe",
         "SDARMoEConfig": "hetu_tpu.models.sdar_moe",
         "SDARMoEForCausalLM": "hetu_tpu.models.sdar_moe",
         "BrumbyConfig": "hetu_tpu.models.brumby",
         "BrumbyForCausalLM": "hetu_tpu.models.brumby",
         "JambaConfig": "hetu_tpu.models.jamba",
         "JambaForCausalLM": "hetu_tpu.models.jamba",
         "Qwen3NextConfig": "hetu_tpu.models.qwen3_next",
         "Qwen3NextForCausalLM": "hetu_tpu.models.qwen3_next"}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = ["GPTConfig", "GPTLMHeadModel", "LlamaConfig", "BertConfig", "BertModel", "CNNConfig", "SimpleCNN", "MLPClassifier", "RNNConfig", "SimpleRNN", "LlamaLMHeadModel",
           "Cohere2MoEConfig", "Cohere2MoEForCausalLM",
           "MLAMoEConfig", "MLAMoEForCausalLM",
           "MiniCPMSALAConfig", "MiniCPMSALAForCausalLM",
           "KDAMLAMoEConfig", "KDAMLAMoEForCausalLM",
           "SDARMoEConfig", "SDARMoEForCausalLM",
           "BrumbyConfig", "BrumbyForCausalLM",
           "JambaConfig", "JambaForCausalLM",
           "Qwen3NextConfig", "Qwen3NextForCausalLM",
           "generate", "decode", "init_kv_caches"]

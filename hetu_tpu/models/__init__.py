"""Model zoo: GPT-2, Llama and Command A+ (cohere2_moe) families.

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

__all__ = ["GPTConfig", "GPTLMHeadModel", "LlamaConfig", "BertConfig", "BertModel", "CNNConfig", "SimpleCNN", "MLPClassifier", "RNNConfig", "SimpleRNN", "LlamaLMHeadModel",
           "Cohere2MoEConfig", "Cohere2MoEForCausalLM",
           "generate", "decode", "init_kv_caches"]

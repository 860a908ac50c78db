"""Model families matching the reference's benchmark configs.

Reference parity: GPT/BERT/LLaMA live in the PaddleNLP ecosystem
(`paddlenlp/transformers/{gpt,bert,llama}/modeling.py` [UNVERIFIED — the
reference mount is empty; BASELINE.md configs 3-5 name these models]);
vision models live in `python/paddle/vision/models` (already in
paddle_tpu.vision).  These are the flagship LM families the benchmarks
and the multichip dryrun drive.
"""
from .gpt import GPTConfig, GPTModel, GPTForCausalLM, GPTPretrainingCriterion
from .bert import BertConfig, BertModel, BertForMaskedLM
from .llama import LlamaConfig, LlamaModel, LlamaForCausalLM
from .ernie import (ErnieConfig, ErnieModel, ErnieForMaskedLM,
                    ErnieForSequenceClassification)
from .moe_gpt import (MoEGPTConfig, MoEGPTModel, MoEGPTForCausalLM,
                      MoEGPTPretrainingCriterion)
from .minicpm_sala import MiniCPMSALAConfig, MiniCPMSALAForCausalLM
from .afmoe import AfmoeConfig, AfmoeForCausalLM
from .qwen3_next import Qwen3NextConfig, Qwen3NextForCausalLM
from .generation import GenerationMixin, generate

__all__ = [
    "GPTConfig", "GPTModel", "GPTForCausalLM", "GPTPretrainingCriterion",
    "BertConfig", "BertModel", "BertForMaskedLM",
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
    "ErnieConfig", "ErnieModel", "ErnieForMaskedLM",
    "ErnieForSequenceClassification",
    "MoEGPTConfig", "MoEGPTModel", "MoEGPTForCausalLM",
    "MoEGPTPretrainingCriterion", "MiniCPMSALAConfig",
    "MiniCPMSALAForCausalLM", "AfmoeConfig", "AfmoeForCausalLM",
    "Qwen3NextConfig", "Qwen3NextForCausalLM",
    "GenerationMixin", "generate",
]

"""Model zoo.

The reference keeps end-to-end model fixtures in test/ (e.g.
test/auto_parallel/get_gpt_model.py, test/book/) and vision models in
python/paddle/vision/models; its north-star configs (BASELINE.md) are
ResNet-50, GPT-2 124M, and Llama-2 7B. This package provides those model
families as first-class citizens, built TPU-first: static shapes, bf16-friendly
compute, attention through the fused flash-attention path, and optional
tensor-parallel variants over the hybrid mesh.
"""
from .bert import (BertConfig, BertForMaskedLM,
                   BertForSequenceClassification, BertModel,
                   bert_base_config, bert_tiny_config, shard_bert)
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    llama2_7b_config, llama_tiny_config, shard_llama)
from .glm_dsa import GlmDsaConfig, GlmDsaForCausalLM, glm_dsa_tiny_config
from .keye import KeyeConfig, KeyeForCausalLM, keye_tiny_config
from .gpt import GPT2Config, GPT2ForCausalLM, GPT2Model, gpt2_124m_config
from .resnet import (BasicBlock, BottleneckBlock, ResNet, resnet18, resnet34,
                     resnet50, resnet101, resnet152)
from .lfm2 import Lfm2Config, Lfm2ForCausalLM, lfm2_tiny_config
from .mellum import MellumConfig, MellumForCausalLM, mellum_tiny_config
from .sambay import SambaYConfig, SambaYForCausalLM, sambay_tiny_config
from .unet import (UNetConfig, UNetModel, ddim_sample, ddpm_loss,
                   sd_unet_config, unet_tiny_config)

__all__ = [
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama2_7b_config",
    "llama_tiny_config", "shard_llama",
    "GPT2Config", "GPT2Model", "GPT2ForCausalLM", "gpt2_124m_config",
    "BertConfig", "BertModel", "BertForSequenceClassification",
    "BertForMaskedLM", "bert_base_config", "bert_tiny_config", "shard_bert",
    "ResNet", "BasicBlock", "BottleneckBlock", "resnet18", "resnet34",
    "resnet50", "resnet101", "resnet152",
    "SambaYConfig", "SambaYForCausalLM", "sambay_tiny_config",
    "GlmDsaConfig", "GlmDsaForCausalLM", "glm_dsa_tiny_config",
    "MellumConfig", "MellumForCausalLM", "mellum_tiny_config",
    "KeyeConfig", "KeyeForCausalLM", "keye_tiny_config",
    "Lfm2Config", "Lfm2ForCausalLM", "lfm2_tiny_config",
    "UNetConfig", "UNetModel", "unet_tiny_config", "sd_unet_config",
    "ddpm_loss", "ddim_sample",
]

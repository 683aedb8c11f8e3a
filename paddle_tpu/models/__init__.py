"""paddle_tpu.models — model zoo for the BASELINE.json capability configs."""

from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    LlamaDecoderLayer, LlamaAttention, LlamaMLP,
                    LlamaForCausalLMPipe)
from .moe_lm import MoEConfig, MoEForCausalLM, MoEDecoderLayer
# the hybrid Mamba-2 / attention / expert LM (``nemotron_h``'s layout): served
# through ContinuousBatchingEngine on one chip at a stated share of the model
# (a prefix of the blocks, a share of the experts and of the vocabulary);
# ``forward`` is for tests and evaluation: not trained (the chunked scan has
# no hand-written backward and no training cell runs it)
from .hybrid_lm import HybridConfig, HybridForCausalLM, Mamba2Mixer
from .ernie import ErnieConfig, ErnieForCausalLM
from .dit import DiTConfig, DiT, DiTBlock, timestep_embedding
from .vision import (ResNet, resnet18, resnet50, OCRRecConfig, OCRRecModel,
                     OCRDetModel, DBHead)
from . import diffusion  # noqa: E402  (DDPM/DDIM/rectified-flow schedulers)

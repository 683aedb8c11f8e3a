"""paddle_tpu.models — model zoo for the BASELINE.json capability configs."""

from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    LlamaDecoderLayer, LlamaAttention, LlamaMLP,
                    LlamaForCausalLMPipe)
from .moe_lm import MoEConfig, MoEForCausalLM, MoEDecoderLayer
# the hybrid state-space / attention / expert LM: ``nemotron_h``'s layout
# (Mamba-2, routed experts; served on one chip at a stated share of the
# model: a prefix of the blocks, a share of the experts and of the
# vocabulary) and ``jamba``'s (Mamba-1, a dense MLP behind every mixer, a
# tied head; AI21-Jamba2-3B served whole on one chip), both through
# ContinuousBatchingEngine; ``forward`` is for tests and evaluation: not
# trained (neither scan has a hand-written backward and no training cell
# runs one)
from .hybrid_lm import (HybridConfig, HybridForCausalLM, Mamba1Mixer,
                        Mamba2Mixer)
from .ernie import ErnieConfig, ErnieForCausalLM
from .dit import DiTConfig, DiT, DiTBlock, timestep_embedding
from .vision import (ResNet, resnet18, resnet50, OCRRecConfig, OCRRecModel,
                     OCRDetModel, DBHead)
from . import diffusion  # noqa: E402  (DDPM/DDIM/rectified-flow schedulers)

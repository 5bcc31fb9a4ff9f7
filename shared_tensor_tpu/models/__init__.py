"""Workload models for the shared-tensor training story (BASELINE configs
2 and 4). The reference is model-agnostic parameter sync (SURVEY.md §5.7);
these models exist because its README names them as the intended workloads
(char-rnn, reference README.md:37) and benchmark arms (ResNet async-DP).
``mla_moe`` is the first transformer: a DeepSeek-V3-family decoder whose
table is of deployment size (its ``Config``, ``init_params``, ``forward`` and
``loss_fn`` stay under ``mla_moe.``: char-rnn's are the package's);
``swa_moe`` the second, a grouped-query decoder with window and full
attention layers, an early router and ReGLU experts, which takes what the two
share from ``mla_moe``; ``gated_swa_moe`` the third, whose window and full
layers differ in their head counts and their RoPE, with a per-head output
gate, a dense first layer and a softmax-over-all router beside a shared
expert, made of the other two's functions."""

from . import char_rnn, gated_swa_moe, mla_moe, resnet, swa_moe
from .char_rnn import (
    CharRNNConfig,
    encode_corpus,
    forward,
    init_params,
    loss_fn,
    make_batches,
    sample,
)
from .gated_swa_moe import Config as GatedSwaMoeConfig
from .mla_moe import Config as MlaMoeConfig
from .resnet import ResNetConfig
from .swa_moe import Config as SwaMoeConfig

__all__ = [
    "char_rnn",
    "resnet",
    "mla_moe",
    "swa_moe",
    "gated_swa_moe",
    "MlaMoeConfig",
    "GatedSwaMoeConfig",
    "SwaMoeConfig",
    "CharRNNConfig",
    "ResNetConfig",
    "init_params",
    "forward",
    "loss_fn",
    "sample",
    "make_batches",
    "encode_corpus",
]

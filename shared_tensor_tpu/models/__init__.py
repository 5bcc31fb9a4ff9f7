"""Workload models for the shared-tensor training story (BASELINE configs
2 and 4). The reference is model-agnostic parameter sync (SURVEY.md §5.7);
these models exist because its README names them as the intended workloads
(char-rnn, reference README.md:37) and benchmark arms (ResNet async-DP).
``mla_moe`` is the first transformer: a DeepSeek-V3-family decoder whose
table is of deployment size (its ``Config``, ``init_params``, ``forward`` and
``loss_fn`` stay under ``mla_moe.``: char-rnn's are the package's)."""

from . import char_rnn, mla_moe, resnet
from .char_rnn import (
    CharRNNConfig,
    encode_corpus,
    forward,
    init_params,
    loss_fn,
    make_batches,
    sample,
)
from .mla_moe import Config as MlaMoeConfig
from .resnet import ResNetConfig

__all__ = [
    "char_rnn",
    "resnet",
    "mla_moe",
    "MlaMoeConfig",
    "CharRNNConfig",
    "ResNetConfig",
    "init_params",
    "forward",
    "loss_fn",
    "sample",
    "make_batches",
    "encode_corpus",
]

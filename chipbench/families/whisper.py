"""Whisper with its log-mel front end (arXiv 2212.04356): the encoder-
decoder family's program configuration and operation count, plus the
two convolutions, beside the plain reference that has them."""
from __future__ import annotations

from chipbench.families import encdec
from chipbench.reference import whisper as reference

FIELDS = encdec.FIELDS + ("n_mels",)
data_key = encdec.data_key


def program_task(conf: dict):
    from repro.core import task_for_config
    from repro.models.encdec import EncDecConfig

    return task_for_config(EncDecConfig(**{k: conf[k] for k in FIELDS}), name=conf["name"])


def feature_width(conf: dict) -> int:
    return conf["n_mels"]


def forward_flops(conf: dict, frame_len, label_len):
    """The encoder-decoder's count plus the front end, which always runs
    the whole window: ``conv1`` at 2 * max_source frames, ``conv2`` at
    max_source."""
    D, M, S = conf["d_model"], conf["n_mels"], conf["max_source"]
    front = 2 * S * (2 * 3 * M * D) + S * (2 * 3 * D * D)
    return encdec.forward_flops(conf, frame_len, label_len) + front


def train_flops(conf: dict, frame_len, label_len):
    return 3 * forward_flops(conf, frame_len, label_len)

"""The published-width entry points, checked on the CPU without running
the model at full width (shapes come from ``jax.eval_shape``).

- ``--preset full`` builds ``rnnt-librispeech`` at its published widths
  on a corpus at the config's own feature dim and vocab;
- ``--preset tiny`` / ``--task asr-rnnt`` keep their corpus and their
  losses bit for bit;
- the compile-cache helper and ``chip_smoke.py``'s refusal of a
  machine without a TPU.
"""

import importlib.util
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.task import arch_task, default_corpus, get_task

ROOT = Path(__file__).resolve().parents[1]
PUBLISHED_N_PARAMS = 105_333_760


@pytest.fixture(scope="module")
def full_task():
    return arch_task("rnnt-librispeech", full=True)


def test_full_preset_corpus_matches_config(full_task):
    cfg = full_task.bundle.config
    assert (cfg.feat_dim, cfg.vocab, cfg.enc_hidden) == (128, 4096, 1152)
    corpus = full_task.make_corpus(0)
    assert corpus.cfg.feat_dim == cfg.feat_dim
    assert corpus.cfg.vocab_size == cfg.vocab
    assert corpus.arena_features.shape[-1] == cfg.feat_dim
    assert 0 < corpus.arena_labels.max() < cfg.vocab


def test_full_preset_corpus_takes_overrides(full_task):
    corpus = full_task.make_corpus(0, max_label_len=40)
    assert (corpus.u_max, corpus.t_max) == (40, 80)


def test_full_preset_n_params(full_task):
    shapes = jax.eval_shape(full_task.bundle.init, jax.random.PRNGKey(0))
    assert full_task.bundle.param_count(shapes) == PUBLISHED_N_PARAMS


def test_tiny_and_arch_presets_keep_the_default_corpus():
    assert get_task("asr-rnnt").make_corpus is default_corpus
    assert arch_task("rnnt-librispeech").make_corpus is default_corpus


# Losses of `train --rounds 3 --clients 4 --batch 2 --data-limit 4` on
# the CPU, recorded before --preset full existed and again when the LSTM
# backward began to sum dW_hh in one matmul after its time loop (the
# third round's loss moved in its seventh digit): both spellings of the
# container-scale run must reproduce them exactly.
TINY_LOSSES = [9.611196517944336, 9.723388671875, 7.515156269073486]


@pytest.mark.parametrize("argv", [["--preset", "tiny"], ["--task", "asr-rnnt"]])
def test_tiny_preset_losses_unchanged(argv, tmp_path, monkeypatch, capsys):
    from repro.launch import train

    out = tmp_path / "hist.json"
    monkeypatch.setattr(sys, "argv", [
        "train", *argv, "--rounds", "3", "--eval-every", "0", "--clients", "4",
        "--batch", "2", "--data-limit", "4", "--no-prefetch", "--out", str(out),
    ])
    train.main()
    capsys.readouterr()
    assert json.loads(out.read_text())["loss"] == TINY_LOSSES


def test_compile_cache_respects_the_environment(monkeypatch):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_has_one_fixed_path_in_the_checkout(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.use_compile_cache() is None  # CPU: no cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_chip_smoke_refuses_a_machine_without_tpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "no TPU" in out
    assert '"ok"' not in out
    np.testing.assert_equal(smoke.N_PARAMS, PUBLISHED_N_PARAMS)

"""The harness end to end on a tiny Whisper cell (log-mel front end) on
the CPU: the program's round engine against the plain reference, and
the fault the comparison has to catch. The cell lives under its own
data root, ``data_whisper/``."""
import json
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.calibrate import half_batch

DATA = Path(__file__).resolve().parent / "data_whisper"
CPU_PEAKS = {"cpu": {"source": "test", "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


@pytest.fixture
def run_whisper(capsys):
    def go(plant=None):
        rc = harness.main(["--workload", "tiny-whisper.t", "--seed", str(2**33 + 7),
                           "--seconds", "0.2", "--trace", "0"], root=DATA, require_tpu=False,
                          plant=plant, peaks=CPU_PEAKS)
        out = capsys.readouterr().out.strip().splitlines()
        return rc, (json.loads(out[-1]) if rc == 0 else None)

    return go


def test_tiny_whisper_run_is_correct(run_whisper):
    rc, result = run_whisper()
    assert rc == 0
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    for c in result["checks"].values():
        assert c["value"] <= c["limit"]


def test_tiny_whisper_half_batch_is_caught(run_whisper):
    rc, result = run_whisper(plant=half_batch)
    assert rc == 0 and result["correct"] is False
    assert result["checks"]["update_gap"]["value"] > result["checks"]["update_gap"]["limit"]

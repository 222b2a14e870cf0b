"""chip_smoke.py's phases on the CPU, at ~2k docs on port 0.

The chip run itself refuses the CPU; here its phases run directly: REST
`_bulk`/`_search` on the 1-shard index and on the 4-shard mesh over virtual
devices, every answer checked against the oracle and the device launches
counted. The checks themselves are shown to catch a bad answer and a
request the device did not serve.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

N_DOCS = 2_000


@pytest.fixture
def planner_off(monkeypatch):
    monkeypatch.setenv("ESTPU_EXEC_PLANNER", "0")


@pytest.mark.parametrize("n_shards", [1, 4])
def test_phases_serve_from_the_device_at_parity(planner_off, n_shards):
    out = chip_smoke.run(N_DOCS, seed=13, n_shards=n_shards)
    assert out["docs"] == N_DOCS
    assert out["requests"] == chip_smoke.N_QUERIES
    assert out["hbm_ledger_bytes"] > 0
    if n_shards > 1:
        assert out["mesh"]["served"] == chip_smoke.N_QUERIES
        assert len(out["mesh"]["devices"]) == n_shards


def test_parity_check_catches_a_wrong_answer(planner_off, monkeypatch):
    expected = chip_smoke.Corpus.expected

    def scaled(self, body):
        ids, scores, total = expected(self, body)
        return ids, scores * np.float32(1.001), total

    monkeypatch.setattr(chip_smoke.Corpus, "expected", scaled)
    with pytest.raises(chip_smoke.SmokeFailure, match="parity mismatch"):
        chip_smoke.run(N_DOCS, seed=13, n_shards=1)


def test_launch_check_catches_oracle_routing(monkeypatch):
    """With the planner on, its oracle exploration serves early requests
    on the CPU: the launch count must refuse them."""
    monkeypatch.setenv("ESTPU_EXEC_PLANNER", "1")
    with pytest.raises(chip_smoke.SmokeFailure, match="launched nothing"):
        chip_smoke.run(N_DOCS, seed=13, n_shards=1)


def test_refuses_the_cpu(capsys):
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.require_tpu(1)
    assert chip_smoke.main(["--docs", str(N_DOCS)]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)

"""Smoke tests for the three load tools that no cell drives yet
(tools/txload.py, lightload.py, dasload.py and its --pc track): each
runs alone at its smallest size and its own counts are held. No rate,
no latency."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _txload(rec):
    assert 0 < rec["committed"] <= rec["accepted"] <= rec["submitted"]


def _lightload(rec):
    assert rec["heights_committed"] > 0
    # no client is left with a header it could not verify
    assert rec["clients_served"] == rec["clients"]
    assert rec["http_stream_lines"] > 0
    assert rec["http_stream_verified"] == rec["http_stream_lines"]
    assert rec["http_stream_errors"] == []
    assert rec["max_verify_calls_per_height"] == 1


def _dasload(rec):
    assert rec["heights_committed"] > 0
    assert rec["honest"]["clients_confident_min"] == rec["honest"]["clients"]
    assert rec["http_samples_ok"] == rec["http_samples"]
    assert rec["http_errors"] == []
    # a client's draws are seeded by height and root, so WHICH clients
    # draw a withheld chunk differs from run to run; every one that did
    # detected it, and every withheld chunk served was a failed sample
    w = rec["withholding"]
    assert w["clients_detected_withholding"] > 0
    assert (w["clients_detected_withholding"] + w["clients_confident"]
            == w["clients"])
    assert w["samples"] - w["samples_ok"] == rec["withheld_hits"]


def _dasload_pc(rec):
    assert rec["header_root_binds_pc"] is True
    assert rec["honest"]["clients_confident_min"] == rec["honest"]["clients"]
    assert rec["http_samples_ok"] == rec["http_samples"]
    assert rec["http_errors"] == []
    # on this track a client draws distinct columns, more of them than
    # are left when m_c+1 are withheld: detection is every client's
    for leg in ("withholding", "lying_encoder"):
        assert rec[leg]["clients_detected"] == rec[leg]["clients"]
        assert rec[leg]["clients_confident"] == 0
    assert rec["oneD_blind_confident_fraction"] == 1.0


@pytest.mark.parametrize("tool,argv,check", [
    ("txload", ["--clients", "4", "--duration", "2"], _txload),
    ("lightload", ["--clients", "8", "--duration", "3", "--workers", "2",
                   "--http-streams", "1"], _lightload),
    ("dasload", ["--clients", "8", "--duration", "3", "--http-samples", "2",
                 "--codec-mb", "0.1"], _dasload),
    ("dasload", ["--pc", "--clients", "8", "--duration", "3",
                 "--http-samples", "2", "--open-iters", "2"], _dasload_pc),
], ids=["txload", "lightload", "dasload", "dasload-pc"])
def test_load_tool_runs_alone(tool, argv, check, tmp_path):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", f"{tool}.py"), *argv],
        capture_output=True, text=True, timeout=60, cwd=str(tmp_path),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr[-2000:]
    check(json.loads(p.stdout))

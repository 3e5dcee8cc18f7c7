"""Each cell, rehearsed on the CPU through the whole of run.py: the last line
parses, names platform cpu and carries the metrics BENCHMARK.json promises.
And the controls: with the timed path broken underneath, `correct` is false.

Each run is a child pinned to JAX_PLATFORMS=cpu (run.py owns its process).
Slow the first time (XLA:CPU compiles the value-form ladder, ~1 min a cell).
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def run(cell, *extra, seed=3, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = BENCH["command"] + ["--workload", cell, "--seed", str(seed),
                              "--seconds", "2", *extra]
    cmd[0] = sys.executable
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=timeout)


def last(p):
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def names(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_end_to_end_line(cell):
    p = run(cell, "--trace", "0", "--rehearse")
    line = last(p)
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    # each number compared beside its limit: the line's last key, and the
    # last lines on standard error
    assert list(line)[-1] == "checks" and line["checks"]
    assert all(c["ok"] for c in line["checks"].values())
    tail = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert [ln.split(":")[0] for ln in tail] == [
        "check " + name for name in line["checks"]]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == names("end_to_end", cell)
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_traced_line(cell):
    p = run(cell, "--trace", "1", "--rehearse")
    line = last(p)
    assert line["correct"] is True
    # the profiler's start and stop (seconds on the calling thread) are in no
    # span the per-layer metrics read
    assert "outside every span of the measured window" in p.stdout
    # off the chip no batch takes a device path and nothing runs on a
    # device: the device-trace metrics and RLC's share find nothing to read
    got = set(line["metrics"])
    assert got and got <= names("per_layer", cell)
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_a_verifier_that_accepts_everything_is_not_correct(cell):
    line = last(run(cell, "--trace", "0", "--rehearse", "--fault",
                    "accept_all", seed=4))
    assert line["correct"] is False and line["fault"] == "accept_all"
    assert not all(c["ok"] for c in line["checks"].values())


def _reaches_the_device(cell):
    with open(os.path.join(ROOT, "benchmark", "workloads", cell + ".json")) as f:
        return json.load(f)["traffic"]["device_from_lanes"] is not None


DEVICE_CELLS = [c for c in CELLS if _reaches_the_device(c)]


@pytest.mark.parametrize("cell", DEVICE_CELLS)
def test_control_a_dispatch_that_hides_the_device_is_not_correct(cell):
    """The rehearsal sends its batches down the device path (XLA:CPU); with
    the host_path fault every one of them runs on the host engine instead,
    and the path check, fed by the timed path's own counter, says so."""
    p = run(cell, "--trace", "0", "--rehearse", "--fault", "host_path", seed=5)
    line = last(p)
    assert line["correct"] is False and line["fault"] == "host_path"
    failing = [ln for ln in p.stdout.splitlines()
               if ln.strip().startswith("check ") and ln.rstrip().endswith("FAIL")]
    assert failing and all("on_a_host_path" in ln or "on_a_device_path" in ln
                           for ln in failing), failing


def test_off_the_chip_without_rehearse_exits_nonzero_and_prints_no_result():
    p = run(CELLS[0], "--trace", "0", timeout=300)
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
    assert "needs 1 TPU chip" in p.stderr


def test_an_unknown_cell_is_refused():
    p = run("no-such.cell", "--trace", "0", "--rehearse", timeout=60)
    assert p.returncode != 0 and "no workload named" in p.stderr

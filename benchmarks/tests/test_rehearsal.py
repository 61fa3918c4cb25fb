"""Each cell end to end at a tiny size, here on the CPU, through the same
code as a chip run; only the look for a chip is stubbed (drive.py).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Not part of tier-1.  A CPU run shows decisions and control flow, never a
time: no number printed here is a device metric.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELLS = ["1pool-drain", "1pool-quota-bound", "8pool-drain"]


def drive(*args, script=os.path.join(HERE, "drive.py"), cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p.returncode, (json.loads(lines[-1]) if lines else None), p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_and_its_control_is_not(cell):
    rc, out, err = drive("--workload", cell, "--seed", str(2 ** 31 + 11),
                         "--seconds", "5", "--trace", "0", "--control", "1")
    assert rc == 0, err[-2000:]
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"           # the comparison comes last
    assert out["metrics"]["setup_s"]["value"] > 0
    assert len(out["metrics"]) >= 2
    # every number compared is printed beside its limit on stderr too
    for name, (value, limit) in out["checks"].items():
        assert f"check {name} = " in err
        assert value <= limit
    # the control (fair-share guarantee broken), put in the program's
    # place and held to the same limits, must read not correct
    assert out["control_correct"] is False, out["control_checks"]
    assert any(v > lim for v, lim in out["control_checks"].values())
    assert "control in the program's place: correct = False" in err


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_a_broken_timed_path_reads_incorrect(fault):
    rc, out, err = drive("--fault", fault, "--workload", "1pool-drain",
                         "--seed", "3", "--seconds", "4", "--trace", "0")
    assert rc == 0, err[-2000:]
    assert out["correct"] is False
    bad = {k for k, (v, lim) in out["checks"].items() if v > lim}
    assert bad & {"set_gap", "host_gap"}, out["checks"]


def test_without_a_tpu_there_is_no_result():
    rc, out, err = drive("--workload", "1pool-drain", "--seed", "1",
                         "--seconds", "5", "--trace", "0",
                         script=os.path.join(BENCH, "run.py"))  # no stub
    assert rc != 0 and out is None
    assert "not a TPU" in err


def test_alone_in_a_directory_there_is_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, out, _err = drive("--workload", "1pool-drain", "--seed", "1",
                          "--seconds", "5", "--trace", "0",
                          script=str(tmp_path / "benchmarks" / "run.py"),
                          cwd=str(tmp_path))
    assert rc != 0 and out is None

"""Crash-point recovery matrix wiring (sim/crashpoint.py; the
CrashMonkey/ALICE-style harness behind ``python -m cook_tpu.sim
--crashpoints``, docs/ROBUSTNESS.md "WAL v2").

Tier-1 smokes a reduced matrix — every leg runs, fault sites are
strided and intra-frame cuts reduced to boundaries — and asserts zero
violations plus the coverage floor (each leg actually produced cases).
The full matrix at default scale, including the peer-repair path over
real socket replication, soaks under ``-m slow``."""

import json
import subprocess
import sys

import pytest

from cook_tpu.sim.crashpoint import (
    DISK_FAULT_POINTS,
    build_ops,
    run_crashpoints,
)


class TestSmoke:
    def test_reduced_matrix_recovers_everywhere(self, tmp_path):
        res = run_crashpoints(n_jobs=2, stride=2, cuts_per_line=1,
                              use_replication=False,
                              workdir=str(tmp_path))
        assert res.ok, res.summary()
        # coverage floor: every leg ran real cases
        legs = res.summary()["legs"]
        n_ops = len(build_ops(2))
        assert legs["fault-site"] == len(DISK_FAULT_POINTS) * (
            (n_ops + 1) // 2)
        assert legs["byte-boundary"] > 0
        assert legs["corruption"] > 0
        assert legs["checkpoint"] >= 3
        assert legs["launch-ack"] == 2      # refund and adopt

    def test_workload_script_is_deterministic(self):
        assert build_ops(3) == build_ops(3)


class TestCli:
    def test_sim_crashpoints_exit_zero_and_summary(self):
        proc = subprocess.run(
            [sys.executable, "-m", "cook_tpu.sim", "--crashpoints",
             "--jobs", "2", "--crashpoint-stride", "3"],
            capture_output=True, text=True, timeout=300,
            env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stdout + proc.stderr
        summary = json.loads(proc.stdout)
        assert summary["ok"] and summary["violations"] == []


@pytest.mark.slow
class TestSoak:
    def test_full_matrix_with_peer_repair(self, tmp_path):
        res = run_crashpoints(n_jobs=5, stride=1, cuts_per_line=3,
                              use_replication=True,
                              workdir=str(tmp_path))
        assert res.ok, res.summary()

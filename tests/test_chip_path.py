"""The device boundary's honesty rules (ISSUE 21): chip_smoke.py refuses
to pass without a TPU, the compile cache is placed by one rule, a kernel
that cannot build raises instead of degrading, the process says where it
runs, and shard workers never slide to the CPU."""

import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from cook_tpu.ops import telemetry
from cook_tpu.utils.flight import recorder as flight_recorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestChipSmokeRefuses:
    def _run(self, cwd, script):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                              capture_output=True, text=True, timeout=120)

    def test_cpu_platform_exits_nonzero_before_loading(self):
        p = self._run(REPO, os.path.join(REPO, "chip_smoke.py"))
        assert p.returncode != 0
        assert "not a TPU" in p.stderr
        assert "refusing to run the load" in p.stderr
        # no result line, and the daemon child never started
        assert p.stdout.strip() == ""
        assert "daemon" not in p.stderr

    def test_alone_without_the_program_exits_nonzero(self, tmp_path):
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        p = self._run(str(tmp_path), str(tmp_path / "chip_smoke.py"))
        assert p.returncode != 0
        assert p.stdout.strip() == ""
        assert "no cook_tpu package" in p.stderr


class TestChipSmokeResultLine:
    def test_last_line_has_exactly_the_contract_keys(self):
        import importlib.util
        import json
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        probe = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                 "versions": {"jax": "0.9.0"}}
        line = mod.result_line(True, probe)
        assert "\n" not in line
        assert json.loads(line) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
        assert json.loads(mod.result_line(False, probe))["ok"] is False


class TestCompilationCachePlacement:
    """env set -> nothing set in code; unset -> config, else .jax_cache
    (on a TPU only)."""

    @pytest.fixture
    def updates(self, monkeypatch):
        calls = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.__setitem__(k, v))
        monkeypatch.setattr(telemetry.os, "makedirs",
                            lambda *a, **kw: None)
        return calls

    def test_env_set_sets_no_directory_in_code(self, monkeypatch, updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        got = telemetry.enable_compilation_cache("/from/config")
        assert got == "/placed/outside"
        assert "jax_compilation_cache_dir" not in updates
        # the floors may still be dropped
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_unset_uses_configured_dir(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert telemetry.enable_compilation_cache("/from/config") \
            == "/from/config"
        assert updates["jax_compilation_cache_dir"] == "/from/config"

    def test_unset_and_unconfigured_defaults_by_platform(self, monkeypatch,
                                                         updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert telemetry.DEFAULT_CACHE_DIR == os.path.join(REPO,
                                                           ".jax_cache")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert telemetry.enable_compilation_cache("") \
            == telemetry.DEFAULT_CACHE_DIR
        assert updates["jax_compilation_cache_dir"] \
            == telemetry.DEFAULT_CACHE_DIR
        updates.clear()
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert telemetry.enable_compilation_cache("") is None
        assert updates == {}


class TestBuildErrorVsRuntimeFault:
    def test_first_use_failure_is_a_build_error(self):
        def bad(x):
            raise TypeError("cannot trace this")
        fn = telemetry.instrument_jit("test.bad", jax.jit(bad))
        with pytest.raises(telemetry.KernelBuildError, match="test.bad"):
            fn(jnp.zeros(4))

    def test_failure_after_a_success_is_a_runtime_fault(self):
        state = {"fail": False}

        def flaky(x):
            def cb(v):
                if state["fail"]:
                    raise RuntimeError("device fault")
                return v
            return jax.pure_callback(cb, jax.ShapeDtypeStruct((4,),
                                                              x.dtype), x)
        fn = telemetry.instrument_jit("test.flaky", jax.jit(flaky))
        jax.block_until_ready(fn(jnp.zeros(4)))
        state["fail"] = True
        with pytest.raises(Exception) as exc:
            jax.block_until_ready(fn(jnp.zeros(4)))
        assert not isinstance(exc.value, telemetry.KernelBuildError)
        # a NEW shape is a new executable: its first failure is a build
        # error again
        with pytest.raises(telemetry.KernelBuildError):
            jax.block_until_ready(fn(jnp.zeros(8)))

    def test_warmup_failure_fails_the_boot(self, monkeypatch):
        from cook_tpu.config import Config
        from cook_tpu.sched import Scheduler
        from cook_tpu.sched.fused import FusedCycleDriver
        from cook_tpu.state import Store

        def boom(self, **kw):
            raise telemetry.KernelBuildError("fused.pool_cycle",
                                             ValueError("no lowering"))
        monkeypatch.setattr(FusedCycleDriver, "warmup", boom)
        cfg = Config()
        cfg.pipeline.warmup_tasks = cfg.pipeline.warmup_hosts = 64
        with pytest.raises(telemetry.KernelBuildError):
            Scheduler(Store(), cfg, [], rank_backend="tpu")

    def test_cycle_thread_stops_and_reports_a_build_error(self,
                                                          monkeypatch):
        import threading

        from cook_tpu.config import Config
        from cook_tpu.sched import Scheduler
        from cook_tpu.state import Store
        cfg = Config()
        cfg.match_interval_seconds = 0.01
        sched = Scheduler(Store(), cfg, [], rank_backend="tpu")
        err = telemetry.KernelBuildError("fused.pool_cycle",
                                         ValueError("no lowering"))

        def boom():
            raise err
        monkeypatch.setattr(sched, "step_cycle", boom)
        seen = threading.Event()
        sched.on_fatal = lambda exc: seen.set()
        sched.run()
        try:
            assert seen.wait(5.0)
            assert sched.fatal_error is err
            assert sched._stop.is_set()
        finally:
            sched.shutdown()


class TestDeviceBlock:
    def test_health_and_cycle_records_say_where_they_run(self):
        from cook_tpu.cluster import FakeCluster, FakeHost
        from cook_tpu.config import Config
        from cook_tpu.rest.api import CookApi
        from cook_tpu.sched import Scheduler
        from cook_tpu.state import Job, Resources, Store
        store = Store()
        hosts = [FakeHost("h0", capacity=Resources(cpus=4.0, mem=4096.0))]
        sched = Scheduler(store, Config(), [FakeCluster("f", hosts)],
                          rank_backend="tpu")
        want = {"platform": jax.devices()[0].platform,
                "device_kind": jax.devices()[0].device_kind,
                "count": len(jax.devices())}
        assert {k: sched.device[k] for k in want} == want
        store.create_jobs([Job(uuid="00000000-0000-0000-0000-000000000001",
                               user="u", command="true",
                               resources=Resources(cpus=1.0, mem=64.0))])
        sched.step_cycle()
        assert flight_recorder.recent(1)[-1]["device"] == want
        health = CookApi(store, scheduler=sched).debug_health()
        assert {k: health["device"][k] for k in want} == want
        assert CookApi(store).debug_health()["device"] is None
        # the numpy reference path holds no JAX device and says so
        ref = Scheduler(Store(), Config(), [], rank_backend="cpu")
        assert ref.device["platform"] == "numpy"


class TestShardWorkerChips:
    SPEC = {"role": "sched", "cfg": {}}

    @pytest.fixture(autouse=True)
    def tpu_env(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)

    @pytest.mark.parametrize("platforms", [None, "tpu", "tpu,cpu"])
    def test_one_chip_per_worker(self, monkeypatch, platforms):
        from cook_tpu.sched import shard
        if platforms is not None:  # the chip machine ships "tpu,cpu"
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        monkeypatch.setattr(shard, "_tpu_chips", lambda: 4)
        monkeypatch.setattr(shard, "_holds_jax_backend", lambda: False)
        envs = shard._worker_chip_env(self.SPEC, 2)
        assert [e["TPU_VISIBLE_DEVICES"] for e in envs] == ["0", "1"]
        assert all(e["JAX_PLATFORMS"] == "tpu" for e in envs)
        assert len({e["TPU_MESH_CONTROLLER_PORT"] for e in envs}) == 2

    def test_fewer_chips_than_shards_is_refused(self, monkeypatch):
        from cook_tpu.sched import shard
        monkeypatch.setattr(shard, "_tpu_chips", lambda: 1)
        monkeypatch.setattr(shard, "_holds_jax_backend", lambda: False)
        with pytest.raises(shard.ShardPlacementError, match="2 TPU chips"):
            shard._worker_chip_env(self.SPEC, 2)

    def test_parent_holding_the_backend_is_refused(self, monkeypatch):
        from cook_tpu.sched import shard
        jax.devices()  # this process now holds its backend
        monkeypatch.setattr(shard, "_tpu_chips", lambda: 4)
        with pytest.raises(shard.ShardPlacementError,
                           match="initialised a JAX backend"):
            shard._worker_chip_env(self.SPEC, 2)

    def test_no_pinning_without_chips_or_without_jax(self, monkeypatch):
        from cook_tpu.sched import shard
        monkeypatch.setattr(shard, "_tpu_chips", lambda: 0)
        assert shard._worker_chip_env(self.SPEC, 2) == [{}, {}]
        monkeypatch.setattr(shard, "_tpu_chips", lambda: 4)
        cpu_spec = {"role": "sched", "cfg": {"rank_backend": "cpu"}}
        assert shard._worker_chip_env(cpu_spec, 2) == [{}, {}]
        assert shard._worker_chip_env({"role": "store"}, 2) == [{}, {}]
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert shard._worker_chip_env(self.SPEC, 2) == [{}, {}]

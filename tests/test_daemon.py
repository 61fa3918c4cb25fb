"""Process-shell tests: ``python -m cook_tpu`` boots config -> store ->
election -> clusters -> scheduler -> REST and exits per the supervisor
contract (VERDICT r1 #5; reference: components.clj:345-365 -main,
mesos.clj:153-328 leader lifecycle).

Two real processes contend for the same election lock: the follower 307s
leader-only requests, killing the leader fails over, /shutdown-leader makes
the new leader exit nonzero (supervisor restart contract)."""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_config(tmp_path, node: str, election_dir) -> str:
    conf = {
        "host": "127.0.0.1",
        "port": 0,
        "data_dir": str(tmp_path / f"data-{node}"),
        "election_dir": str(election_dir),
        "admins": ["admin"],
        "clusters": [{"factory": "cook_tpu.cluster.fake.factory",
                      "kwargs": {"name": f"fake-{node}", "n_hosts": 2}}],
        # cpu backend: the numpy reference path, so the daemon subprocess
        # starts without a JAX backend (and would not contend for a chip)
        "scheduler": {"rank_backend": "cpu", "cycle_mode": "split",
                      "match_interval_seconds": 0.1,
                      "rank_interval_seconds": 0.1},
    }
    path = tmp_path / f"cook-{node}.json"
    path.write_text(json.dumps(conf))
    return str(path)


def spawn(config_path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               PYTHONUNBUFFERED="1")
    return subprocess.Popen(
        [sys.executable, "-m", "cook_tpu", "--config", config_path, *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO, env=env)


def wait_serving(proc, timeout=30) -> str:
    """Read the daemon banner; returns the node URL."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise AssertionError(
                    f"daemon exited rc={proc.returncode} before serving")
            time.sleep(0.05)
            continue
        if line.startswith("cook_tpu: serving "):
            return line.split()[2]
    raise AssertionError("daemon did not start serving in time")


def get(url, timeout=5, redirect=True):
    class NoRedirect(urllib.request.HTTPRedirectHandler):
        def redirect_request(self, *a, **kw):
            return None
    opener = urllib.request.build_opener() if redirect else \
        urllib.request.build_opener(NoRedirect)
    req = urllib.request.Request(url, headers={"X-Cook-User": "admin"})
    return opener.open(req, timeout=timeout)


def post(url, payload=None, timeout=5):
    req = urllib.request.Request(
        url, data=json.dumps(payload or {}).encode(),
        headers={"X-Cook-User": "admin", "Content-Type": "application/json"},
        method="POST")
    return urllib.request.urlopen(req, timeout=timeout)


def wait_leader(url, timeout=20) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with get(f"{url}/info") as r:
                if json.load(r).get("leader"):
                    return True
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.2)
    return False


@pytest.fixture
def procs():
    running = []
    yield running
    for p in running:
        if p.poll() is None:
            p.kill()
        p.wait(timeout=10)


class TestDaemon:
    def test_lifecycle_submit_and_clean_exit(self, tmp_path, procs):
        cfg = write_config(tmp_path, "a", tmp_path)
        p = spawn(cfg)
        procs.append(p)
        url = wait_serving(p)
        assert wait_leader(url), "single node must take leadership"
        # submit through REST; the wall-clock cycle threads launch it
        with post(f"{url}/jobs", {"jobs": [{
                "uuid": "00000000-0000-0000-0000-00000000da3e",
                "command": "true", "cpus": 1.0, "mem": 64.0}]}) as r:
            assert r.status in (200, 201)
        deadline = time.time() + 15
        state = None
        while time.time() < deadline:
            with get(f"{url}/jobs/00000000-0000-0000-0000-00000000da3e") as r:
                state = json.load(r)["status"]
            if state == "running":
                break
            time.sleep(0.2)
        assert state == "running", state
        # SIGTERM is a clean supervisor stop: exit 0
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=15) == 0

    def test_ha_failover_and_shutdown_leader(self, tmp_path, procs):
        election = tmp_path
        pa = spawn(write_config(tmp_path, "a", election))
        procs.append(pa)
        url_a = wait_serving(pa)
        assert wait_leader(url_a)

        pb = spawn(write_config(tmp_path, "b", election))
        procs.append(pb)
        url_b = wait_serving(pb)
        # follower 307-redirects leader-only endpoints at the leader
        deadline = time.time() + 10
        status, location = None, None
        while time.time() < deadline:
            try:
                with get(f"{url_b}/queue", redirect=False) as r:
                    status = r.status
            except urllib.error.HTTPError as e:
                status, location = e.code, e.headers.get("Location", "")
                if status == 307:
                    break
            time.sleep(0.2)
        assert status == 307, status
        assert location.startswith(url_a)

        # kill the leader; the follower must take over
        pa.kill()
        pa.wait(timeout=10)
        assert wait_leader(url_b, timeout=20), "follower did not take over"

        # /shutdown-leader resigns -> nonzero exit (supervisor restart)
        try:
            with post(f"{url_b}/shutdown-leader") as r:
                assert r.status == 200
        except (urllib.error.URLError, OSError):
            pass  # the node may die mid-response
        assert pb.wait(timeout=15) == 1

    def test_api_only_never_leads(self, tmp_path, procs):
        election = tmp_path
        pa = spawn(write_config(tmp_path, "a", election))
        procs.append(pa)
        url_a = wait_serving(pa)
        assert wait_leader(url_a)
        pb = spawn(write_config(tmp_path, "b", election), "--api-only")
        procs.append(pb)
        url_b = wait_serving(pb)
        with get(f"{url_b}/info") as r:
            assert json.load(r).get("leader") is False
        # even after the leader dies, an api-only node stays a follower
        pa.kill()
        pa.wait(timeout=10)
        time.sleep(1.5)
        with get(f"{url_b}/info") as r:
            assert json.load(r).get("leader") is False
        assert pb.poll() is None


class TestCrashRecovery:
    def test_kill9_mid_flight_restart_resumes(self, tmp_path, procs):
        """SIGKILL the leader with submitted work in the journal; a fresh
        daemon over the same data_dir replays the store and keeps
        scheduling (the reference's exit-and-restart recovery contract:
        all state re-read on takeover, mesos.clj:296-313)."""
        election = tmp_path / "election"
        election.mkdir()
        cfg = write_config(tmp_path, "crash", election)
        p1 = spawn(cfg)
        procs.append(p1)
        url = wait_serving(p1)
        assert wait_leader(url)
        # jobs that outlive the crash (fake-cluster tasks run "forever")
        with post(f"{url}/jobs", {"jobs": [
                {"command": "sleep 999", "cpus": 1, "mem": 64}
                for _ in range(4)]}) as r:
            uuids = json.load(r)["jobs"]
        deadline = time.time() + 15
        while time.time() < deadline:
            with get(f"{url}/jobs/{uuids[0]}") as r:
                if json.load(r)["state"] == "running":
                    break
            time.sleep(0.1)
        os.kill(p1.pid, signal.SIGKILL)   # no clean shutdown, no snapshot
        p1.wait(timeout=10)

        p2 = spawn(cfg)
        procs.append(p2)
        url2 = wait_serving(p2)
        assert wait_leader(url2)
        # the journal replayed: all four jobs are back
        for uuid in uuids:
            with get(f"{url2}/jobs/{uuid}") as r:
                job = json.load(r)
            assert job["state"] in ("waiting", "running")
        # and the scheduler still schedules new work after recovery
        with post(f"{url2}/jobs", {"jobs": [
                {"command": "sleep 999", "cpus": 1, "mem": 64}]}) as r:
            [fresh] = json.load(r)["jobs"]
        deadline = time.time() + 15
        state = None
        while time.time() < deadline:
            with get(f"{url2}/jobs/{fresh}") as r:
                state = json.load(r)["state"]
            if state == "running":
                break
            time.sleep(0.1)
        assert state == "running"


def test_build_scheduler_config_task_constraints_and_planes():
    """Daemon JSON -> Config: nested task_constraints and pool-regex
    planes (reference: config.clj :task-constraints + pools planes)."""
    from cook_tpu.daemon import build_scheduler_config
    cfg = build_scheduler_config({
        "task_constraints": {"docker_parameters_allowed": ["env"],
                             "max_ports": 4,
                             "unknown_key_ignored": True},
        "default_containers": [
            {"pool-regex": "^p$", "container": {"image": "i:1"}},
            {"pool-regex": ".*"}],  # malformed: skipped, not fatal
        "valid_gpu_models": [
            {"pool-regex": "^gpu", "valid-models": ["a100"]}],
    })
    assert cfg.task_constraints.docker_parameters_allowed == ["env"]
    assert cfg.task_constraints.max_ports == 4
    assert cfg.default_container_for_pool("p") == {"image": "i:1"}
    assert cfg.default_container_for_pool("other") is None
    assert cfg.gpu_models_for_pool("gpu-a") == ["a100"]


def test_build_scheduler_config_refuses_wire_bytes_in_planes():
    """A pool-default env/container embedding NUL or the \\x1e wire
    separator fails the BOOT (like a bad pool-regex) — otherwise every
    job in the pool would fail opaquely at launch time."""
    import pytest
    from cook_tpu.daemon import build_scheduler_config
    with pytest.raises(ValueError, match="control characters"):
        build_scheduler_config({"default_envs": [
            {"pool-regex": ".*", "env": {"A": "x\x1eB=y"}}]})
    with pytest.raises(ValueError, match="misconfigured|control"):
        build_scheduler_config({"default_containers": [
            {"pool-regex": ".*",
             "container": {"image": "img\x00"}}]})
    # clean planes still load
    cfg = build_scheduler_config({"default_envs": [
        {"pool-regex": ".*", "env": {"A": "line1\nline2"}}]})
    assert cfg.default_env_for_pool("x") == {"A": "line1\nline2"}


def test_build_scheduler_config_validates_matcher_knobs():
    """JSON-configured matcher knobs go through setattr, which bypasses
    dataclass construction — the loader must re-validate so a typo'd
    backend or auto_packing fails the boot, not every match cycle."""
    import pytest
    from cook_tpu.daemon import build_scheduler_config
    cfg = build_scheduler_config({"default_matcher": {
        "auto_packing": "tight", "auto_large_j_threshold": 500}})
    assert cfg.default_matcher.auto_packing == "tight"
    with pytest.raises(ValueError, match="auto_packing"):
        build_scheduler_config({"default_matcher": {
            "auto_packing": "Tight"}})
    with pytest.raises(ValueError, match="backend"):
        build_scheduler_config({"default_matcher": {
            "backend": "tpu-watrfill"}})
    # the removed backend migrates instead of failing
    cfg = build_scheduler_config({"default_matcher": {
        "backend": "tpu-auction-pallas"}})
    assert cfg.default_matcher.backend == "tpu-auction"
    # typo'd KEY also fails the boot (it would silently keep defaults)
    with pytest.raises(ValueError, match="auto_paking"):
        build_scheduler_config({"default_matcher": {
            "auto_paking": "tight"}})


def test_auction_pallas_deprecation_logged_and_counted(caplog):
    """The alias is a promise to operators: constructing it warns, counts
    ``cook_config_deprecated_total`` and rewrites to ``tpu-auction``."""
    import logging
    from cook_tpu.config import MatcherConfig
    from cook_tpu.utils.metrics import registry

    def count():
        return sum(v for lbl, v in registry.series("cook_config_deprecated")
                   if lbl.get("knob") == "matcher.backend"
                   and lbl.get("value") == "tpu-auction-pallas")
    n0 = count()
    with caplog.at_level(logging.WARNING):
        mc = MatcherConfig(backend="tpu-auction-pallas")
    assert mc.backend == "tpu-auction"
    assert any("DEPRECATED" in r.message for r in caplog.records)
    assert count() == n0 + 1


def test_build_scheduler_config_validates_storage_section():
    """The storage-integrity plane's conf section (docs/ROBUSTNESS.md
    "WAL v2") is boot-validated like the sections above: typo'd keys,
    non-boolean switches, and nonsense numerics fail the boot, and the
    hygiene-age knob lands on the module-level sweep default."""
    import pytest
    from cook_tpu.daemon import build_scheduler_config
    from cook_tpu.state import integrity

    before = integrity.HYGIENE_MIN_AGE_S
    try:
        cfg = build_scheduler_config({"storage": {
            "scrub_interval_seconds": 5,
            "scrub_chunk_bytes": 65536,
            "hygiene_min_age_seconds": 120}})
        assert cfg.storage.scrub_interval_seconds == 5.0
        assert cfg.storage.scrub_chunk_bytes == 65536
        assert integrity.HYGIENE_MIN_AGE_S == 120.0
        with pytest.raises(ValueError, match="scrub_chnk_bytes"):
            build_scheduler_config({"storage": {"scrub_chnk_bytes": 1}})
        with pytest.raises(ValueError, match="boolean"):
            build_scheduler_config({"storage": {
                "scrub_enabled": "false"}})
        with pytest.raises(ValueError, match="scrub_chunk_bytes"):
            build_scheduler_config({"storage": {"scrub_chunk_bytes": 0}})
        with pytest.raises(ValueError, match="repair_timeout_seconds"):
            build_scheduler_config({"storage": {
                "repair_timeout_seconds": 0}})
    finally:
        integrity.HYGIENE_MIN_AGE_S = before

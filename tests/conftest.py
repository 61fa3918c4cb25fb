"""Test bootstrap: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding tests run on
XLA's host-platform device virtualization (the driver separately dry-runs
the multi-chip path via __graft_entry__.dryrun_multichip).

``JAX_PLATFORMS`` is honoured by the installed JAX, and is set here
before the first ``import jax`` so the suite never reaches for a chip even
when the caller's environment names one; the config update below says
the same thing to a jax that some plugin imported first.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def lock_sanitizer():
    """Run the whole tier-1 suite under the dynamic lock-order sanitizer
    (cook_tpu/utils/locks.py, docs/ANALYSIS.md): every named-lock
    acquisition records its graph edge, and blocking syscalls (fsync /
    sleep / socket send+connect) are checked against the held-lock
    allowlist.  The teardown assert makes ANY acquisition-graph cycle,
    declared-rank inversion, or unallowlisted blocking-under-lock event
    anywhere in the run a tier-1 failure.

    COOK_LOCK_SANITIZER=0 opts out (e.g. when bisecting an unrelated
    failure); tests that deliberately construct violations use their own
    LockMonitor instance so this global stays meaningful."""
    from cook_tpu.utils import locks

    if os.environ.get("COOK_LOCK_SANITIZER", "1") == "0":
        yield
        return
    locks.monitor.arm_blocking_detector()
    try:
        yield
    finally:
        locks.monitor.disarm_blocking_detector()
        problems = locks.monitor.check()
        assert not problems, (
            "lock-order sanitizer violations during the run "
            "(utils/locks.py contract; docs/ANALYSIS.md):\n\n"
            + "\n\n".join(problems))
        # static-coverage contract (docs/ANALYSIS.md): every ordering
        # the dynamic sanitizer OBSERVED anywhere in this run must be
        # in the interprocedural analysis's static edge set — an
        # observed-only edge is a call-resolution gap that would let a
        # statically-invisible inversion ship.  (The reverse direction
        # — static edges tier-1 never drove — is the `cs lint
        # --lock-coverage` report, not a failure.)
        from cook_tpu.analysis.summaries import static_edge_families
        static = set(static_edge_families(wait=True) or [])
        observed = set(locks.monitor.observed_edges())
        missing = sorted(observed - static)
        assert not missing, (
            "lock orderings observed at runtime but missing from the "
            "static lock-edge set (cs lint --lock-coverage; a "
            "resolution gap in cook_tpu/analysis/callgraph.py): "
            + ", ".join(missing))

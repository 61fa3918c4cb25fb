"""The fused XLA cycle (sched/fused.py over
parallel/sharded.make_pool_cycle) is the only cycle the served path
runs; the split path (step_rank + step_match) and ops/reference_impl
are what it is compared against (ISSUE 30): launch decisions over a
driver matrix, the record every fused cycle leaves, the compact kernel
against the plain reference, the delta scatter's value codec at its
width edges, and the configurations the tree ships.

``make_cfg``, ``build_world``, ``churn``, ``decisions`` and ``drive`` are
the harness sched/shard.py and tests/test_sharded.py cite.
"""

import functools
import json
import re
from pathlib import Path

import numpy as np
import pytest

from cook_tpu.cluster import FakeCluster, FakeHost
from cook_tpu.config import Config, MatcherConfig
from cook_tpu.sched import Scheduler
from cook_tpu.state import Group, Job, Pool, Resources, Store
from cook_tpu.utils.flight import recorder as flight_recorder
from cook_tpu.utils.metrics import registry

REPO = Path(__file__).resolve().parent.parent


def counter_value(name, labels=None):
    """Sum of the counter's series whose labels include ``labels``
    (0.0 when absent)."""
    labels = labels or {}
    return sum(v for lbl, v in registry.series(name)
               if all(lbl.get(k) == want for k, want in labels.items()))


def launches_by_kernel():
    return {lbl["kernel"]: v
            for lbl, v in registry.series("cook_kernel_launches")}


# ---------------------------------------------------------------------------
# world builders (fixed uuids: two builds produce identical worlds)
# ---------------------------------------------------------------------------

def make_cfg(backend="auto", depth=0, resident=True, quantized=True,
             cycle_mode="fused"):
    cfg = Config()
    cfg.cycle_mode = cycle_mode
    cfg.default_matcher.backend = backend
    cfg.pipeline.depth = depth
    cfg.resident_pack = resident
    cfg.quantized_wire = quantized
    return cfg


def build_world(cfg, n_jobs=16, n_hosts=5, seed=3, cpus=16.0,
                gang_size=0, gang_min=0, gang_max=0):
    rng = np.random.default_rng(seed)
    store = Store()
    store.put_pool(Pool(name="default"))
    hosts = [FakeHost(hostname=f"h{i}",
                      capacity=Resources(cpus=cpus, mem=16384.0))
             for i in range(n_hosts)]
    sched = Scheduler(store, cfg, [FakeCluster("fake-1", hosts)],
                      rank_backend="tpu")
    jobs = []
    for i in range(n_jobs):
        j = Job(uuid=f"00000000-0000-0000-0000-{i:012d}",
                user=f"user{i % 3}", command="true", pool="default",
                priority=int(rng.integers(0, 100)),
                resources=Resources(cpus=float(rng.integers(1, 4)),
                                    mem=float(rng.integers(128, 1024))),
                submit_time_ms=1000 + i)
        jobs.append(j)
        store.create_jobs([j])
    if gang_size:
        members = [Job(uuid=f"00000000-0000-0000-0001-{i:012d}",
                       user="ganguser", command="true", group="g1",
                       resources=Resources(cpus=2.0, mem=256.0),
                       submit_time_ms=900)
                   for i in range(gang_size)]
        store.create_jobs(members, groups=[Group(
            uuid="g1", gang=True, gang_size=gang_size,
            gang_min=gang_min, gang_max=gang_max,
            jobs=[m.uuid for m in members])])
        jobs.extend(members)
    return store, sched, jobs


def decisions(store, jobs):
    out = {}
    for j in jobs:
        job = store.job(j.uuid)
        hosts = [store.instance(t).hostname for t in job.instances
                 if store.instance(t) is not None]
        out[j.uuid] = (job.state.value, tuple(sorted(hosts)))
    return out


def churn(store, wave, n=4, seed=11):
    rng = np.random.default_rng(seed + wave)
    fresh = [Job(uuid=f"00000000-0000-0000-{wave + 2:04d}-{i:012d}",
                 user=f"user{i % 3}", command="true", pool="default",
                 resources=Resources(cpus=float(rng.integers(1, 4)),
                                     mem=float(rng.integers(128, 512))),
                 submit_time_ms=5000 + wave * 100 + i)
             for i in range(n)]
    store.create_jobs(fresh)
    return fresh


def drive(cfg, cycles=4, split=False, **kw):
    """Drive a world through ``cycles`` cycles with churn plus one more.
    On the fused path the fallback must not be what produced the
    decisions: every cycle's record reads ``path == "fused"`` and
    ``cook_kernel_fallback_total`` stands still, whatever the kernel."""
    n0 = counter_value("cook_kernel_fallback")
    seq0 = flight_recorder.last_seq()
    store, sched, jobs = build_world(cfg, **kw)

    def step():
        if split:
            sched.step_rank()
            sched.step_match()
        else:
            sched.step_cycle()
    for w in range(cycles):
        step()
        jobs.extend(churn(store, w))
    step()
    if not split:
        paths = [r["path"] for r in flight_recorder.recent(cycles + 8)
                 if r["seq"] > seq0 and r["kind"] == "fused"]
        assert len(paths) == cycles + 1 and set(paths) == {"fused"}, paths
        assert counter_value("cook_kernel_fallback") == n0
    return decisions(store, jobs)


GANGS = {
    "none": {},
    "rigid": dict(gang_size=3),
    # min 2 of 4 on 5 hosts: places at >= min, grows later
    "elastic": dict(gang_size=4, gang_min=2, gang_max=4, cpus=8.0),
}
# another world (priorities, sizes) and a longer run: seven cycles cross
# more churn waves than the matrix's five
WORLDS = dict(GANGS, seed5=dict(seed=5, n_jobs=24, cycles=6))


@functools.lru_cache(maxsize=None)
def split_baseline(world):
    """The split driver's decisions for a world, computed once."""
    return drive(make_cfg(backend="cpu", cycle_mode="split"), split=True,
                 **WORLDS[world])


def assert_same(base, got):
    assert base == got, {k: (base[k], got.get(k))
                         for k in base if base[k] != got.get(k)}


# ---------------------------------------------------------------------------
# driver parity matrix
# ---------------------------------------------------------------------------

@pytest.mark.gang
class TestDriverParityMatrix:
    @pytest.mark.parametrize("quantized", [True, False])
    @pytest.mark.parametrize("resident", [True, False])
    @pytest.mark.parametrize("depth", [0, 2])
    @pytest.mark.parametrize("gang", sorted(GANGS))
    def test_fused_matches_split(self, gang, depth, resident, quantized):
        base = split_baseline(gang)
        got = drive(make_cfg(depth=depth, resident=resident,
                             quantized=quantized), **GANGS[gang])
        assert_same(base, got)

    @pytest.mark.parametrize("depth", [0, 2])
    def test_fused_matches_split_second_seed(self, depth):
        assert_same(split_baseline("seed5"),
                    drive(make_cfg(depth=depth), **WORLDS["seed5"]))

    @pytest.mark.parametrize("depth", [0, 2])
    def test_elastic_gang_places_at_min(self, depth):
        # capacity for only 2 members at once: a rigid 4-gang would wait
        # whole; the elastic min-2 gang must come up partial
        store, sched, jobs = build_world(
            make_cfg(depth=depth), n_jobs=0, n_hosts=2, cpus=4.0,
            gang_size=4, gang_min=2, gang_max=4)
        for _ in range(4):
            sched.step_cycle()
        live = [j for j in jobs
                if store.job(j.uuid).state.value == "running"]
        assert 2 <= len(live) <= 4, [store.job(j.uuid).state
                                     for j in jobs]

    @pytest.mark.parametrize("depth", [0, 2])
    def test_two_pools_in_one_group_with_a_gang(self, depth):
        """Two pools stacked in ONE dispatch group ([P = 2, T]), the
        second holding a gang that fits and one that never can (four
        whole-host members, three hosts): decisions equal the split
        driver's, pool slot by pool slot.  Everything else fits, so the
        pipelined driver's stale snapshot cannot reorder who runs."""
        def run(cfg, split):
            store = Store()
            for name in ("default", "other"):
                store.put_pool(Pool(name=name))
            clusters = [
                FakeCluster(f"fake-{pool}", [
                    FakeHost(hostname=f"{pool[0]}{i}", pool=pool,
                             capacity=Resources(cpus=8.0, mem=4096.0))
                    for i in range(3)])
                for pool in ("default", "other")]
            sched = Scheduler(store, cfg, clusters, rank_backend="tpu")
            jobs = []
            for guuid, n, cpus in (("fits", 2, 2.0), ("never", 4, 8.0)):
                members = [Job(uuid=f"00000000-0000-0000-{n:04d}-{i:012d}",
                               user="gang", command="true", group=guuid,
                               pool="other",
                               resources=Resources(cpus=cpus, mem=512.0),
                               submit_time_ms=900 + n)
                           for i in range(n)]
                store.create_jobs(members, groups=[Group(
                    uuid=guuid, gang=True, gang_size=n,
                    jobs=[m.uuid for m in members])])
                jobs.extend(members)
            for pool, tag in (("default", 7), ("other", 8)):
                singles = [Job(uuid=f"00000000-0000-0000-{tag:04d}-{i:012d}",
                               user=f"u{i % 2}", command="true", pool=pool,
                               resources=Resources(cpus=1.0, mem=128.0),
                               submit_time_ms=1000 + i) for i in range(16)]
                store.create_jobs(singles)
                jobs.extend(singles)
            seq0 = flight_recorder.last_seq()
            for _ in range(3):
                if split:
                    sched.step_rank()
                    sched.step_match()
                else:
                    sched.step_cycle()
            if not split:
                recs = [r for r in flight_recorder.recent(8)
                        if r["seq"] > seq0 and r["kind"] == "fused"]
                assert [r["path"] for r in recs] == ["fused"] * 3
                assert recs[0]["pools"] == 2, recs[0]
            return decisions(store, jobs)
        base = run(make_cfg(backend="cpu", cycle_mode="split"), True)
        got = run(make_cfg(depth=depth), False)
        assert_same(base, got)
        never = [u for u in base if u.startswith("00000000-0000-0000-0004")]
        fits = [u for u in base if u.startswith("00000000-0000-0000-0002")]
        assert all(got[u][1] == () for u in never)
        assert all(got[u][0] == "running" for u in fits)


# ---------------------------------------------------------------------------
# the cycle's record
# ---------------------------------------------------------------------------

class TestCycleRecord:
    @pytest.mark.parametrize("depth", [0, 2])
    def test_cycle_record_path_and_launches(self, depth):
        """``kernel_launches`` on a fused record is what /debug/cycles
        documents: ``fused.pool_cycle`` once a dispatched group plus the
        ``delta.apply`` / ``delta.append`` scatters, and nothing else."""
        store, sched, jobs = build_world(make_cfg(depth=depth))
        cycle_kernels = {"fused.pool_cycle", "delta.apply", "delta.append"}
        before = launches_by_kernel()
        seq0 = flight_recorder.last_seq()
        cycles = 3
        for w in range(cycles):
            sched.step_cycle()
            churn(store, w)
        recs = [r for r in flight_recorder.recent(cycles + 8)
                if r["seq"] > seq0 and r["kind"] == "fused"]
        assert [r["path"] for r in recs] == ["fused"] * cycles
        after = launches_by_kernel()
        moved = {k: after[k] - before.get(k, 0.0) for k in after
                 if after[k] != before.get(k, 0.0)}
        assert set(moved) <= cycle_kernels, moved
        assert sum(r["kernel_launches"] for r in recs) == \
            sum(moved.values()), (recs, moved)
        # one pool, one DRU mode: one group a dispatch, one dispatch a
        # cycle; the pipelined driver keeps one more in flight
        assert moved["fused.pool_cycle"] == cycles + (1 if depth else 0)
        assert all(r["kernel_launches"] >= 1 for r in recs)

    def test_dispatch_fetches_the_four_compact_outputs(self):
        store, sched, jobs = build_world(make_cfg(), gang_size=3)
        driver = sched._ensure_fused()
        staged = driver.stage(sched)
        assert len(staged.groups) == 1
        gd = driver.dispatch_group(staged.groups[0])
        assert len(gd.outs) == 4
        fetched = driver.fetch_group(gd)
        assert len(fetched) == 4
        cand_row, cand_assign, cand_qpos, n_queue = fetched
        assert cand_row.shape == cand_assign.shape == cand_qpos.shape
        assert int(n_queue[0]) == len(jobs)


# ---------------------------------------------------------------------------
# kernel-level parity against the plain reference
# ---------------------------------------------------------------------------

def _random_compact_inputs(seed=0, P=2, T=64, H=16, U=8, E=8, N=128):
    import jax.numpy as jnp
    from cook_tpu.ops.delta import (FLAG_ENQUEUE_OK, FLAG_LAUNCH_OK,
                                    FLAG_PENDING, FLAG_USER_FIRST,
                                    FLAG_VALID)
    from cook_tpu.parallel.sharded import CompactPoolCycleInputs
    rng = np.random.default_rng(seed)
    rows = np.stack([rng.permutation(np.arange(T))
                     for _ in range(P)]).astype(np.int32)
    pend = rng.random((P, T)) < 0.7
    uid = np.sort(rng.integers(0, U, (P, T)), axis=1)
    is_first = np.zeros((P, T), dtype=bool)
    is_first[:, 0] = True
    is_first[:, 1:] = uid[:, 1:] != uid[:, :-1]
    flags = (pend.astype(np.uint8) * FLAG_PENDING + FLAG_VALID
             + is_first.astype(np.uint8) * FLAG_USER_FIRST
             + (rng.random((P, T)) < 0.95).astype(np.uint8)
             * FLAG_ENQUEUE_OK
             + (rng.random((P, T)) < 0.9).astype(np.uint8)
             * FLAG_LAUNCH_OK)
    res_base = np.zeros((N, 4), dtype=np.float32)
    res_base[:, 0] = rng.integers(1, 4, N)
    res_base[:, 1] = rng.integers(1, 16, N) * 128.0
    res_base[:, 2] = (rng.random(N) < 0.1) * 1.0
    res_base[:, 3] = 1.0
    host_gpu = rng.random((P, H)) < 0.1
    host_blocked = rng.random((P, H)) < 0.1
    exc_rows = np.full((P, E), -1, dtype=np.int32)
    exc_rows[0, 0] = 3
    avail = rng.integers(0, 64, (P, H, 4)).astype(np.float32)
    avail[..., 1] *= 128.0      # mem in the jobs' units, so some fit
    inp = CompactPoolCycleInputs(
        rows=jnp.asarray(rows), flags=jnp.asarray(flags),
        res_base=jnp.asarray(res_base),
        disk_base=jnp.asarray(
            rng.integers(0, 4, N).astype(np.float32) * 10.0),
        tokens_u=jnp.full((P, U), np.inf, dtype=jnp.float32),
        shares_u=jnp.full((P, U, 3), 100.0, dtype=jnp.float32),
        quota_u=jnp.full((P, U, 4), np.inf, dtype=jnp.float32),
        num_considerable=jnp.full((P,), 32, dtype=jnp.int32),
        pool_quota=jnp.full((P, 4), np.inf, dtype=jnp.float32),
        group_quota=jnp.full((P, 4), np.inf, dtype=jnp.float32),
        group_id=jnp.zeros((P,), dtype=jnp.int32),
        host_gpu=jnp.asarray(host_gpu),
        host_blocked=jnp.asarray(host_blocked),
        exc_rows=jnp.asarray(exc_rows),
        exc_mask=jnp.asarray(rng.random((P, E, H)) < 0.5),
        avail=jnp.asarray(avail),
        capacity=jnp.asarray(
            avail + rng.integers(0, 8, (P, H, 4)).astype(np.float32)))
    return inp


def _reference_pool_cycle(inp, p, cap):
    """One pool of the compact cycle in plain numpy over
    ops/reference_impl: (queue rows, cand_row, cand_assign, cand_qpos).
    Quotas, tokens and pool caps are unbounded in these inputs, so
    admission is rank order, the enqueue / launch flags and the cap."""
    from cook_tpu.ops import reference_impl
    from cook_tpu.ops.delta import (FLAG_ENQUEUE_OK, FLAG_LAUNCH_OK,
                                    FLAG_PENDING, FLAG_USER_FIRST)
    rows = np.asarray(inp.rows)[p]
    flags = np.asarray(inp.flags)[p]
    T = len(rows)
    usage = np.asarray(inp.res_base)[rows]
    disk = np.asarray(inp.disk_base)[rows]
    pending = (flags & FLAG_PENDING) != 0
    user = np.cumsum((flags & FLAG_USER_FIRST) != 0) - 1
    shares_u = np.asarray(inp.shares_u)[p]
    quota_u = np.asarray(inp.quota_u)[p]
    users, shares, quotas = [], {}, {}
    for u in np.unique(user):
        pos = np.flatnonzero(user == u)
        name = f"user{u:03d}"
        users.append(reference_impl.UserTasks(
            name, pos.tolist(), usage[pos], pending[pos].tolist()))
        shares[name] = tuple(shares_u[u])
        quotas[name] = quota_u[u]
    ranked = [t for t, _dru in
              reference_impl.rank_by_dru(users, shares, quotas)]
    queue = [t for t in ranked if flags[t] & FLAG_ENQUEUE_OK]
    qpos = {t: i for i, t in enumerate(queue)}
    n_cons = int(np.asarray(inp.num_considerable)[p])
    admitted = [t for t in queue
                if flags[t] & FLAG_LAUNCH_OK][:min(n_cons, cap)]
    job_res = np.concatenate([usage[:, :3], disk[:, None]], axis=1)
    host_gpu = np.asarray(inp.host_gpu)[p]
    blocked = np.asarray(inp.host_blocked)[p]
    exc = {int(t): e for e, t in enumerate(np.asarray(inp.exc_rows)[p])
           if t >= 0}
    exc_mask = np.asarray(inp.exc_mask)[p]
    mask = np.stack([
        exc_mask[exc[t]] if t in exc
        else (host_gpu if job_res[t, 2] > 0 else ~host_gpu) & ~blocked
        for t in admitted]).reshape(len(admitted), len(host_gpu))
    assign = reference_impl.greedy_match(
        job_res[admitted], mask, np.asarray(inp.avail)[p],
        np.asarray(inp.capacity)[p])
    return (queue, admitted, assign.tolist(),
            [qpos[t] for t in admitted], T)


class TestKernelParity:
    @pytest.mark.parametrize("seed", [0, 7])
    def test_compact_cycle_matches_reference(self, seed):
        import jax
        from jax.sharding import Mesh
        from cook_tpu.parallel.mesh import POOL_AXIS
        from cook_tpu.parallel.sharded import make_pool_cycle
        cap = 32
        inp = _random_compact_inputs(seed=seed)
        mesh = Mesh(np.array(jax.devices()[:1]), (POOL_AXIS,))
        res = make_pool_cycle(mesh, considerable_cap=cap,
                              structured=True, compact=True)(inp)
        for p in range(inp.rows.shape[0]):
            queue, cand, assign, qpos, T = _reference_pool_cycle(
                inp, p, cap)
            assert cand, "nothing admitted: the inputs test nothing"
            n_queue = int(np.asarray(res.n_queue)[p])
            assert n_queue == len(queue)
            assert np.asarray(res.queue_rows)[p][:n_queue].tolist() == queue
            pad = [-1] * (cap - len(cand))
            assert np.asarray(res.cand_row)[p].tolist() == cand + pad
            assert np.asarray(res.cand_assign)[p].tolist() == assign + pad
            assert np.asarray(res.cand_qpos)[p].tolist() == qpos + pad
            assert any(h >= 0 for h in assign) and -1 in assign


# ---------------------------------------------------------------------------
# the delta scatter's value codec
# ---------------------------------------------------------------------------

class TestDeltaCodec:
    def _stage(self, largest, quantize=True):
        from cook_tpu.ops.delta import PackDeltaApplier
        T = 65536
        idx = np.arange(8, dtype=np.int32) + T      # second pool's row
        vals = (idx % T).astype(np.int32)
        vals[3] += largest
        return PackDeltaApplier(donate=False).stage(
            (2, T), idx, vals, np.zeros(8, dtype=np.uint8),
            quantize=quantize)

    @pytest.mark.parametrize("largest,codec,wide", [
        (127, "ROWS_I8", 0), (128, "ROWS_I16", 0),
        (32767, "ROWS_I16", 0), (32768, "ROWS_WIDE", 1)])
    def test_delta_codec_negotiation(self, largest, codec, wide):
        from cook_tpu.ops import delta
        n0 = counter_value("cook_quant_wide_fallback", {"field": "delta"})
        st = self._stage(largest)
        assert st.codec == getattr(delta, codec)
        assert st.vals.dtype == {"ROWS_I8": np.int8, "ROWS_I16": np.int16,
                                 "ROWS_WIDE": np.int32}[codec]
        assert counter_value("cook_quant_wide_fallback",
                             {"field": "delta"}) == n0 + wide

    def test_quantize_off_ships_wide(self):
        from cook_tpu.ops import delta
        n0 = counter_value("cook_quant_wide_fallback")
        st = self._stage(1, quantize=False)
        assert st.codec == delta.ROWS_WIDE and st.vals.dtype == np.int32
        assert counter_value("cook_quant_wide_fallback") == n0


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def _removed_in_matcher_config():
    MatcherConfig(backend="tpu-megakernel")


def _removed_in_default_matcher():
    from cook_tpu.daemon import build_scheduler_config
    build_scheduler_config(
        {"default_matcher": {"backend": "tpu-megakernel"}})


def _removed_in_pool_matchers():
    Config(pool_matchers=[
        ("^gpu$", MatcherConfig(backend="tpu-megakernel"))])


SHIPPED = sorted(
    p for p in list((REPO / "examples").glob("*.json"))
    + list((REPO / "benchmarks" / "configs").glob("*.json"))
    if "scheduler" in json.loads(p.read_text()))


class TestConfiguration:
    @pytest.mark.parametrize("place", [
        _removed_in_matcher_config, _removed_in_default_matcher,
        _removed_in_pool_matchers], ids=lambda f: f.__name__[12:])
    def test_removed_backend_is_refused(self, place):
        with pytest.raises(ValueError) as exc:
            place()
        for accepted in ("auto", "tpu-greedy", "tpu-auction",
                         "tpu-waterfill", "cpu"):
            assert accepted in str(exc.value)

    def test_shipped_configs_found(self):
        names = {p.name for p in SHIPPED}
        assert {"cook.json", "cook-production.json",
                "cook-1pool-100kx5k.json", "cook-8pool-50k.json"} <= names

    @pytest.mark.parametrize("file", SHIPPED,
                             ids=lambda p: f"{p.parent.name}/{p.name}")
    def test_shipped_config_builds(self, file):
        """A later PR that removes an option a shipped (or frozen
        benchmark) configuration names fails here, on the CPU, not as a
        cell that cannot start."""
        from cook_tpu.daemon import (_SCALAR_CONFIG_FIELDS,
                                     build_scheduler_config)
        spec = json.loads(file.read_text())["scheduler"]
        cfg = build_scheduler_config(spec)
        assert cfg.cycle_mode == "fused"
        assert cfg.default_matcher.backend in (
            "auto", "tpu-greedy", "tpu-auction", "tpu-waterfill", "cpu")
        # every key was taken up, not skipped: scalars landed on Config,
        # matcher keys on the MatcherConfig (sections are validated by
        # build_scheduler_config itself; rank_backend is the daemon's)
        for key, value in spec.items():
            if key in _SCALAR_CONFIG_FIELDS:
                assert getattr(cfg, key) == value, key
            elif not isinstance(value, (dict, list)):
                assert key == "rank_backend", key
        for key, value in spec.get("default_matcher", {}).items():
            assert getattr(cfg.default_matcher, key) == value, key
        for key, value in spec.get("pipeline", {}).items():
            assert getattr(cfg.pipeline, key) == value, key


# ---------------------------------------------------------------------------
# the kernel catalog of docs/OBSERVABILITY.md
# ---------------------------------------------------------------------------

def _names_in_tree(pattern):
    """Every match of ``pattern``'s one group under cook_tpu/."""
    return {m for path in (REPO / "cook_tpu").rglob("*.py")
            for m in re.findall(pattern, path.read_text())}


def _documented_kernels(metric, column):
    """Backticked dotted names (and ``match``) in one column (3 labels,
    4 description) of the metric's row of docs/OBSERVABILITY.md — the
    kernel label values it lists."""
    text = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    row = next(line for line in text.splitlines()
               if line.startswith(f"| `{metric}`"))
    cell = row.split("|", 4)[column]
    if column == 3:
        cell = cell.split("|")[0]
    return {m for m in re.findall(r"`([a-z_]+(?:\.[a-z_]+)?)`", cell)
            if "." in m or m == "match"}


def test_kernel_catalog_matches_the_tree():
    jitted = _names_in_tree(r'instrument_jit\(\s*"([a-z_]+\.[a-z_]+)"')
    assert len(jitted) >= 10, f"scan looks broken: {sorted(jitted)}"
    assert _documented_kernels("cook_jit_compile_total", 4) == jitted
    assert _documented_kernels("cook_warmup_executions_total", 3) == \
        _names_in_tree(r'_count_warmup\("([^"]+)"') <= jitted
    assert _documented_kernels("cook_kernel_launches_total", 4) <= jitted
    assert _documented_kernels("cook_kernel_fallback_total", 4) == \
        _names_in_tree(
            r'"cook_kernel_fallback",\s*labels=\{"kernel": "([^"]+)"\}')

"""Pallas fused-cycle megakernel (ISSUE 14; ops/pallas_cycle.py,
ops/quant.py, sched/fused.py megakernel dispatch path).

The contract under test:

* KERNEL PARITY: the single-launch megakernel's outputs are bit-identical
  to the fused XLA driver (parallel/sharded.make_pool_cycle compact) on
  random compact inputs — same module functions, one launch;
* DRIVER PARITY MATRIX: launch decisions byte-identical across
  megakernel / fused XLA / split drivers, sync and depth-2 pipelined,
  over rigid AND elastic (gang_min < gang_max) gangs, compact and
  quantized wire, resident and rebuild modes;
* QUANTIZED WIRE: expand(quantize(x)) == x wherever a narrow form was
  negotiated; non-representable domains fall back WIDE explicitly
  (cook_quant_wide_fallback_total) — quantization is lossless-or-wide,
  never approximate;
* FUSED GANG STAGE: the in-kernel gang_min-gated segment reduction
  matches reference_impl.gang_reduce, and the driver consumes the fused
  verdicts only while the candidate view is intact;
* ROBUSTNESS: a megakernel dispatch failure degrades to the fused XLA
  cycle (cook_kernel_fallback_total{kernel=pallas.megacycle}) with
  decisions unchanged — the cycle never dies;
* TELEMETRY: CycleRecord.kernel_launches / .path land on /debug/cycles
  (megakernel cycles read path="megakernel", 1 launch).
"""

import numpy as np
import pytest

from cook_tpu.cluster import FakeCluster, FakeHost
from cook_tpu.config import Config, MatcherConfig
from cook_tpu.ops import pallas_cycle, quant
from cook_tpu.sched import Scheduler
from cook_tpu.state import Group, Job, Pool, Resources, Store
from cook_tpu.utils.flight import recorder as flight_recorder
from cook_tpu.utils.metrics import registry


def counter_value(name, labels):
    """Current value of one labeled counter series (0.0 when absent)."""
    for lbl, v in registry.series(name):
        if all(lbl.get(k) == want for k, want in labels.items()):
            return v
    return 0.0


# ---------------------------------------------------------------------------
# world builders (fixed uuids: two builds produce identical worlds)
# ---------------------------------------------------------------------------

def make_cfg(backend="tpu-megakernel", depth=0, resident=True,
             quantized=True, cycle_mode="fused"):
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


def drive(cfg, cycles=4, split=False, expect_mega=None, **kw):
    """Drive a world; when the megakernel is the pinned backend, the
    fallback must not be what produced the decisions: every cycle's
    path is ``megakernel`` and the kernel's fallback counter stands
    still (a dead kernel behind a working fallback passed this matrix
    once — ISSUE 21)."""
    if expect_mega is None:
        expect_mega = (not split and cfg.default_matcher.backend
                       == "tpu-megakernel")
    fallback = {"kernel": "pallas.megacycle"}
    n0 = counter_value("cook_kernel_fallback", fallback)
    seq0 = flight_recorder.last_seq()
    store, sched, jobs = build_world(cfg, **kw)
    for w in range(cycles):
        if split:
            sched.step_rank()
            sched.step_match()
        else:
            sched.step_cycle()
        jobs.extend(churn(store, w))
    if split:
        sched.step_rank()
        sched.step_match()
    else:
        sched.step_cycle()
    if expect_mega:
        paths = [r["path"] for r in flight_recorder.recent(cycles + 8)
                 if r["seq"] > seq0 and r["kind"] == "fused"]
        assert paths and set(paths) == {"megakernel"}, paths
        assert counter_value("cook_kernel_fallback", fallback) == n0
    return decisions(store, jobs)


# ---------------------------------------------------------------------------
# kernel-level parity
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


def _wire_from(inp, gang=None, quantized=False):
    import jax.numpy as jnp
    P, T = inp.rows.shape
    H = inp.avail.shape[1]
    host_bits = np.stack(
        [quant.pack_bits(np.asarray(inp.host_gpu)),
         quant.pack_bits(np.asarray(inp.host_blocked))], axis=1)
    if gang is None:
        gang = pallas_cycle.empty_gang_wire(P, T, H)
    codecs = (quant.ROWS_WIDE, 0.0, 0.0)
    rows, avail, cap = inp.rows, inp.avail, inp.capacity
    if quantized:
        qr = quant.quantize_rows(np.asarray(inp.rows))
        qa = quant.quantize_fixed(np.asarray(inp.avail), "avail")
        qc = quant.quantize_fixed(np.asarray(inp.capacity), "capacity")
        codecs = (qr.codec, qa.scale, qc.scale)
        rows, avail, cap = (jnp.asarray(qr.data), jnp.asarray(qa.data),
                            jnp.asarray(qc.data))
    wire = pallas_cycle.MegaCycleWire(
        rows=rows, flags=inp.flags, res_base=inp.res_base,
        disk_base=inp.disk_base, tokens_u=inp.tokens_u,
        shares_u=inp.shares_u, quota_u=inp.quota_u,
        num_considerable=inp.num_considerable,
        pool_quota=inp.pool_quota, group_quota=inp.group_quota,
        group_id=inp.group_id, host_bits=jnp.asarray(host_bits),
        exc_rows=inp.exc_rows, exc_mask=inp.exc_mask,
        avail=avail, capacity=cap,
        gang_id=jnp.asarray(gang[0]), gang_size=jnp.asarray(gang[1]),
        gang_attr=jnp.asarray(gang[2]), host_topo=jnp.asarray(gang[3]))
    return wire, codecs


class TestKernelParity:
    def _fused(self, inp, cap=32):
        import jax
        from jax.sharding import Mesh
        from cook_tpu.parallel.mesh import POOL_AXIS
        from cook_tpu.parallel.sharded import make_pool_cycle
        mesh = Mesh(np.array(jax.devices()[:1]), (POOL_AXIS,))
        return make_pool_cycle(mesh, considerable_cap=cap,
                               structured=True, compact=True)(inp)

    @pytest.mark.parametrize("seed", [0, 7])
    def test_megakernel_bit_identical_to_fused_xla(self, seed):
        inp = _random_compact_inputs(seed=seed)
        res = self._fused(inp)
        wire, codecs = _wire_from(inp)
        mega = pallas_cycle.megacycle(wire, considerable_cap=32,
                                      interpret=True)
        for name in ("queue_rows", "n_queue", "cand_row", "cand_assign",
                     "cand_qpos"):
            a, b = np.asarray(getattr(res, name)), \
                np.asarray(getattr(mega, name))
            assert (a == b).all(), name

    def test_quantized_wire_decision_identical(self):
        inp = _random_compact_inputs(seed=1)
        wire, _ = _wire_from(inp)
        wire_q, codecs = _wire_from(inp, quantized=True)
        # the negotiation actually picked narrow forms on this workload
        assert codecs[0] != quant.ROWS_WIDE
        assert codecs[1] != 0.0 and codecs[2] != 0.0
        a = pallas_cycle.megacycle(wire, considerable_cap=32,
                                   interpret=True)
        b = pallas_cycle.megacycle(wire_q, considerable_cap=32,
                                   rows_codec=codecs[0],
                                   avail_scale=codecs[1],
                                   cap_scale=codecs[2], interpret=True)
        for name in a._fields:
            assert (np.asarray(getattr(a, name))
                    == np.asarray(getattr(b, name))).all(), name

    def test_fused_gang_stage_matches_reference(self):
        from cook_tpu.ops import reference_impl
        inp = _random_compact_inputs(seed=2)
        P, T = inp.rows.shape
        H = inp.avail.shape[1]
        gang_id = np.full((P, T), -1, dtype=np.int32)
        gang_id[0, 5:9] = 0          # gang of 4 (sorted positions 5..8)
        gang_id[1, 2:4] = 1          # second pool, gang segment 1
        G = 4
        gang_size = np.full((P, G), 2 ** 30, dtype=np.int32)
        gang_size[0, 0] = 4
        gang_size[1, 1] = 2
        gang_attr = np.zeros((P, G), dtype=np.int32)
        host_topo = np.full((P, 1, H), -1, dtype=np.int32)
        host_topo[:, 0] = 0
        wire, _ = _wire_from(inp, gang=(gang_id, gang_size, gang_attr,
                                        host_topo))
        mega = pallas_cycle.megacycle(wire, considerable_cap=32,
                                      interpret=True)
        cr = np.asarray(mega.cand_row)
        ca = np.asarray(mega.cand_assign)
        for p in range(P):
            gid_c = np.where(cr[p] >= 0,
                             gang_id[p][np.maximum(cr[p], 0)], -1)
            out, dropped = reference_impl.gang_reduce(
                ca[p], gid_c.astype(np.int32), gang_size[p],
                gang_attr[p], host_topo[p])
            assert (np.asarray(mega.cand_gang)[p] == out).all()
            assert (np.asarray(mega.cand_dropped)[p]
                    == dropped.astype(np.int32)).all()


# ---------------------------------------------------------------------------
# driver parity matrix
# ---------------------------------------------------------------------------

@pytest.mark.gang
class TestDriverParityMatrix:
    """Megakernel vs fused XLA vs split drivers, sync + depth-2
    pipelined, rigid + elastic gangs: launch decisions byte-identical."""

    @pytest.mark.parametrize("depth", [0, 2])
    @pytest.mark.parametrize("gang", ["none", "rigid", "elastic"])
    def test_megakernel_vs_fused(self, depth, gang):
        kw = {}
        if gang == "rigid":
            kw = dict(gang_size=3)
        elif gang == "elastic":
            # min 2 of 4 on 5 hosts: places at >= min, grows later
            kw = dict(gang_size=4, gang_min=2, gang_max=4, cpus=8.0)
        base = drive(make_cfg(backend="auto", depth=depth), **kw)
        mega = drive(make_cfg(depth=depth), **kw)
        assert base == mega, {k: (base[k], mega[k])
                              for k in base if base[k] != mega[k]}

    def test_megakernel_vs_split(self):
        base = drive(make_cfg(backend="cpu", cycle_mode="split"),
                     split=True, gang_size=3)
        mega = drive(make_cfg(), gang_size=3)
        assert base == mega

    @pytest.mark.parametrize("resident", [True, False])
    @pytest.mark.parametrize("quantized", [True, False])
    def test_wire_modes_decision_identical(self, resident, quantized):
        base = drive(make_cfg(backend="auto"), gang_size=3)
        mega = drive(make_cfg(resident=resident, quantized=quantized),
                     gang_size=3)
        assert base == mega

    def test_elastic_gang_places_at_min_under_megakernel(self):
        # capacity for only 2 members at once: a rigid 4-gang would wait
        # whole; the elastic min-2 gang must come up partial
        cfg = make_cfg()
        store, sched, jobs = build_world(
            cfg, n_jobs=0, n_hosts=2, cpus=4.0,
            gang_size=4, gang_min=2, gang_max=4)
        for _ in range(3):
            sched.step_cycle()
        live = [j for j in jobs
                if store.job(j.uuid).state.value == "running"]
        assert 2 <= len(live) <= 4, [store.job(j.uuid).state
                                     for j in jobs]


# ---------------------------------------------------------------------------
# quantized-wire round-trip properties
# ---------------------------------------------------------------------------

class TestQuantCodecs:
    def test_rows_roundtrip_near_identity(self):
        rng = np.random.default_rng(0)
        rows = np.arange(4096, dtype=np.int64)
        swaps = rng.integers(0, 4095, 64)
        rows[swaps], rows[swaps + 1] = rows[swaps + 1], rows[swaps].copy()
        q = quant.quantize_rows(rows)
        assert q.codec == quant.ROWS_I8
        assert (quant.expand_rows(q) == rows).all()

    def test_rows_widths_and_overflow_fallback(self):
        n0 = counter_value("cook_quant_wide_fallback",
                                    {"field": "rows"})
        rows = np.arange(4096) + 1000          # delta 1000: i16
        q = quant.quantize_rows(rows)
        assert q.codec == quant.ROWS_I16
        assert (quant.expand_rows(q) == rows).all()
        rows = np.arange(4096) + 100_000       # out of i16: wide
        q = quant.quantize_rows(rows)
        assert q.codec == quant.ROWS_WIDE
        assert (quant.expand_rows(q) == rows).all()
        assert counter_value("cook_quant_wide_fallback",
                                      {"field": "rows"}) == n0 + 1

    def test_rows_device_decode_matches_host(self):
        rows = np.arange(512) + 17
        q = quant.quantize_rows(rows)
        dev = np.asarray(quant.expand_rows_device(q.codec, q.data, 512))
        assert (dev == quant.expand_rows(q)).all()

    def test_fixed_roundtrip_per_column_scales(self):
        rng = np.random.default_rng(1)
        x = np.stack([rng.integers(0, 64, 256) * 0.5,       # halves
                      rng.integers(0, 16384, 256) * 1.0,    # ints
                      rng.integers(0, 8, 256) * 1.0,
                      rng.integers(0, 1000, 256) * 1024.0],  # big, /64
                     axis=1).astype(np.float32)
        q = quant.quantize_fixed(x, "avail")
        assert q.scale != 0.0 and q.data.dtype == np.uint16
        assert (quant.expand_fixed(q) == x).all()
        dev = np.asarray(quant.expand_fixed_device(q.scale, q.data))
        assert (dev == x).all()

    def test_fixed_nonrepresentable_falls_back_wide(self):
        x = np.full((8, 4), 0.3, dtype=np.float32)  # not dyadic
        q = quant.quantize_fixed(x, "avail")
        assert q.scale == 0.0
        assert (quant.expand_fixed(q) == x).all()

    def test_bitpack_roundtrip(self):
        rng = np.random.default_rng(2)
        for n in (1, 7, 8, 9, 100):
            x = rng.random((3, n)) < 0.5
            packed = quant.pack_bits(x)
            assert (quant.unpack_bits(packed, n) == x).all()
            dev = np.asarray(quant.unpack_bits_device(packed, n))
            assert (dev == x).all()

    def test_delta_scatter_quantized_matches_wide(self):
        import jax.numpy as jnp
        from cook_tpu.ops.delta import PackDeltaApplier
        rng = np.random.default_rng(3)
        P, T = 2, 512
        rows0 = np.zeros((P, T), dtype=np.int32)
        flags0 = np.zeros((P, T), dtype=np.uint8)
        idx = np.sort(rng.choice(P * T, 64, replace=False)).astype(
            np.int32)
        vals = ((idx % T) + rng.integers(-100, 100, 64)).astype(np.int32)
        fvals = rng.integers(0, 32, 64).astype(np.uint8)
        ap = PackDeltaApplier(donate=False)
        rw, fw = ap.apply(jnp.asarray(rows0), jnp.asarray(flags0),
                          idx, vals, fvals, quantize=False)
        rq, fq = ap.apply(jnp.asarray(rows0), jnp.asarray(flags0),
                          idx, vals, fvals, quantize=True)
        assert (np.asarray(rw) == np.asarray(rq)).all()
        assert (np.asarray(fw) == np.asarray(fq)).all()
        # and the staged narrow batch was genuinely smaller
        st_w = ap.stage((P, T), idx, vals, fvals, quantize=False)
        st_q = ap.stage((P, T), idx, vals, fvals, quantize=True)
        assert st_q.codec != quant.ROWS_WIDE
        assert st_q.nbytes < st_w.nbytes


# ---------------------------------------------------------------------------
# config / telemetry / robustness
# ---------------------------------------------------------------------------

class TestReviewRegressions:
    """Fix-pinning tests from the PR 14 review round."""

    def test_rebuild_mode_rows_actually_negotiate_narrow(self):
        """The rows codec must engage over the BUCKET-PADDED production
        wire, not just the bench's unpadded identity rows: zero padding
        used to read as delta -t and force wide on every pool not
        exactly filling its bucket (identity padding fixes it)."""
        n0 = counter_value("cook_quant_wide_fallback", {"field": "rows"})
        base = drive(make_cfg(backend="auto"), cycles=2)
        got = drive(make_cfg(resident=False, quantized=True), cycles=2)
        assert got == base
        assert counter_value("cook_quant_wide_fallback",
                             {"field": "rows"}) == n0

    def test_sticky_fixed_scales_reused(self):
        x = (np.arange(32, dtype=np.float32).reshape(8, 4)) * 0.5
        q1 = quant.quantize_fixed(x, "avail")
        # a coarser-but-still-exact preferred scale must be KEPT (the
        # scale tuple is a static jit key; flapping means retraces)
        coarse = tuple(s * 2 for s in q1.scale)
        q2 = quant.quantize_fixed(x * 2, "avail", prefer=coarse)
        assert q2.scale == coarse
        assert (quant.expand_fixed(q2) == x * 2).all()
        # a preferred scale that no longer round-trips renegotiates
        q3 = quant.quantize_fixed(np.full((2, 4), 0.125,
                                          dtype=np.float32),
                                  "avail", prefer=(1.0, 1.0, 1.0, 1.0))
        assert q3.scale != (1.0, 1.0, 1.0, 1.0)
        assert (quant.expand_fixed(q3) == 0.125).all()

    def _two_pool_world(self, cfg):
        """default pool pinned per cfg + an 'other' pool on auto, each
        with a small gang that cannot fully place (all-or-nothing must
        hold on BOTH paths of a mixed group)."""
        store = Store()
        store.put_pool(Pool(name="default"))
        store.put_pool(Pool(name="other"))
        hosts = [FakeHost(hostname=f"h{i}",
                          capacity=Resources(cpus=4.0, mem=4096.0))
                 for i in range(2)]
        hosts_o = [FakeHost(hostname=f"o{i}", pool="other",
                            capacity=Resources(cpus=4.0, mem=4096.0))
                   for i in range(2)]
        sched = Scheduler(
            store, cfg,
            [FakeCluster("fake-1", hosts),
             FakeCluster("fake-2", hosts_o)],
            rank_backend="tpu")
        # a 3-member gang of 4-cpu jobs on 2x4cpu hosts: can never
        # place whole — any member launching is a partial-gang bug
        members = [Job(uuid=f"00000000-0000-0000-0009-{i:012d}",
                       user="gang", command="true", group="gx",
                       pool="other",
                       resources=Resources(cpus=4.0, mem=512.0),
                       submit_time_ms=900)
                   for i in range(3)]
        store.create_jobs(members, groups=[Group(
            uuid="gx", gang=True, gang_size=3,
            jobs=[m.uuid for m in members])])
        singles = [Job(uuid=f"00000000-0000-0000-0008-{i:012d}",
                       user=f"u{i}", command="true", pool="default",
                       resources=Resources(cpus=1.0, mem=128.0),
                       submit_time_ms=1000 + i) for i in range(3)]
        store.create_jobs(singles)
        return store, sched, members, singles

    def test_explicit_pin_takes_mixed_group_and_gang_guard_holds(self):
        """An explicit tpu-megakernel pin routes the whole dispatch
        group through the megakernel even when a co-grouped pool is on
        'auto' (CPU); the auto pool stages NO gang wire, so its gang
        verdicts must come from the host reduction — a partial gang in
        that pool must still launch NOTHING."""
        cfg = make_cfg()  # default matcher pinned tpu-megakernel
        cfg.pool_matchers = [("other", MatcherConfig(backend="auto"))]
        store, sched, members, singles = self._two_pool_world(cfg)
        for _ in range(3):
            sched.step_cycle()
        rec = flight_recorder.recent(5)
        assert any(r["path"] == "megakernel" for r in rec), \
            [r["path"] for r in rec]
        for m in members:
            assert store.job(m.uuid).instances == [], \
                (m.uuid, store.job(m.uuid).state)
        for s in singles:
            assert store.job(s.uuid).state.value in ("running",
                                                     "completed")


class TestWarmup:
    def test_warmup_compiles_megakernel_executables(self):
        """Boot warmup must cover the megakernel when it is the live
        path: the first production cycle then reuses a compiled
        executable instead of tracing in-cycle (residual: the first
        negotiated fixed-point scale tuple, by design)."""
        cfg = make_cfg()
        cfg.pipeline.warmup_tasks = 64
        cfg.pipeline.warmup_hosts = 8
        before = set(pallas_cycle._FNS)
        store, sched, jobs = build_world(cfg)
        runs = sched.warmup_kernels()
        assert runs > 0
        warmed = set(pallas_cycle._FNS) - before
        assert warmed, "warmup built no megakernel executables"


class TestBackendConfig:
    def test_megakernel_backend_validates(self):
        assert MatcherConfig(backend="tpu-megakernel").backend == \
            "tpu-megakernel"
        with pytest.raises(ValueError):
            MatcherConfig(backend="tpu-megakernel-typo")

    def test_auction_pallas_deprecation_logged_and_counted(self, caplog):
        import logging
        n0 = counter_value(
            "cook_config_deprecated",
            {"knob": "matcher.backend", "value": "tpu-auction-pallas"})
        with caplog.at_level(logging.WARNING):
            mc = MatcherConfig(backend="tpu-auction-pallas")
        assert mc.backend == "tpu-auction"
        assert any("DEPRECATED" in r.message for r in caplog.records)
        assert counter_value(
            "cook_config_deprecated",
            {"knob": "matcher.backend",
             "value": "tpu-auction-pallas"}) == n0 + 1

    def test_split_path_resolves_megakernel_to_greedy(self):
        from cook_tpu.sched.matcher import Matcher
        mc = MatcherConfig(backend="tpu-megakernel")
        assert Matcher.resolve_backend(mc, 10) == "tpu-greedy"


class TestTelemetryAndFallback:
    def test_cycle_record_path_and_launch_count(self):
        store, sched, jobs = build_world(make_cfg())
        sched.step_cycle()
        rec = flight_recorder.recent(3)[-1]
        assert rec["path"] == "megakernel"
        assert rec["kernel_launches"] == 1, rec["kernel_launches"]
        store, sched, jobs = build_world(make_cfg(backend="auto"))
        sched.step_cycle()
        rec = flight_recorder.recent(3)[-1]
        assert rec["path"] == "fused"

    def test_runtime_fault_degrades_to_fused_xla(self, monkeypatch):
        """A RUNTIME fault at dispatch (not a build error) still takes
        the counted fallback — the robustness contract."""
        from cook_tpu.ops import pallas_cycle as pc
        base = drive(make_cfg(backend="auto"), cycles=1)
        n0 = counter_value("cook_kernel_fallback",
                                    {"kernel": "pallas.megacycle"})

        def boom(*a, **kw):
            raise RuntimeError("device lost mid-dispatch")
        monkeypatch.setattr(pc, "megacycle", boom)
        got = drive(make_cfg(), cycles=1, expect_mega=False)
        assert got == base
        assert counter_value(
            "cook_kernel_fallback",
            {"kernel": "pallas.megacycle"}) > n0
        rec = flight_recorder.recent(3)[-1]
        assert rec["path"] == "fused"

    def test_trace_error_on_explicit_pin_raises(self, monkeypatch):
        """A kernel that cannot trace or lower fails the same way every
        cycle: the explicit pin raises instead of re-tracing behind
        cook_kernel_fallback_total forever (what pl.store's removal did
        under JAX 0.9.0 until ISSUE 21)."""
        from cook_tpu.ops import pallas_cycle as pc
        from cook_tpu.ops.telemetry import KernelBuildError

        def dead_kernel(*refs, **static):
            raise AttributeError(
                "module 'jax.experimental.pallas' has no attribute 'store'")
        monkeypatch.setattr(pc, "_kernel", dead_kernel)
        monkeypatch.setattr(pc, "_FNS", {})  # no executable from before
        n0 = counter_value("cook_kernel_fallback",
                           {"kernel": "pallas.megacycle"})
        store, sched, jobs = build_world(make_cfg())
        with pytest.raises(KernelBuildError, match="pallas.megacycle"):
            sched.step_cycle()
        assert counter_value("cook_kernel_fallback",
                             {"kernel": "pallas.megacycle"}) == n0
        assert all(store.job(j.uuid).state.value == "waiting"
                   for j in jobs)


# ---------------------------------------------------------------------------
# lint pass: module-level jnp constants in pallas modules
# ---------------------------------------------------------------------------

@pytest.mark.analysis
class TestPallasModuleConstantPass:
    def _lint(self, tmp_path, source, name):
        import textwrap
        from cook_tpu.analysis.engine import run_lint
        pkg = tmp_path / "pkg"
        target = pkg / name
        target.parent.mkdir(parents=True, exist_ok=True)
        target.write_text(textwrap.dedent(source))
        empty = tmp_path / "empty_baseline.json"
        empty.write_text('{"suppressions": []}')
        return run_lint(package_root=pkg, docs_root=None, baseline=empty)

    def test_module_level_jnp_constant_fires(self, tmp_path):
        r = self._lint(tmp_path, """
            import jax.numpy as jnp
            NEG = jnp.float32(-1e30)
            def kernel(ref):
                return ref[...] + NEG
        """, "ops/pallas_thing.py")
        assert any(f.check == "pallas-module-constant"
                   for f in r.findings), r.findings

    def test_python_literal_and_inner_jnp_clean(self, tmp_path):
        r = self._lint(tmp_path, """
            import jax.numpy as jnp
            BIG = 2**31 - 1
            def kernel(ref):
                neg = jnp.float32(-1e30)
                return ref[...] + neg + BIG
        """, "ops/pallas_thing.py")
        assert not any(f.check == "pallas-module-constant"
                       for f in r.findings), r.findings

    def test_non_pallas_module_exempt(self, tmp_path):
        r = self._lint(tmp_path, """
            import jax.numpy as jnp
            NEG = jnp.float32(-1e30)
        """, "ops/dru_like.py")
        assert not any(f.check == "pallas-module-constant"
                       for f in r.findings)

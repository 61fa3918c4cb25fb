"""Multi-controller scale-out (ISSUE 19; sched/shard.py,
parallel/mesh.py shard alignment, state/partition.py summary exchange
peers, sim/chaos.py process-kill leg; docs/DEPLOY.md "sharded
controllers").

The contract under test:

* ALIGNMENT: PartitionMap pool groups and the mesh pool-sharding layout
  are the SAME partition — `validate_shard_alignment` derives each
  shard's pool block, and any operator-declared layout that disagrees
  (or doesn't divide) is a clear config error at daemon boot;
* SHARD TELEMETRY: a shard worker's CycleRecords carry its shard id,
  `/debug/cycles` rolls sharded records into a per-shard `by_shard`
  summary, and every shard's span ring stitches into ONE Perfetto
  export as distinct process tracks;
* CROSS-PROCESS PARITY: a fixed-seed world driven through 1-process and
  N-process topologies produces bit-identical launched sets — the
  per-pool decision path makes sharding by pool decision-preserving;
* BOUNDED GLOBAL STATE: cross-shard per-user totals ride the
  UserSummaryExchange peer feed with the staleness bound ASSERTED —
  a dead peer makes the bound trip, it never silently serves stale;
* FAILOVER: a REAL SIGKILL of one partition's shard worker process
  promotes its synced standby via the candidate ranking while sibling
  shard processes keep committing — zero committed-write loss
  (`sim --chaos-failover --partitions N`).
"""

import json
import time
import urllib.request

import pytest

from cook_tpu.parallel.mesh import (ShardAlignmentError, shard_of_partition,
                                    validate_shard_alignment)
from cook_tpu.state.partition import (PartitionMap, SummaryStalenessError,
                                      UserSummaryExchange)

pytestmark = pytest.mark.sharded

WORLD = {"n_jobs": 24, "n_users": 3, "hosts_per_pool": 3, "seed": 3}
#: the no-jax worker config: split cycle + cpu rank boots in well under
#: a second per process, and the decision path is the same per-pool
#: rank/match the parity contract covers
CPU_CFG = {"backend": "cpu", "rank_backend": "cpu", "cycle_mode": "split"}
POOLS = ["pool0", "pool1", "pool2", "pool3"]


# ---------------------------------------------------------------------------
# alignment: partition groups == mesh shard layout, or a boot error
# ---------------------------------------------------------------------------

class TestShardAlignment:
    def test_contiguous_blocks(self):
        assert [shard_of_partition(p, 8, 2) for p in range(8)] == \
            [0, 0, 0, 0, 1, 1, 1, 1]
        assert [shard_of_partition(p, 4, 4) for p in range(4)] == \
            [0, 1, 2, 3]

    def test_derived_layout_and_declared_agreement(self):
        pmap = PartitionMap(count=4, pools={f"pool{i}": i
                                            for i in range(4)})
        layout = validate_shard_alignment(pmap, 2)
        assert layout == {0: ["pool0", "pool1"], 1: ["pool2", "pool3"]}
        # declaring the SAME layout explicitly is accepted
        assert validate_shard_alignment(
            pmap, 2, {"pool0": 0, "pool1": 0, "pool2": 1, "pool3": 1})

    def test_mismatched_declaration_is_config_error(self):
        pmap = PartitionMap(count=4, pools={f"pool{i}": i
                                            for i in range(4)})
        with pytest.raises(ShardAlignmentError) as ei:
            validate_shard_alignment(pmap, 2, {"pool1": 1})
        msg = str(ei.value)
        assert "pool1" in msg and "shard" in msg

    def test_indivisible_partition_count_refused(self):
        pmap = PartitionMap(count=3, pools={f"pool{i}": i
                                            for i in range(3)})
        with pytest.raises(ShardAlignmentError):
            validate_shard_alignment(pmap, 2)

    def test_declared_shard_out_of_range(self):
        pmap = PartitionMap(count=4, pools={f"pool{i}": i
                                            for i in range(4)})
        with pytest.raises(ShardAlignmentError):
            validate_shard_alignment(pmap, 2, {"pool0": 2})

    def test_partition_config_validates_shards(self):
        from cook_tpu.config import PartitionConfig
        PartitionConfig(count=4, pools={"a": 0}, shards=2,
                        shard_pools={"a": 0})
        with pytest.raises(ValueError):
            PartitionConfig(count=3, pools={"a": 0}, shards=2)
        with pytest.raises(ValueError):
            PartitionConfig(count=4, pools={"a": 0}, shards=2,
                            shard_pools={"a": 5})
        with pytest.raises(ValueError):
            # shard_pools without shards has nothing to validate against
            PartitionConfig(count=4, pools={"a": 0},
                            shard_pools={"a": 0})

    def test_daemon_boot_rejects_misaligned_layout(self):
        """The satellite-1 cross-check: a daemon conf whose declared
        shard_pools disagree with the PartitionMap's derived owner must
        die with the alignment error AT BOOT, before any plane starts."""
        from cook_tpu.daemon import CookDaemon
        conf = {"port": 0,
                "scheduler": {"partitions": {
                    "count": 4,
                    "pools": {f"pool{i}": i for i in range(4)},
                    "shards": 2,
                    # pool3 lives on partition 3 -> shard 1; declaring 0
                    # splits the write plane from the mesh shard
                    "shard_pools": {"pool3": 0}}}}
        daemon = CookDaemon(conf)
        with pytest.raises(ShardAlignmentError) as ei:
            daemon.start()
        assert "pool3" in str(ei.value)

    def test_daemon_boot_accepts_aligned_layout(self):
        from cook_tpu.daemon import CookDaemon
        conf = {"port": 0,
                "scheduler": {"partitions": {
                    "count": 4,
                    "pools": {f"pool{i}": i for i in range(4)},
                    "shards": 2,
                    "shard_pools": {"pool0": 0, "pool3": 1}}}}
        daemon = CookDaemon(conf)
        try:
            daemon.start()
        finally:
            daemon.shutdown()


# ---------------------------------------------------------------------------
# shard telemetry: CycleRecord.shard + by_shard roll-up + /debug/cycles
# ---------------------------------------------------------------------------

class TestShardTelemetry:
    def test_cycle_record_carries_shard(self):
        from cook_tpu.utils import flight
        flight.set_shard(3)
        try:
            rec = flight.CycleRecord(1, "fused")
            assert rec.shard == 3
            assert rec.to_doc()["shard"] == 3
        finally:
            flight.set_shard(None)
        assert flight.CycleRecord(2, "fused").shard is None

    def test_summary_by_shard_rollup(self):
        from cook_tpu.utils.flight import FlightRecorder, set_shard
        rec = FlightRecorder()
        try:
            for shard in (0, 0, 1):
                set_shard(shard)
                with rec.cycle("fused"):
                    pass
        finally:
            set_shard(None)
        by_shard = rec.summary()["by_shard"]
        assert set(by_shard) == {"0", "1"}
        assert by_shard["0"]["cycles"] == 2
        assert by_shard["1"]["cycles"] == 1
        assert by_shard["1"]["cycle_ms_p50"] >= 0.0
        assert by_shard["1"]["cycle_ms_p99"] >= by_shard["1"]["cycle_ms_p50"]

    def test_unsharded_summary_has_no_by_shard(self):
        from cook_tpu.utils.flight import FlightRecorder
        rec = FlightRecorder()
        with rec.cycle("fused"):
            pass
        assert "by_shard" not in rec.summary()

    def test_debug_cycles_endpoint_rolls_up(self):
        from cook_tpu.rest.api import ApiServer, CookApi
        from cook_tpu.state import Store
        from cook_tpu.utils import flight
        flight.set_shard(2)
        try:
            with flight.recorder.cycle("fused"):
                pass
            server = ApiServer(CookApi(Store()))
            server.start()
            try:
                body = json.load(urllib.request.urlopen(
                    server.url + "/debug/cycles?limit=5"))
            finally:
                server.stop()
        finally:
            flight.set_shard(None)
        assert "2" in body["by_shard"]
        assert body["cycles"][-1]["shard"] == 2


# ---------------------------------------------------------------------------
# summary exchange: peer feed + asserted staleness bound (no processes)
# ---------------------------------------------------------------------------

class TestPeerSummaryExchange:
    _uid = 0

    def _store(self, user_jobs):
        from cook_tpu.state import Job, Pool, Resources, Store
        store = Store()
        store.put_pool(Pool(name="default"))
        for user, n in user_jobs.items():
            for _ in range(n):
                TestPeerSummaryExchange._uid += 1
                store.create_jobs([Job(
                    uuid=f"00000000-0000-4000-8000-"
                         f"{TestPeerSummaryExchange._uid:012d}",
                    user=user, command="true",
                    resources=Resources(cpus=1, mem=64))])
        return store

    def test_peer_tables_merge_into_totals(self):
        store = self._store({"alice": 2})
        peer_table = {"alice": {"pending": 3.0, "running": 1.0}}
        ex = UserSummaryExchange([store], max_age_s=5.0,
                                 peer_fetch=lambda: [(peer_table, 0.0)])
        totals = ex.user_totals("alice")
        assert totals["pending"] == 5.0
        assert totals["running"] == 1.0
        assert ex.stats()["peer_tables"] == 1

    def test_peer_age_backdates_freshness(self):
        store = self._store({"alice": 1})
        ex = UserSummaryExchange([store], max_age_s=0.5,
                                 peer_fetch=lambda: [({}, 10.0)],
                                 assert_bound=True)
        with pytest.raises(SummaryStalenessError):
            ex.user_totals("alice")

    def test_bound_not_asserted_by_default(self):
        store = self._store({"alice": 1})
        ex = UserSummaryExchange([store], max_age_s=0.5,
                                 peer_fetch=lambda: [({}, 10.0)])
        assert ex.user_totals("alice")["pending"] == 1.0
        assert ex.staleness_s() >= 10.0

    def test_fresh_peers_keep_bound(self):
        store = self._store({"alice": 1})
        ex = UserSummaryExchange([store], max_age_s=0.5,
                                 peer_fetch=lambda: [({}, 0.0)],
                                 assert_bound=True)
        assert ex.user_totals("alice")["pending"] == 1.0


# ---------------------------------------------------------------------------
# cross-process topologies (real shard worker processes)
# ---------------------------------------------------------------------------

def _drive(sup, cycles=3):
    sup.broadcast({"cmd": "cycle", "n": cycles}, timeout_s=120)
    return sup.collect_decisions()


class TestShardedTopology:
    # One shared 2-process topology for the whole class: worker boots
    # dominate these tests' wall time, and every probe except the parity
    # baseline reads the same topology.  The dead-peer test kills shard 1
    # and therefore MUST stay last in definition order.
    @pytest.fixture(scope="class")
    def topo(self, tmp_path_factory):
        from cook_tpu.sched.shard import sched_topology
        sup = sched_topology(2, POOLS, WORLD, cfg=CPU_CFG,
                             summary_max_age_s=0.4,
                             root=str(tmp_path_factory.mktemp("topo2")))
        yield sup
        sup.stop()

    def test_workers_own_disjoint_pool_blocks(self, topo):
        from cook_tpu.sched.shard import shard_pools
        assert shard_pools(POOLS, 0, 2) == ["pool0", "pool1"]
        assert shard_pools(POOLS, 1, 2) == ["pool2", "pool3"]
        assert topo.procs[0].addr["pools"] == ["pool0", "pool1"]
        assert topo.procs[1].addr["pools"] == ["pool2", "pool3"]

    def test_parity_one_vs_two_processes(self, topo, tmp_path):
        """The tentpole parity contract: the SAME fixed-seed world
        through a single process and through 2 shard processes launches
        the bit-identical job set (states + sorted hostnames), extending
        the test_cycle_parity matrix across process boundaries."""
        from cook_tpu.sched.shard import sched_topology
        sup1 = sched_topology(1, POOLS, WORLD, cfg=CPU_CFG,
                              root=str(tmp_path / "topo1"))
        try:
            got1 = _drive(sup1)
        finally:
            sup1.stop()
        got2 = _drive(topo)
        assert len(got1) == WORLD["n_jobs"]
        assert any(h for _s, h in got1.values()), "nothing launched"
        assert got2 == got1

    def test_flight_and_trace_stitch_across_shards(self, topo):
        _drive(topo, cycles=2)
        flight = topo.collect_flight()
        assert set(flight) == {0, 1}
        for shard, summary in flight.items():
            assert set(summary["by_shard"]) == {str(shard)}
            assert summary["by_shard"][str(shard)]["cycles"] >= 2
        trace = topo.collect_trace("test-stitch")
        pids = {ev["pid"] for ev in trace["traceEvents"]}
        names = {ev["args"]["name"]
                 for ev in trace["traceEvents"]
                 if ev.get("ph") == "M"
                 and ev.get("name") == "process_name"}
        assert len(pids) == 2
        assert {"shard-0", "shard-1"} <= names
        members = trace["otherData"]["members"]
        assert all(m["ok"] and m["spans"] > 0 for m in members)

    def test_cross_shard_user_totals_and_dead_peer_staleness(self, topo):
        local = [topo.rpc(i, {"cmd": "summary"})["users"]
                 for i in (0, 1)]
        want = sum(local[i].get("user0", {}).get("pending", 0.0)
                   + local[i].get("user0", {}).get("running", 0.0)
                   for i in (0, 1))
        resp = topo.rpc(0, {"cmd": "user_totals", "user": "user0"})
        got = (resp["totals"]["pending"]
               + resp["totals"]["running"])
        assert got == pytest.approx(want)
        assert resp["staleness_s"] <= 0.4
        # kill the peer: shard 0's asserted bound must TRIP once the
        # cached table ages past max_age_s — never silently stale
        topo.kill(1)
        deadline = time.monotonic() + 10.0
        stale = None
        while time.monotonic() < deadline:
            resp = topo.rpc(0, {"cmd": "user_totals", "user": "user0"})
            if "stale" in resp:
                stale = resp["stale"]
                break
            time.sleep(0.1)
        assert stale is not None, "staleness bound never tripped"
        assert "max_age" in stale or "stale" in stale.lower()


# ---------------------------------------------------------------------------
# process-kill failover (the chaos leg, tier-1 smoke + slow soak)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestProcessKillFailover:
    def test_sigkill_failover_smoke(self, tmp_path):
        """Tier-1 smoke of `sim --chaos-failover --partitions 2` with a
        REAL SIGKILL: victim's standby promotes via candidate ranking,
        siblings never stall, zero committed-write loss."""
        from cook_tpu.sim.chaos import (PartitionChaosConfig,
                                        run_partition_chaos_procs)
        res = run_partition_chaos_procs(PartitionChaosConfig(
            partitions=2, jobs_before=2, writers=2,
            sibling_stream_s=0.8, data_root=str(tmp_path)))
        assert res.ok, res.violations
        assert res.process_kill is True
        assert res.promoted_epoch == 2
        assert res.victim_indeterminate >= 1
        assert res.sibling_errors == 0
        assert res.sibling_commits_during_promotion >= 1
        assert res.summary()["process_kill"] is True

    @pytest.mark.slow
    def test_sigkill_failover_soak_four_partitions(self, tmp_path):
        from cook_tpu.sim.chaos import (PartitionChaosConfig,
                                        run_partition_chaos_procs)
        res = run_partition_chaos_procs(PartitionChaosConfig(
            partitions=4, victim=1, data_root=str(tmp_path)))
        assert res.ok, res.violations
        assert res.committed >= 4 * res.partitions

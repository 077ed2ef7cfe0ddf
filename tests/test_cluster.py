"""Cluster-plane tests: gossip mailboxes, supervisor lifecycle, the
end-to-end ownership rule, and cluster-vs-single-engine parity.

The heavy real-engine choreography (two supervised engine processes
draining losslessly, gossip digest convergence across processes, a
SIGKILL/restart cycle mid-serve) is re-proved by every verify run in
``scripts/cluster_smoke.py`` → ``artifacts/CLUSTER_r14.json``; the
tests here keep tier-1 fast by exercising the same protocol objects
in-process (the mailbox/gossip planes are just mmapped files — two
:class:`GossipPlane` endpoints in one process are byte-for-byte the
cross-process protocol) plus the supervisor's restart machinery
against the millisecond lifecycle stub.
"""

import os
import platform
import time

import numpy as np
import pytest

from flowsentryx_tpu.cluster.gossip import GossipPlane, create_plane
from flowsentryx_tpu.cluster.mailbox import (
    StatusBlock, VerdictMailbox, status_path,
)
from flowsentryx_tpu.core import schema
from flowsentryx_tpu.engine.shm import RingNotReady
from flowsentryx_tpu.engine.writeback import BlacklistUpdate, CollectSink

pytestmark = pytest.mark.skipif(
    platform.system() != "Linux",
    reason="cluster plane is mmap shm + process groups (Linux)")


def _upd(keys, untils):
    return BlacklistUpdate(key=np.asarray(keys, np.uint32),
                           until_s=np.asarray(untils, np.float32))


# ---------------------------------------------------------------------------
# the shm plane: mailboxes and status blocks
# ---------------------------------------------------------------------------


class TestVerdictMailbox:
    def test_geometry_refusals(self, tmp_path):
        with pytest.raises(ValueError, match="power of two"):
            VerdictMailbox.create(tmp_path / "m", slots=3, k_max=4)
        with pytest.raises(ValueError, match="k_max"):
            VerdictMailbox.create(tmp_path / "m", slots=4, k_max=0)

    def test_unpublished_magic_refused(self, tmp_path):
        p = tmp_path / "zeroed.mbx"
        p.write_bytes(b"\0" * 4096)
        with pytest.raises(RingNotReady, match="magic"):
            VerdictMailbox(p)

    def test_roundtrip_and_k_from_geometry(self, tmp_path):
        mbx = VerdictMailbox.create(tmp_path / "m", slots=4, k_max=2)
        assert mbx.k_max == 2  # derived from slot_words, not re-passed
        wire = np.arange(2 * 2 + 4, dtype=np.uint32)
        assert mbx.publish(wire, seq=7, count=2)
        assert mbx.readable() == 1
        [(seq, got)] = mbx.pop_wires(8)
        assert seq == 7
        np.testing.assert_array_equal(got, wire)
        assert mbx.readable() == 0

    def test_full_mailbox_drops_instead_of_blocking(self, tmp_path):
        mbx = VerdictMailbox.create(tmp_path / "m", slots=2, k_max=1)
        wire = np.zeros(2 + 4, np.uint32)
        assert mbx.publish(wire, 1, 1)
        assert mbx.publish(wire, 2, 1)
        t0 = time.monotonic()
        assert not mbx.publish(wire, 3, 1)  # full: False, instantly
        assert time.monotonic() - t0 < 0.1
        assert mbx.readable() == 2

    def test_wraparound_preserves_wires(self, tmp_path):
        mbx = VerdictMailbox.create(tmp_path / "m", slots=2, k_max=1)
        for seq in range(1, 8):
            wire = np.full(2 + 4, seq, np.uint32)
            assert mbx.publish(wire, seq, 1)
            [(got_seq, got)] = mbx.pop_wires(4)
            assert got_seq == seq
            np.testing.assert_array_equal(got, wire)

    def test_u64_seq_split_across_2pow32_boundary(self, tmp_path):
        """Satellite (ISSUE 15): the u64 seq is split across two u32
        header words (cell[0]=lo, cell[1]=hi) — pin the split AND the
        reassembly exactly at the 2^32 word boundary (a lo-word-only
        regression would alias seq 2^32 to 0 and read a torn-restart
        gap where there is none)."""
        mbx = VerdictMailbox.create(tmp_path / "m", slots=4, k_max=2)
        wire = np.zeros(2 * 2 + 4, np.uint32)
        for seq in [(1 << 32) - 1, 1 << 32, (1 << 32) + 1,
                    (1 << 63) + 7]:
            assert mbx.publish(wire, seq, 0)
            cell = mbx._cells[(int(mbx._head[0]) - 1)
                              & (mbx.slots - 1)]
            assert int(cell[0]) == seq & 0xFFFFFFFF   # lo word
            assert int(cell[1]) == seq >> 32          # hi word
            [(got_seq, _w)] = mbx.pop_wires(1)
            assert got_seq == seq

    def test_popped_wire_survives_producer_overwrite(self, tmp_path):
        # pop_wires copies: the returned wire must stay intact when the
        # producer laps the ring over the same slot
        mbx = VerdictMailbox.create(tmp_path / "m", slots=2, k_max=1)
        first = np.full(2 + 4, 11, np.uint32)
        mbx.publish(first, 1, 1)
        [(_, got)] = mbx.pop_wires(1)
        for seq in range(2, 4):  # re-use both slots
            mbx.publish(np.full(2 + 4, 99, np.uint32), seq, 1)
        np.testing.assert_array_equal(got, first)


class TestStatusBlock:
    def test_create_and_writer_fields_roundtrip(self, tmp_path):
        st = StatusBlock.create(tmp_path / "s.blk", rank=3)
        assert st.rank == 3
        for f in ("c_hbeat", "c_state", "c_batches", "c_records",
                  "c_stop", "c_gen", "c_t0"):
            assert st.ctl_get(f) == 0  # zeroed = "never booted"
            st.ctl_set(f, 41)
            assert st.ctl_get(f) == 41
        st2 = StatusBlock(tmp_path / "s.blk")  # a second attacher
        assert st2.ctl_get("c_state") == 41

    def test_unpublished_magic_refused(self, tmp_path):
        p = tmp_path / "zero.blk"
        p.write_bytes(b"\0" * schema.SHM_STATUS_SIZE)
        with pytest.raises(RingNotReady, match="magic"):
            StatusBlock(p)


# ---------------------------------------------------------------------------
# the gossip plane: publish/merge protocol, in-process
# ---------------------------------------------------------------------------


class TestGossipPlane:
    def _planes(self, tmp_path, n=2, sinks=False, **kw):
        create_plane(tmp_path, n, **kw)
        return [GossipPlane(tmp_path, r, n,
                            sink=CollectSink() if sinks else None,
                            merge_interval_s=0.0)
                for r in range(n)]

    def test_create_plane_refuses_single_engine(self, tmp_path):
        with pytest.raises(ValueError, match=">= 2 engines"):
            create_plane(tmp_path, 1)

    def test_plane_requires_created_dir(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            GossipPlane(tmp_path, 0, 2)

    def test_rank_bounds(self, tmp_path):
        create_plane(tmp_path, 2)
        with pytest.raises(ValueError, match="rank"):
            GossipPlane(tmp_path, 2, 2)

    def test_attach_refuses_fleet_size_mismatch(self, tmp_path):
        # a 2-engine attach on a 3-engine plane would construct fine
        # (rank 0/1 files all exist) and serve while silently never
        # gossiping with rank 2 — the geometry stamp refuses it
        create_plane(tmp_path, 3)
        with pytest.raises(ValueError, match="created for 3"):
            GossipPlane(tmp_path, 0, 2)

    def test_block_on_a_enforced_by_b_one_tick_byte_identical(
            self, tmp_path):
        """The headline gossip claim: a block landed on engine A is in
        engine B's merged view (and B's kernel-tier sink) after ONE
        merge tick, with byte-identical untils."""
        a, b = self._planes(tmp_path, sinks=True)
        untils = np.array([12.25, 99.5, 3.125], np.float32)
        a.publish(_upd([101, 202, 303], untils), now=1.0)
        assert b.tick(force=True) == 3
        assert b.report()["merged_digest"] == \
            a.report()["published_digest"]
        assert b.report()["rx_seq_gaps"] == 0
        got = b.sink.blocked
        assert set(got) == {101, 202, 303}
        for k, u in zip([101, 202, 303], untils):
            assert np.float32(got[k]) == u  # exact, not approximate
        # and nothing came back to A (its RX side is empty)
        assert a.tick(force=True) == 0

    def test_last_wins_by_key(self, tmp_path):
        a, b = self._planes(tmp_path, sinks=True)
        a.publish(_upd([7], [10.0]), now=0.0)
        a.publish(_upd([7], [20.0]), now=0.1)
        assert b.tick(force=True) == 2
        assert b.sink.blocked[7] == 20.0
        assert b.report()["merged_digest"] == \
            a.report()["published_digest"]

    def test_group_bigger_than_k_chunks_into_wires(self, tmp_path):
        a, b = self._planes(tmp_path, k_max=4)
        keys = np.arange(10, dtype=np.uint32) + 1
        a.publish(_upd(keys, np.arange(10) + 0.5), now=0.0)
        assert a.report()["tx_wires"] == 3  # 4 + 4 + 2
        assert b.tick(force=True) == 10
        assert b.report()["merged_digest"] == \
            a.report()["published_digest"]

    def test_full_mailbox_drop_is_counted_and_gap_detected(
            self, tmp_path):
        a, b = self._planes(tmp_path, slots=2)
        for i in range(3):  # third wire hits a full 2-slot mailbox
            a.publish(_upd([i + 1], [1.0]), now=0.0)
        assert a.report()["tx_dropped"] == 1
        assert b.tick(force=True) == 2
        assert b.report()["rx_seq_gaps"] == 0
        a.publish(_upd([9], [1.0]), now=0.0)  # seq 4 after lost seq 3
        assert b.tick(force=True) == 1
        assert b.report()["rx_seq_gaps"] == 1  # counted, never silent

    def test_tick_throttled_to_merge_interval(self, tmp_path):
        create_plane(tmp_path, 2)
        a = GossipPlane(tmp_path, 0, 2, merge_interval_s=60.0)
        b = GossipPlane(tmp_path, 1, 2, merge_interval_s=60.0)
        a.publish(_upd([1], [1.0]), now=0.0)
        assert b.tick() == 1  # first tick is always live
        a.publish(_upd([2], [1.0]), now=0.0)
        assert b.tick() == 0  # throttled, nothing statted
        assert b.tick(force=True) == 1  # force bypasses the throttle

    def test_tick_heartbeats_status_block(self, tmp_path):
        (a, _b) = self._planes(tmp_path)
        assert a.status.ctl_get("c_hbeat") == 0
        a.tick(force=True)
        assert a.status.ctl_get("c_hbeat") > 0

    def test_empty_update_publishes_nothing(self, tmp_path):
        a, b = self._planes(tmp_path)
        a.publish(_upd([], []), now=0.0)
        assert a.report()["tx_wires"] == 0
        assert b.tick(force=True) == 0


# ---------------------------------------------------------------------------
# the ownership rule, one level up
# ---------------------------------------------------------------------------


class TestClusterLayout:
    def test_rank_is_fan_out_shard_over_workers(self):
        from flowsentryx_tpu.parallel.layout import cluster_rank_of

        saddr = (np.arange(4096, dtype=np.uint64)
                 * 2654435761 % (1 << 32)).astype(np.uint32)
        for n, w in ((2, 1), (2, 3), (4, 2)):
            rank = cluster_rank_of(saddr, n, w)
            want = schema.shard_of(saddr, n * w) // np.uint32(w)
            np.testing.assert_array_equal(rank, want)
            assert rank.min() >= 0 and rank.max() < n

    def test_owns_partitions_exactly_once(self):
        from flowsentryx_tpu.parallel.layout import ClusterLayout

        saddr = np.arange(2048, dtype=np.uint32) * np.uint32(40503) \
            + np.uint32(17)
        layouts = [ClusterLayout(r, 4, workers_per_engine=2)
                   for r in range(4)]
        owned = np.stack([lo.owns(saddr) for lo in layouts])
        np.testing.assert_array_equal(owned.sum(axis=0),
                                      np.ones(len(saddr)))
        assert layouts[1].total_shards == 8
        assert layouts[1].shard_span == range(2, 4)

    def test_layout_validation(self):
        from flowsentryx_tpu.parallel.layout import ClusterLayout

        with pytest.raises(ValueError, match=">= 2 engines"):
            ClusterLayout(0, 1)
        with pytest.raises(ValueError, match="rank"):
            ClusterLayout(2, 2)
        with pytest.raises(ValueError, match="workers_per_engine"):
            ClusterLayout(0, 2, workers_per_engine=0)


# ---------------------------------------------------------------------------
# supervisor lifecycle (against the millisecond stub)
# ---------------------------------------------------------------------------


class TestClusterSupervisor:
    def _sup(self, tmp_path, specs, **kw):
        from flowsentryx_tpu.cluster.runner import stub_engine_main
        from flowsentryx_tpu.cluster.supervisor import ClusterSupervisor

        return ClusterSupervisor(tmp_path / "cl", specs,
                                 entry=stub_engine_main, **kw)

    def test_refuses_single_engine_fleet(self, tmp_path):
        from flowsentryx_tpu.cluster.supervisor import ClusterSupervisor

        with pytest.raises(ValueError, match="fsx serve"):
            ClusterSupervisor(tmp_path / "cl", [{}])

    def test_clean_lifecycle_both_ranks_done(self, tmp_path):
        sup = self._sup(tmp_path,
                        [{"stub_serve_s": 0.2}, {"stub_serve_s": 0.2}])
        sup.boot()
        agg = sup.run()
        assert agg["restarts"] == [0, 0]
        assert agg["failed_ranks"] == []
        assert sorted(r["rank"] for r in agg["reports"]) == [0, 1]
        # the supervisor stamped one shared epoch for the whole fleet
        assert agg["t0_ns"] > 0
        for r in range(2):
            st = StatusBlock(status_path(tmp_path / "cl", r))
            assert st.ctl_get("c_state") == schema.CSTATE_DONE
            assert st.ctl_get("c_t0") == agg["t0_ns"]

    def test_crash_fail_open_restart_restores_checkpoint(self, tmp_path):
        """Rank 1 hard-dies mid-serve (``os._exit``, no DONE): the
        supervisor must killpg + respawn it at gen 1 handing it its
        last checkpoint, while rank 0 finishes untouched."""
        ck = tmp_path / "ck_r1.npz"
        ck.write_bytes(b"stub flow memory")
        sup = self._sup(
            tmp_path,
            [{"stub_serve_s": 0.6},
             {"stub_serve_s": 0.6, "stub_crash_after_s": 0.1,
              "checkpoint": str(ck)}])
        sup.boot()
        agg = sup.run()
        assert agg["restarts"] == [0, 1]
        assert agg["failed_ranks"] == []
        gen1 = [r for r in agg["reports"]
                if r["rank"] == 1 and r["gen"] == 1]
        assert gen1, "no gen-1 report from the restarted rank"
        assert gen1[0]["restored"] == str(ck)
        # rank 0's report is gen 0: the survivor never restarted
        assert [r["gen"] for r in agg["reports"] if r["rank"] == 0] \
            == [0]

    def test_restart_without_checkpoint_restores_nothing(self, tmp_path):
        sup = self._sup(
            tmp_path,
            [{"stub_serve_s": 0.5},
             {"stub_serve_s": 0.5, "stub_crash_after_s": 0.1}])
        sup.boot()
        agg = sup.run()
        assert agg["restarts"] == [0, 1]
        gen1 = [r for r in agg["reports"]
                if r["rank"] == 1 and r["gen"] == 1]
        assert gen1 and gen1[0]["restored"] is None

    def test_repeated_kills_exhaust_max_restarts(self, tmp_path):
        """The chaos hook driven past the restart budget: after
        ``max_restarts`` respawns the next death is terminal and the
        rank lands in ``failed_ranks`` (the fleet keeps serving the
        other shard — fail-open, not fail-stop)."""
        sup = self._sup(tmp_path,
                        [{"stub_serve_s": 30.0}, {"stub_serve_s": 30.0}],
                        max_restarts=1)
        sup.boot()
        try:
            deadline = time.monotonic() + 30.0
            killed = 0
            st1 = StatusBlock(status_path(tmp_path / "cl", 1))
            want_gen, hbeat_floor = 0, 0
            while killed < 2 and time.monotonic() < deadline:
                sup.poll()
                # a status field is its writer's last words, so the
                # corpse still reads SERVING after a kill — only a
                # heartbeat ADVANCE past the kill-time value proves the
                # next generation is alive and ticking
                if (st1.ctl_get("c_gen") == want_gen
                        and st1.ctl_get("c_hbeat") > hbeat_floor):
                    hbeat_floor = st1.ctl_get("c_hbeat")
                    sup.kill(1)
                    killed += 1
                    want_gen += 1
                time.sleep(0.02)
            assert killed == 2
            while 1 not in sup._failed \
                    and time.monotonic() < deadline:
                sup.poll()
                time.sleep(0.02)
            assert sup.restarts[1] == 1
            assert 1 in sup._failed
            assert sup._procs[0].is_alive()  # the survivor serves on
        finally:
            sup.close()
        assert sup.aggregate()["failed_ranks"] == [1]

    def test_request_stop_drains_fleet_early(self, tmp_path):
        sup = self._sup(tmp_path,
                        [{"stub_serve_s": 30.0}, {"stub_serve_s": 30.0}])
        sup.boot()
        t0 = time.monotonic()
        agg = sup.run(max_seconds=0.3)
        assert time.monotonic() - t0 < 15.0  # not the 30 s serve
        assert agg["failed_ranks"] == []
        assert agg["restarts"] == [0, 0]

    def test_aggregate_counts_each_rank_latest_gen_once(self, tmp_path):
        import json

        # a rank that wrote a gen-0 report and was then restarted must
        # not have both generations' records summed against one wall
        sup = self._sup(tmp_path, [{}, {}])
        d = tmp_path / "cl"
        d.mkdir(parents=True, exist_ok=True)
        for r, g, n, w in [(0, 0, 100, 1.0), (0, 1, 40, 0.5),
                           (1, 0, 60, 2.0)]:
            (d / f"report_r{r}_g{g}.json").write_text(json.dumps(
                {"rank": r, "gen": g,
                 "report": {"records": n, "batches": 1, "wall_s": w}}))
        agg = sup.aggregate()
        assert agg["records"] == 40 + 60
        assert agg["max_wall_s"] == 2.0

    def test_aggregate_merges_latency_hists_exactly(self, tmp_path):
        import json

        from flowsentryx_tpu.engine.metrics import LatencyHist

        # per-rank HDR bucket counts merge into EXACT cluster
        # percentiles (never averaged per-rank p99s); a rank without
        # a latency block (a stub, an old report) is skipped
        h0, h1 = LatencyHist(), LatencyHist()
        for _ in range(99):
            h0.add(100e-6)
        h0.add(50e-3)          # rank 0's one slow record
        for _ in range(100):
            h1.add(200e-6)
        sup = self._sup(tmp_path, [{}, {}])
        d = tmp_path / "cl"
        d.mkdir(parents=True, exist_ok=True)
        for r, h in ((0, h0), (1, h1)):
            (d / f"report_r{r}_g0.json").write_text(json.dumps(
                {"rank": r, "gen": 0,
                 "report": {"records": h.n, "batches": 1, "wall_s": 1.0,
                            "latency": {
                                "seal_to_verdict": h.to_dict(),
                                "hist": h.to_counts()}}}))
        (d / "report_r2_g0.json").write_text(json.dumps(
            {"rank": 2, "gen": 0,
             "report": {"records": 0, "batches": 0, "wall_s": 0.1}}))
        agg = sup.aggregate()
        lat = agg["latency"]
        ref = LatencyHist()
        ref.merge(h0)
        ref.merge(h1)
        assert lat["seal_to_verdict"] == ref.to_dict()
        assert lat["seal_to_verdict"]["n"] == 200
        # the merged p999 sees rank 0's slow tail, the p50 the bulk
        assert lat["seal_to_verdict"]["p999"] > 10_000
        assert lat["seal_to_verdict"]["p50"] < 500
        assert set(lat["per_rank_p99"]) == {"0", "1"}

    def test_boot_stamps_wall_epoch_twin(self, tmp_path):
        # the monotonic epoch's CLOCK_REALTIME twin (ISSUE 15): what a
        # peer HOST rebases this fleet's verdict wires with — stamped
        # into every status block next to c_t0
        sup = self._sup(tmp_path,
                        [{"stub_serve_s": 0.1}, {"stub_serve_s": 0.1}])
        sup.boot()
        agg = sup.run()
        assert agg["t0_wall_ns"] > 0
        for r in range(2):
            st = StatusBlock(status_path(tmp_path / "cl", r))
            assert st.ctl_get("c_t0_wall") == agg["t0_wall_ns"]

    def test_refusal_names_ranks_ages_and_remediation(self, tmp_path):
        """Satellite (ISSUE 15): the boot-over-live-plane refusal must
        tell the operator WHICH ranks are live, HOW fresh their
        heartbeats are, and WHAT to do — not just that it refused."""
        d = tmp_path / "cl"
        create_plane(d, 2)
        now_ns = time.clock_gettime_ns(time.CLOCK_MONOTONIC)
        for r, age_s in ((0, 1.0), (1, 3.0)):
            st = StatusBlock(status_path(d, r))
            st.ctl_set("c_state", schema.CSTATE_SERVING)
            st.ctl_set("c_hbeat", now_ns - int(age_s * 1e9))
        sup = self._sup(tmp_path,
                        [{"stub_serve_s": 0.1}, {"stub_serve_s": 0.1}])
        with pytest.raises(RuntimeError) as ei:
            sup.boot()
        msg = str(ei.value)
        assert "rank 0 heartbeated" in msg
        assert "rank 1 heartbeated" in msg
        assert "s ago" in msg            # the ages, human-readable
        assert "Remediation" in msg      # what to actually do
        assert "fresh directory" in msg

    def test_boot_ignores_future_heartbeat_as_stale(self, tmp_path):
        # CLOCK_MONOTONIC restarts at reboot: a persisted plane whose
        # heartbeats are AHEAD of the current clock is a dead fleet,
        # not a live one — boot must stomp it, not refuse
        d = tmp_path / "cl"
        create_plane(d, 2)
        st = StatusBlock(status_path(d, 0))
        st.ctl_set("c_state", schema.CSTATE_SERVING)
        st.ctl_set("c_hbeat",
                   time.clock_gettime_ns(time.CLOCK_MONOTONIC)
                   + int(1e14))
        sup = self._sup(tmp_path,
                        [{"stub_serve_s": 0.1}, {"stub_serve_s": 0.1}])
        sup.boot()
        agg = sup.run()
        assert agg["failed_ranks"] == []

    def test_boot_refuses_live_plane_stomps_dead_one(self, tmp_path):
        # create_plane re-truncates every mmap'd file: booting a new
        # fleet over a LIVE one would SIGBUS its serving engines and
        # double-consume their SPSC ring shards — refuse while
        # heartbeats are fresh, allow once the fleet is dead
        sup1 = self._sup(tmp_path,
                         [{"stub_serve_s": 30.0}, {"stub_serve_s": 30.0}])
        sup1.boot()
        try:
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                sts = [StatusBlock(status_path(tmp_path / "cl", r))
                       for r in range(2)]
                if all(st.ctl_get("c_state") == schema.CSTATE_SERVING
                       and st.ctl_get("c_hbeat") for st in sts):
                    break
                time.sleep(0.02)
            sup2 = self._sup(
                tmp_path,
                [{"stub_serve_s": 30.0}, {"stub_serve_s": 30.0}])
            with pytest.raises(RuntimeError, match="live engines"):
                sup2.boot()
        finally:
            sup1.close()
        # the fleet is dead now: the same dir must boot cleanly
        sup3 = self._sup(tmp_path,
                         [{"stub_serve_s": 0.1}, {"stub_serve_s": 0.1}])
        sup3.boot()
        agg = sup3.run()
        assert agg["failed_ranks"] == []

    def test_drain_overrun_rank_is_failed_not_silent_success(
            self, tmp_path):
        # a rank that ignores stop and overruns the drain bound is
        # force-killed by close() — it MUST surface in failed_ranks
        # (the CLI exit code keys on it); reading a truncated drain
        # as success would hide lost shard records from the operator
        sup = self._sup(tmp_path, [
            {"stub_serve_s": 0.2},
            {"stub_serve_s": 30.0, "stub_ignore_stop": True},
        ])
        sup.boot()
        agg = sup.run(max_seconds=0.3, drain_timeout_s=1.0)
        assert agg["failed_ranks"] == [1]
        assert agg["restarts"] == [0, 0]  # killed, not crash-restarted


class TestPinCores:
    """The per-core deployment shape: rank r owns core r with an
    XLA pool sized to its one core (runner.pin_core_for/pin_to_core,
    `fsx cluster --pin-cores`)."""

    def test_auto_pins_when_fleet_fits_host(self):
        from flowsentryx_tpu.cluster.runner import pin_core_for

        assert [pin_core_for(r, 2, "auto", ncpu=2)
                for r in range(2)] == [0, 1]

    def test_auto_leaves_oversubscribed_fleet_to_scheduler(self):
        from flowsentryx_tpu.cluster.runner import pin_core_for

        # forcing two engines to time-slice one core while another
        # idles is worse than letting the scheduler balance
        assert pin_core_for(0, 4, "auto", ncpu=2) is None

    def test_on_pins_modulo_host(self):
        from flowsentryx_tpu.cluster.runner import pin_core_for

        assert pin_core_for(3, 4, "on", ncpu=2) == 1

    def test_off_never_pins(self):
        from flowsentryx_tpu.cluster.runner import pin_core_for

        assert pin_core_for(0, 2, "off", ncpu=2) is None

    def test_pin_to_core_sets_mask_and_right_sizes_pool(self):
        from flowsentryx_tpu.cluster.runner import pin_to_core

        mask0 = os.sched_getaffinity(0)
        env0 = os.environ.get("XLA_FLAGS")
        try:
            pin_to_core(0)
            assert os.sched_getaffinity(0) == {0}
            # the pool right-sizing must ride XLA_FLAGS (read at
            # backend init), not a jax import-order requirement
            assert ("intra_op_parallelism_threads=1"
                    in os.environ["XLA_FLAGS"])
        finally:
            os.sched_setaffinity(0, mask0)
            if env0 is None:
                os.environ.pop("XLA_FLAGS", None)
            else:
                os.environ["XLA_FLAGS"] = env0


# ---------------------------------------------------------------------------
# pre-boot CLI refusals (all jax-free, each naming its problem)
# ---------------------------------------------------------------------------


class TestClusterCLI:
    def _run(self, argv, capsys):
        from flowsentryx_tpu.cli import main

        rc = main(argv)
        return rc, capsys.readouterr()

    def test_cluster_flag_refusals(self, capsys):
        rc, cap = self._run(["cluster", "--engines", "1"], capsys)
        assert rc == 1 and "fsx serve" in cap.err
        rc, cap = self._run(
            ["cluster", "--engines", "2", "--shards", "3"], capsys)
        assert rc == 1 and "multiple" in cap.err
        # 0 % 2 == 0 must not sneak an engine fleet with no shards
        # past the refusals into N jax boots that all crash
        rc, cap = self._run(
            ["cluster", "--engines", "2", "--shards", "0"], capsys)
        assert rc == 1 and "cannot feed" in cap.err
        rc, cap = self._run(
            ["cluster", "--engines", "2", "--shards", "-2"], capsys)
        assert rc == 1 and "cannot feed" in cap.err
        rc, cap = self._run(
            ["cluster", "--checkpoint", "/tmp/same_path.npz"], capsys)
        assert rc == 1 and "{rank}" in cap.err
        # a stray placeholder must refuse pre-boot, not KeyError after
        # the jax boot; a format-spec'd {rank:02d} is a VALID template
        # (proved by falling through to the next refusal in line)
        rc, cap = self._run(
            ["cluster", "--checkpoint", "/tmp/ck_{rank}_{host}.npz"],
            capsys)
        assert rc == 1 and "rank= alone" in cap.err
        rc, cap = self._run(
            ["cluster", "--checkpoint", "/tmp/ck_{rank:02d}.npz",
             "--checkpoint-every", "-1"], capsys)
        assert rc == 1 and "--checkpoint-every must be >= 0" in cap.err
        rc, cap = self._run(
            ["cluster", "--checkpoint-every", "5"], capsys)
        assert rc == 1 and "--checkpoint" in cap.err

    def test_cluster_multi_host_flag_refusals(self, capsys):
        # the --hosts trio (ISSUE 15), each refusal naming its problem
        rc, cap = self._run(
            ["cluster", "--hosts", "10.0.0.1:9000,10.0.0.2:9000"],
            capsys)
        assert rc == 1 and "--host-id" in cap.err
        rc, cap = self._run(["cluster", "--host-id", "0"], capsys)
        assert rc == 1 and "--hosts" in cap.err
        rc, cap = self._run(
            ["cluster", "--gossip-listen", "0.0.0.0:9000"], capsys)
        assert rc == 1 and "--hosts" in cap.err
        rc, cap = self._run(
            ["cluster", "--hosts", "10.0.0.1:9000,nonsense",
             "--host-id", "0"], capsys)
        assert rc == 1 and "not IP:PORT" in cap.err
        rc, cap = self._run(
            ["cluster", "--hosts", "10.0.0.1:9000", "--host-id", "0"],
            capsys)
        assert rc == 1 and "1 host(s)" in cap.err
        rc, cap = self._run(
            ["cluster", "--hosts", "10.0.0.1:9000,10.0.0.2:9000",
             "--host-id", "2"], capsys)
        assert rc == 1 and "not in [0, 2)" in cap.err
        rc, cap = self._run(
            ["cluster", "--hosts", "10.0.0.1:9000,10.0.0.2:9000",
             "--host-id", "0", "--gossip-listen", "bad"], capsys)
        assert rc == 1 and "--gossip-listen" in cap.err
        # derived engine ports (base+1+r) must fit under 65536 too —
        # otherwise the "refusal" is a bind crash-loop in a child
        rc, cap = self._run(
            ["cluster", "--hosts", "10.0.0.1:65534,10.0.0.2:9000",
             "--host-id", "0"], capsys)
        assert rc == 1 and "exceeds 65535" in cap.err
        # a 1-engine rank of a multi-host fleet is LEGITIMATE: the
        # --engines >= 2 refusal must not fire before the next check
        # in line (here: a bogus listen port keeps it jax-free)
        rc, cap = self._run(
            ["cluster", "--engines", "1", "--shards", "1",
             "--hosts", "10.0.0.1:9000,10.0.0.2:9000",
             "--host-id", "0", "--gossip-listen", "x:0"], capsys)
        assert rc == 1 and "fsx serve" not in cap.err

    def test_serve_cluster_rank_refusals(self, tmp_path, capsys):
        base = ["serve", "--scenario", "benign", "--packets", "64"]
        rc, cap = self._run(base + ["--cluster-rank", "0"], capsys)
        assert rc == 1 and "R/N" in cap.err
        rc, cap = self._run(base + ["--cluster-rank", "0/1"], capsys)
        assert rc == 1 and "fsx serve" in cap.err
        rc, cap = self._run(base + ["--cluster-rank", "2/2"], capsys)
        assert rc == 1 and "[0, 2)" in cap.err
        rc, cap = self._run(base + ["--cluster-rank", "0/2"], capsys)
        assert rc == 1 and "--ingest-workers" in cap.err
        ring = ["--feature-ring", str(tmp_path / "fring"),
                "--ingest-workers", "1"]
        rc, cap = self._run(
            base + ring + ["--cluster-rank", "0/2"], capsys)
        assert rc == 1 and "--cluster-dir" in cap.err
        rc, cap = self._run(
            base + ring + ["--cluster-rank", "0/2",
                           "--cluster-dir", str(tmp_path / "nowhere")],
            capsys)
        assert rc == 1 and "not an initialized gossip plane" in cap.err
        # an initialized plane whose epoch was never stamped: refused
        # BEFORE jax boots — an engine serving against t0=0 would
        # publish untils no peer can compare
        create_plane(tmp_path / "plane", 2)
        rc, cap = self._run(
            base + ring + ["--cluster-rank", "0/2",
                           "--cluster-dir", str(tmp_path / "plane")],
            capsys)
        assert rc == 1 and "epoch" in cap.err and "c_t0" in cap.err


# ---------------------------------------------------------------------------
# cluster-vs-single-engine parity + engine gossip wiring (in-process)
# ---------------------------------------------------------------------------


class TestClusterParity:
    """The cluster topology is the IP-hash partition rule extended to
    whole engines, and a sealed batch never mixes shards — so serving
    the SAME prefilled 2-shard fan-out as one engine with two drain
    workers or as two rank engines with one worker each must produce
    byte-identical blacklists (keys AND untils, under the shared t0
    epoch) and exactly-additive stats.  Probed empirically before this
    test pinned it: the equality is exact, not approximate, BECAUSE
    batch composition is per-shard in both topologies (contrast
    ``test_sharded_ingest_two_workers_equivalent``, where inline
    whole-stream batches legally drift at decision boundaries)."""

    BATCH = 256

    def _records(self):
        from flowsentryx_tpu.engine.traffic import (
            Scenario, TrafficGen, TrafficSpec,
        )

        return TrafficGen(TrafficSpec(
            scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
            n_attack_ips=8, n_benign_ips=24, attack_fraction=0.8,
            seed=13,
        )).next_records(self.BATCH * 8)

    def _fill(self, base, recs, total):
        from flowsentryx_tpu.engine.shm import ShmRing

        shard = schema.shard_of(recs["saddr"], total)
        for k in range(total):
            ring = ShmRing.create(
                schema.shard_ring_path(base, k, total),
                1 << 12, schema.FLOW_RECORD_DTYPE)
            part = recs[shard == np.uint32(k)]
            assert ring.produce(part) == len(part)

    def _drain(self, base, workers, offset, total, t0, gossip=None):
        import jax

        from test_engine import small_cfg

        from flowsentryx_tpu.engine import Engine
        from flowsentryx_tpu.ingest import ShardedIngest

        src = ShardedIngest(base, workers, shard_offset=offset,
                            total_shards=total, queue_slots=16,
                            precompact=False, t0_grace_s=0.2)
        sink = CollectSink()
        eng = Engine(small_cfg(batch=self.BATCH, cap=1 << 14,
                               pps_threshold=200.0, bps_threshold=1e9),
                     src, sink, readback_depth=4, t0_ns=t0,
                     sink_thread=False, gossip=gossip)
        try:
            src.request_stop()
            with jax.transfer_guard("disallow"):
                rep = eng.run()
        finally:
            src.close()
        return rep, sink

    def test_two_rank_engines_equal_one_engine_two_workers(
            self, tmp_path):
        recs = self._records()
        t0 = int(recs["ts_ns"].min())

        base_a = str(tmp_path / "single")
        self._fill(base_a, recs, 2)
        rep_a, sink_a = self._drain(base_a, 2, 0, 2, t0)

        base_b = str(tmp_path / "cluster")
        self._fill(base_b, recs, 2)
        create_plane(tmp_path / "plane", 2)
        planes = [GossipPlane(tmp_path / "plane", r, 2,
                              sink=CollectSink(), merge_interval_s=0.0)
                  for r in range(2)]
        rep_b0, sink_b0 = self._drain(base_b, 1, 0, 2, t0,
                                      gossip=planes[0])
        rep_b1, sink_b1 = self._drain(base_b, 1, 1, 2, t0,
                                      gossip=planes[1])

        # lossless, and every record on exactly one engine
        assert rep_b0.records + rep_b1.records \
            == rep_a.records == len(recs)
        # blacklist parity: keys AND untils byte-identical (the ranks'
        # shards are disjoint, so plain dict-merge is the cluster view)
        merged = dict(sink_b0.blocked)
        merged.update(sink_b1.blocked)
        assert merged == sink_a.blocked
        assert sink_b0.blocked.keys() & sink_b1.blocked.keys() == set()
        # stats parity: every counter exactly additive across ranks
        for field in rep_a.stats:
            assert rep_b0.stats[field] + rep_b1.stats[field] \
                == rep_a.stats[field], field
        # both shards actually exercised mitigation
        assert sink_b0.blocked and sink_b1.blocked

        # engine gossip wiring (Engine._apply_updates -> publish,
        # Engine._reap_ready -> tick): rank 1 served AFTER rank 0
        # published, so its merged view must already hold rank 0's
        # whole blacklist, byte-identical untils, delivered to ITS
        # gossip sink (the second path to the kernel tier)
        r1 = rep_b1.cluster
        assert r1["merged_digest"] == rep_b0.cluster["published_digest"]
        assert r1["rx_seq_gaps"] == 0
        assert planes[1].sink.blocked == sink_b0.blocked
        # the late peer's publishes converge on rank 0's next tick
        planes[0].tick(force=True)
        assert planes[0].report()["merged_digest"] == \
            r1["published_digest"]
        assert planes[0].sink.blocked == sink_b1.blocked

    def test_cluster_report_rides_engine_report(self, tmp_path):
        """EngineReport.cluster is None outside cluster serving, and
        carries the gossip accounting inside it."""
        from flowsentryx_tpu.engine import ArraySource, Engine, NullSink
        from test_engine import small_cfg

        rep = Engine(small_cfg(batch=128),
                     ArraySource(self._records()[:128]),
                     NullSink(), sink_thread=False).run()
        assert rep.cluster is None

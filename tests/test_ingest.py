"""Sharded ingest subsystem tests (flowsentryx_tpu/ingest/).

Covers the cross-process transport (SealedBatchQueue wraparound and
backpressure), the ordering contract (SeqTracker gap/missing
accounting, IP-hash shard affinity), and the worker lifecycle against
REAL spawned drain workers over Python-created ring shards: lossless
drain-on-stop, and crash → engine fail-open on the remaining shards.
The engine-level N=0 vs N=2 verdict equivalence lives in
tests/test_engine.py (it needs the full Engine).
"""

import platform
import time

import numpy as np
import pytest

from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import BatchConfig
from flowsentryx_tpu.engine.shm import SealedBatchQueue, ShmRing
from flowsentryx_tpu.ingest import SeqTracker, ShardedIngest

pytestmark = pytest.mark.skipif(
    platform.system() != "Linux",
    reason="shm ingest assumes Linux (TSO + CLOCK_MONOTONIC contract)",
)


def make_records(n, t0_ns=1_000_000_000, seed=0, n_ips=64):
    rng = np.random.default_rng(seed)
    rec = np.zeros(n, schema.FLOW_RECORD_DTYPE)
    pool = rng.integers(1, 1 << 24, n_ips).astype(np.uint32)
    rec["saddr"] = pool[rng.integers(0, n_ips, n)]
    rec["ts_ns"] = t0_ns + np.arange(n, dtype=np.uint64) * 1000
    rec["pkt_len"] = 64
    rec["ip_proto"] = 17
    rec["feat"] = rng.integers(0, 1 << 20, (n, schema.NUM_FEATURES))
    return rec


class TestSealedBatchQueue:
    def test_roundtrip_and_wraparound(self, tmp_path):
        """Far more batches than slots through a 4-slot queue: payloads
        and headers must survive the index wrap exactly."""
        payload_words = 3 * 4
        q = SealedBatchQueue.create(tmp_path / "q", 4, payload_words)
        consumer = SealedBatchQueue(tmp_path / "q", payload_words)
        sent = 0
        got = []
        while sent < 23 or consumer.readable():
            if sent < 23:
                payload = np.arange(
                    payload_words, dtype=np.uint32) + 1000 * sent
                if q.produce_batch(payload, seq=sent + 1, n_records=sent,
                                   wire_id=schema.WIRE_ID_RAW48,
                                   seal_ns=10**9 + sent,
                                   fill_dur_us=sent * 7):
                    sent += 1
            out = consumer.consume_batch()
            if out is not None:
                got.append(out)
        assert len(got) == 23
        for i, (hdr, payload) in enumerate(got):
            assert int(hdr[0]) | (int(hdr[1]) << 32) == i + 1
            assert int(hdr[2]) == i
            assert int(hdr[4]) | (int(hdr[5]) << 32) == 10**9 + i
            assert int(hdr[6]) == i * 7
            np.testing.assert_array_equal(
                payload, np.arange(payload_words, dtype=np.uint32) + 1000 * i)

    def test_full_queue_backpressure(self, tmp_path):
        q = SealedBatchQueue.create(tmp_path / "q", 2, 8)
        payload = np.zeros(8, np.uint32)

        def push(seq):
            return q.produce_batch(payload, seq=seq, n_records=1,
                                   wire_id=0, seal_ns=1, fill_dur_us=0)

        assert push(1) and push(2)
        assert not push(3)  # full: producer must retry, not overwrite
        assert q.consume_batch() is not None
        assert push(3)

    def test_payload_shape_mismatch_rejected(self, tmp_path):
        SealedBatchQueue.create(tmp_path / "q", 4, 16)
        with pytest.raises(ValueError, match="payload"):
            SealedBatchQueue(tmp_path / "q", expect_payload_words=32)

    def test_control_block_fields_are_independent(self, tmp_path):
        q = SealedBatchQueue.create(tmp_path / "q", 2, 4)
        for i, name in enumerate(("hbeat", "first_ts", "t0", "stop",
                                  "wstate", "emit_drop")):
            q.ctl_set(name, 100 + i)
        for i, name in enumerate(("hbeat", "first_ts", "t0", "stop",
                                  "wstate", "emit_drop")):
            assert q.ctl_get(name) == 100 + i

    def test_emit_drop_unburns_seq_and_counts(self, tmp_path, monkeypatch):
        """A stop-drain give-up on a full queue must NOT look like
        corruption: the batch's seq is un-burned (later emits stay
        consecutive, no gap) and the loss lands in the emit_drop
        counter instead."""
        from flowsentryx_tpu.ingest import worker as worker_mod

        monkeypatch.setattr(worker_mod, "EMIT_STOP_TIMEOUT_S", 0.05)
        max_batch, words = 2, 4
        payload_words = (max_batch + 1) * words
        q = SealedBatchQueue.create(tmp_path / "q", 2, payload_words)

        class _StubBatcher:
            def pop_seal_time(self):
                return time.perf_counter()

        em = worker_mod._Emitter(
            q, _StubBatcher(), schema.WIRE_ID_RAW48, max_batch)
        buf = np.zeros((max_batch + 1, words), np.uint32)
        buf[max_batch, 0] = 2
        em.emit(buf, stopping=False)  # seq 1
        em.emit(buf, stopping=False)  # seq 2 — queue now full
        em.emit(buf, stopping=True)   # full + stopping: bounded, dropped
        assert em.seq == 2
        assert q.ctl_get("emit_drop") == 1
        consumer = SealedBatchQueue(tmp_path / "q", payload_words)
        assert consumer.consume_batch() is not None  # frees a slot
        em.emit(buf, stopping=True)   # enqueues as seq 3
        assert em.seq == 3 and q.ctl_get("emit_drop") == 1
        hdr, _ = consumer.consume_batch()
        assert int(hdr[0]) == 2
        hdr, _ = consumer.consume_batch()
        assert int(hdr[0]) == 3  # consecutive across the drop: no gap


class TestSealedBatchQueueViews:
    """peek_batches()/release() — the zero-copy dequeue half of the
    single-copy dispatch pipeline."""

    def test_peek_views_match_pop_copies_across_wraparound(self, tmp_path):
        """Fill far past the 4-slot ring boundary; every peeked view
        must decode byte-identically (header AND payload) to the
        consume_batch copy of the same slot."""
        payload_words = 3 * 4
        q = SealedBatchQueue.create(tmp_path / "q", 4, payload_words)
        consumer = SealedBatchQueue(tmp_path / "q", payload_words)
        sent = 0
        seen = 0
        while sent < 23 or consumer.readable():
            if sent < 23:
                payload = np.arange(
                    payload_words, dtype=np.uint32) + 1000 * sent
                if q.produce_batch(payload, seq=sent + 1, n_records=sent,
                                   wire_id=schema.WIRE_ID_RAW48,
                                   seal_ns=10**9 + sent,
                                   fill_dur_us=sent * 7):
                    sent += 1
            for hdr_v, view in consumer.peek_batches(2):
                staged = view.copy()  # the arena-style stage-then-release
                hdr_c, payload_c = consumer.consume_batch()
                np.testing.assert_array_equal(hdr_v, hdr_c)
                np.testing.assert_array_equal(staged, payload_c)
                assert int(hdr_c[0]) == seen + 1  # oldest-first order
                seen += 1
        assert seen == 23

    def test_partial_release_keeps_remainder_peekable(self, tmp_path):
        q = SealedBatchQueue.create(tmp_path / "q", 4, 8)
        consumer = SealedBatchQueue(tmp_path / "q", 8)
        for seq in (1, 2, 3):
            assert q.produce_batch(np.full(8, seq, np.uint32), seq=seq,
                                   n_records=1, wire_id=0, seal_ns=1,
                                   fill_dur_us=0)
        assert len(consumer.peek_batches(8)) == 3
        consumer.release(2)
        left = consumer.peek_batches(8)
        assert len(left) == 1 and int(left[0][1][0]) == 3
        assert consumer.readable() == 1

    def test_mutate_after_release_never_reaches_staged_copy(self, tmp_path):
        """The slot-release safety rule: stage BEFORE release, and a
        producer overwrite of the released slot never reaches the
        staged bytes — while the released VIEW (deliberately) does see
        the overwrite, which is exactly why the engine stages first."""
        q = SealedBatchQueue.create(tmp_path / "q", 2, 8)
        consumer = SealedBatchQueue(tmp_path / "q", 8)

        def push(tag, seq):
            return q.produce_batch(np.full(8, tag, np.uint32), seq=seq,
                                   n_records=1, wire_id=0, seal_ns=1,
                                   fill_dur_us=0)

        assert push(0xAAAA, 1) and push(0xBBBB, 2)
        assert not push(0xCCCC, 3)          # full: backpressure holds
        peeked = consumer.peek_batches(2)
        assert len(peeked) == 2
        view_a = peeked[0][1]
        arena_row = np.empty_like(view_a)
        arena_row[:] = view_a               # the ONE staging copy
        consumer.release(1)                 # slot A back to the producer
        assert push(0xCCCC, 3)              # overwrites A's slot bytes
        np.testing.assert_array_equal(
            arena_row, np.full(8, 0xAAAA, np.uint32))
        # slot B untouched, C now peekable behind it
        (_, view_b), (_, view_c) = consumer.peek_batches(2)
        assert int(view_b[0]) == 0xBBBB and int(view_c[0]) == 0xCCCC
        # the released slot's view is DEAD: it shows the new producer
        # bytes, not the batch it used to name
        assert int(view_a[0]) == 0xCCCC


class TestWorkerBackoff:
    """The drain loop's bounded spin-then-sleep idle policy."""

    def test_spin_budget_then_sleep(self):
        from flowsentryx_tpu.ingest.worker import _Backoff

        b = _Backoff(spin_us=200_000, idle_us=100)
        t0 = time.perf_counter()
        assert b.idle() is False        # inside the budget: no sleep
        assert time.perf_counter() - t0 < 0.1
        assert _Backoff(spin_us=0, idle_us=100).idle() is True  # legacy
        b3 = _Backoff(spin_us=500, idle_us=100)
        b3.idle()
        time.sleep(0.002)               # budget expires
        assert b3.idle() is True
        b3.reset()                      # a productive poll re-arms
        assert b3.idle() is False

    def test_params_ride_the_ctl_block(self, tmp_path):
        """ShardedIngest(spin_us=, idle_us=) must land in every queue's
        ctl block BEFORE the workers spawn, where worker_main reads
        them (and where a test can pin them)."""
        base = str(tmp_path / "fring")
        _make_shard_rings(base, 2)
        ing = ShardedIngest(base, 2, precompact=False, t0_grace_s=0.2,
                            spin_us=77, idle_us=333)
        ing.start(BatchConfig(max_batch=64, deadline_us=10_000),
                  schema.WIRE_RAW48, None)
        try:
            for q in ing._queues:
                assert q.ctl_get("spin_us") == 77
                assert q.ctl_get("idle_us") == 333
        finally:
            ing.close()

    def test_negative_params_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="spin_us"):
            ShardedIngest(str(tmp_path / "r"), 1, precompact=False,
                          spin_us=-1)


class TestPollBatchesInto:
    """The staging dequeue the engine's zero-copy loop drives."""

    def test_drains_losslessly_into_rotating_rows(self, tmp_path):
        """poll_batches_into over a real fleet: staged rows carry the
        same records the copying protocol would, with slots released
        eagerly (queue drains even though the caller never consumed)."""
        base = str(tmp_path / "fring")
        rings = _make_shard_rings(base, 2)
        rec = make_records(256 * 4 + 19, n_ips=64)
        parts = _route(rec, 2)
        for ring, part in zip(rings, parts):
            assert ring.produce(part) == len(part)
        ing = _start_fleet(base, 2)
        try:
            deadline = time.monotonic() + 20
            while ing.t0_ns is None:
                ing.poll_batches(0)
                assert time.monotonic() < deadline
                time.sleep(0.01)
            ing.request_stop()
            words = schema.RECORD_WORDS
            dst = np.zeros((4, 257, words), np.uint32)
            total = 0
            got_rows = 0
            deadline = time.monotonic() + 30
            while not ing.exhausted():
                metas = ing.poll_batches_into(dst, 4)
                for sb in metas:
                    assert sb.raw.base is not None  # a dst view, not shm
                    assert sb.raw.shape == (257, words)
                    # meta row mirrors the header count
                    assert int(sb.raw[256, 0]) == sb.n_records
                    total += sb.n_records
                    got_rows += 1
                if not metas:
                    assert time.monotonic() < deadline
                    time.sleep(0.005)
            total += sum(sb.n_records
                         for sb in ing.poll_batches_into(dst, 4))
        finally:
            ing.close()
        assert total == len(rec)
        stats = ing.ingest_stats()
        assert all(w["seq_gaps"] == 0 for w in stats["workers"].values())


class TestSeqTracker:
    def test_in_order(self):
        t = SeqTracker(2)
        assert t.note(0, 1) and t.note(0, 2) and t.note(1, 1)
        assert t.gaps == [0, 0] and t.missing == [0, 0]

    def test_forward_jump_counts_missing(self):
        t = SeqTracker(1)
        t.note(0, 1)
        assert not t.note(0, 4)  # 2 and 3 never arrived
        assert t.gaps[0] == 1 and t.missing[0] == 2
        assert t.note(0, 5)  # resynced

    def test_backward_step_counts_gap_not_missing(self):
        t = SeqTracker(1)
        for s in (1, 2, 3, 4, 5):
            t.note(0, s)
        assert not t.note(0, 2)  # torn restart re-emitting old numbers
        assert t.gaps[0] == 1 and t.missing[0] == 0


class TestShardAffinity:
    def test_shard_of_mirrors_daemon_hash(self):
        """Python and fsxd must route identically; the formula is the
        contract (Fibonacci hash, fsx_shard_of in daemon/fsxd.cpp)."""
        saddr = np.random.default_rng(3).integers(
            0, 1 << 32, 4096, dtype=np.uint64).astype(np.uint32)
        for n in (1, 2, 3, 4, 8):
            expect = ((saddr.astype(np.uint64) * 2654435761) >> 16) % n
            np.testing.assert_array_equal(
                schema.shard_of(saddr, n), expect.astype(np.uint32))

    def test_flow_affinity(self):
        """All records of one source land on one shard — the ordering
        guarantee the subsystem is built on."""
        rec = make_records(4096, n_ips=32)
        sh = schema.shard_of(rec["saddr"], 4)
        for ip in np.unique(rec["saddr"]):
            assert len(np.unique(sh[rec["saddr"] == ip])) == 1

    def test_shard_ring_path(self):
        assert schema.shard_ring_path("/tmp/r", 0, 1) == "/tmp/r"
        assert schema.shard_ring_path("/tmp/r", 2, 4) == "/tmp/r.2"


def _make_shard_rings(base, n_shards, capacity=1 << 14):
    return [
        ShmRing.create(schema.shard_ring_path(base, k, n_shards),
                       capacity, schema.FLOW_RECORD_DTYPE)
        for k in range(n_shards)
    ]


def _route(rec, n_shards):
    sh = schema.shard_of(rec["saddr"], n_shards)
    return [rec[sh == k] for k in range(n_shards)]


def _start_fleet(base, n_workers, max_batch=256):
    ing = ShardedIngest(base, n_workers, queue_slots=16, precompact=False,
                        t0_grace_s=0.2)
    ing.start(BatchConfig(max_batch=max_batch, deadline_us=10_000),
              schema.WIRE_RAW48, None)
    ing.wait_ready()
    return ing


def _drain(ing, deadline_s=30.0):
    out = []
    deadline = time.monotonic() + deadline_s
    while not ing.exhausted():
        got = ing.poll_batches(8)
        out.extend(got)
        if not got:
            assert time.monotonic() < deadline, "fleet never drained"
            time.sleep(0.005)
    out.extend(ing.poll_batches(64))
    return out


class TestWorkerFleet:
    def test_lossless_drain_on_stop(self, tmp_path):
        """Produce → stop → every record comes back sealed, in per-
        worker seq order, including the partial tail batches."""
        base = str(tmp_path / "fring")
        rings = _make_shard_rings(base, 2)
        rec = make_records(256 * 5 + 37, n_ips=64)
        parts = _route(rec, 2)
        for ring, part in zip(rings, parts):
            assert ring.produce(part) == len(part)
        ing = _start_fleet(base, 2)
        try:
            # engine-side epoch handshake, then ask for drain-on-stop
            deadline = time.monotonic() + 20
            while ing.t0_ns is None:
                ing.poll_batches(0)
                assert time.monotonic() < deadline
                time.sleep(0.01)
            assert ing.t0_ns == int(rec["ts_ns"].min())
            ing.request_stop()
            batches = _drain(ing)
        finally:
            ing.close()
        stats = ing.ingest_stats()
        assert sum(sb.n_records for sb in batches) == len(rec)
        per_worker = [sum(1 for sb in batches if sb.worker == k)
                      for k in range(2)]
        for k in range(2):
            w = stats["workers"][str(k)]
            assert w["records"] == len(parts[k])
            assert w["batches"] == per_worker[k]
            assert w["seq_gaps"] == 0 and w["seq_missing"] == 0
            assert not w["dead"]
        assert stats["dropped_tail_batches"] == 0

    def test_backlog_of_slow_record_time_is_conserved(self, tmp_path):
        """compact16 seals at every 65 ms of RECORD time, so a backlog
        of slow traffic (here 1 record/ms: 65 per seal) seals dozens of
        batches out of one drained chunk — far more than the worker's
        two wire buffers.  Each must reach the queue before its buffer
        is reused: every record comes back exactly once, in order
        (the first chip run lost 6,951 of 7.7 M records here, silently,
        with as many duplicated)."""
        base = str(tmp_path / "fring")
        (ring,) = _make_shard_rings(base, 1)
        rec = make_records(3000)
        rec["ts_ns"] = (1_000_000_000
                        + np.arange(len(rec), dtype=np.uint64) * 1_000_000)
        rec["pkt_len"] = np.arange(len(rec)) % 1400 + 60  # tell rows apart
        assert ring.produce(rec) == len(rec)
        ing = ShardedIngest(base, 1, queue_slots=4, precompact=False,
                            t0_grace_s=0.2)
        ing.start(BatchConfig(max_batch=256, deadline_us=10_000),
                  schema.WIRE_COMPACT16, dict(feat_mode="minifloat"))
        try:
            ing.wait_ready()
            deadline = time.monotonic() + 20
            while ing.t0_ns is None:
                ing.poll_batches(0)
                assert time.monotonic() < deadline
                time.sleep(0.01)
            ing.request_stop()
            batches = _drain(ing)
        finally:
            ing.close()
        assert len(batches) > 40  # one seal per 65 ms span, not per 256
        assert sum(sb.n_records for sb in batches) == len(rec)
        rows = np.concatenate([sb.raw[:sb.n_records] for sb in batches])
        want = np.concatenate([
            schema.compact_pack(rec[i:i + 65], int(rec["ts_ns"][i]),
                                feat_mode="minifloat")
            for i in range(0, len(rec), 65)])
        np.testing.assert_array_equal(rows, want)
        w = ing.ingest_stats()["workers"]["0"]
        assert w["seq_gaps"] == 0 and w["seq_missing"] == 0

    def test_external_t0_imposed_before_handshake(self, tmp_path):
        """A restored checkpoint's epoch (Engine.restore → _run_sealed →
        set_t0) must reach the workers instead of their min-first_ts
        handshake, so sealed device times and the sink's ns translation
        share one epoch."""
        base = str(tmp_path / "fring")
        rings = _make_shard_rings(base, 2)
        rec = make_records(512, n_ips=64)
        parts = _route(rec, 2)
        ing = _start_fleet(base, 2)
        try:
            epoch = int(rec["ts_ns"].min()) - 12_345
            ing.set_t0(epoch)
            for ring, part in zip(rings, parts):
                assert ring.produce(part) == len(part)
            ing.request_stop()
            batches = _drain(ing)
            assert ing.t0_ns == epoch  # not overwritten by the handshake
            assert sum(sb.n_records for sb in batches) == len(rec)
        finally:
            ing.close()

    def test_external_t0_after_handshake_errors(self, tmp_path):
        """Imposing a DIFFERENT epoch after batches were already sealed
        against the handshake's is unrecoverable — it must error loudly,
        not skew silently."""
        base = str(tmp_path / "fring")
        rings = _make_shard_rings(base, 2)
        rec = make_records(512, n_ips=64)
        for ring, part in zip(rings, _route(rec, 2)):
            ring.produce(part)
        ing = _start_fleet(base, 2)
        try:
            deadline = time.monotonic() + 20
            while ing.t0_ns is None:
                ing.poll_batches(0)
                assert time.monotonic() < deadline
                time.sleep(0.01)
            with pytest.raises(RuntimeError, match="already resolved"):
                ing.set_t0(ing.t0_ns + 999)
            ing.set_t0(ing.t0_ns)  # same epoch: idempotent no-op
        finally:
            ing.close()

    def test_worker_crash_fails_open(self, tmp_path):
        """Kill one worker mid-stream: the engine keeps consuming the
        remaining shard, and the death is surfaced, not raised."""
        base = str(tmp_path / "fring")
        rings = _make_shard_rings(base, 2)
        rec = make_records(256 * 4, n_ips=64)
        parts = _route(rec, 2)
        for ring, part in zip(rings, parts):
            ring.produce(part[: len(part) // 2])
        ing = _start_fleet(base, 2)
        try:
            deadline = time.monotonic() + 20
            while ing.t0_ns is None:
                ing.poll_batches(0)
                assert time.monotonic() < deadline
                time.sleep(0.01)
            ing._procs[0].terminate()
            ing._procs[0].join(timeout=10)
            # shard 1 keeps flowing after the crash
            rings[1].produce(parts[1][len(parts[1]) // 2:])
            ing.request_stop()
            batches = _drain(ing)
        finally:
            ing.close()
        stats = ing.ingest_stats()
        assert stats["dead_workers"] == [0]
        assert stats["workers"]["1"]["dead"] is False
        # every shard-1 record was served despite the shard-0 corpse
        got1 = sum(sb.n_records for sb in batches if sb.worker == 1)
        assert got1 == len(parts[1])
        assert stats["workers"]["1"]["seq_gaps"] == 0


class TestSlotValidation:
    """PR 13 slot-validation plane: corrupt/poisoned sealed slots are
    counted and SKIPPED — the drain survives, the loss lands in queue
    accounting, and both dequeue protocols agree (docs/CHAOS.md)."""

    def _fleet_with_sealed(self, tmp_path, n_batches=4, max_batch=256):
        base = str(tmp_path / "fring")
        ring = _make_shard_rings(base, 1)[0]
        rec = make_records(max_batch * n_batches, n_ips=64)
        assert ring.produce(rec) == len(rec)
        ing = _start_fleet(base, 1, max_batch=max_batch)
        deadline = time.monotonic() + 20
        while ing.t0_ns is None:
            ing.poll_batches(0)
            assert time.monotonic() < deadline
            time.sleep(0.01)
        q = ing._queues[0]
        while q.readable() < n_batches:
            assert time.monotonic() < deadline, "fleet never sealed"
            time.sleep(0.005)
        return ing, q, rec

    def _hdr_cell(self, q, slot_back=0):
        t = int(q._tail[0])
        return q._cells[(t + slot_back) & (q.slots - 1)]

    def test_bad_magic_slot_skipped_counted_not_fatal(self, tmp_path):
        """A sealed slot whose wire-id word (the per-slot magic) is
        garbage is skipped and counted; the drain worker is untouched
        and every OTHER record still serves."""
        ing, q, rec = self._fleet_with_sealed(tmp_path)
        try:
            cell = self._hdr_cell(q, 0)
            n_bad = int(cell[schema.BATCHQ_N_RECORDS_WORD])
            cell[schema.BATCHQ_WIRE_ID_WORD] = 0xDEAD
            ing.request_stop()
            batches = _drain(ing)
        finally:
            ing.close()
        stats = ing.ingest_stats()
        assert stats["bad_wire_slots"] == 1
        assert stats["workers"]["0"]["bad_wire_slots"] == 1
        assert not stats["workers"]["0"]["dead"]
        # the loss is exactly the refused slot, visible in accounting
        served = sum(sb.n_records for sb in batches)
        assert served + n_bad == len(rec)
        # a corrupt header's seq is not trusted: the NEXT good slot
        # shows the hole
        assert stats["workers"]["0"]["seq_gaps"] >= 1

    def test_poisoned_meta_quarantined_and_spooled(self, tmp_path):
        """A well-formed slot whose metadata violates the declared
        RANGE_* contracts (n_records > max_batch) is quarantined:
        counted, spooled to the quarantine dir, never dispatched,
        never a crash."""
        base = str(tmp_path / "fring")
        ring = _make_shard_rings(base, 1)[0]
        rec = make_records(256 * 3, n_ips=64)
        assert ring.produce(rec) == len(rec)
        spool = tmp_path / "spool"
        ing = ShardedIngest(str(base), 1, queue_slots=16,
                            precompact=False, t0_grace_s=0.2,
                            quarantine_dir=str(spool))
        ing.start(BatchConfig(max_batch=256, deadline_us=10_000),
                  schema.WIRE_RAW48, None)
        ing.wait_ready()
        try:
            deadline = time.monotonic() + 20
            while ing.t0_ns is None:
                ing.poll_batches(0)
                assert time.monotonic() < deadline
                time.sleep(0.01)
            q = ing._queues[0]
            while q.readable() < 3:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            t = int(q._tail[0])
            cell = q._cells[(t + 1) & (q.slots - 1)]
            bad_n = 256 + 9
            cell[schema.BATCHQ_N_RECORDS_WORD] = bad_n
            meta_off = (schema.BATCHQ_SLOT_HDR_WORDS
                        + 256 * schema.RECORD_WORDS)
            cell[meta_off] = bad_n  # coherent tear-free poison
            ing.request_stop()
            batches = _drain(ing)
        finally:
            ing.close()
        stats = ing.ingest_stats()
        assert stats["quarantined_batches"] == 1
        assert stats["quarantined_records"] == 256  # capped at max_batch
        assert stats["bad_wire_slots"] == 0
        dumps = list(spool.glob("quarantine_*.npy"))
        assert len(dumps) == 1
        # spooled payload is the refused slot's bytes, post-mortem-able
        assert np.load(dumps[0]).shape == (257, schema.RECORD_WORDS)
        served = sum(sb.n_records for sb in batches)
        assert served + 256 == len(rec)
        # seq was BURNED for the well-formed poisoned slot: no gap
        assert stats["workers"]["0"]["seq_gaps"] == 0

    def test_seq_gap_slot_counted_and_served(self, tmp_path):
        """Seq-word corruption surfaces in the gap/missing counters —
        the batch itself still serves (payload is intact; ordering
        damage is what the counters exist for)."""
        ing, q, rec = self._fleet_with_sealed(tmp_path)
        try:
            cell = self._hdr_cell(q, 2)
            seq = (int(cell[schema.BATCHQ_SEQ_LO_WORD])
                   | (int(cell[schema.BATCHQ_SEQ_HI_WORD]) << 32)) + 5
            cell[schema.BATCHQ_SEQ_LO_WORD] = seq & 0xFFFFFFFF
            cell[schema.BATCHQ_SEQ_HI_WORD] = (seq >> 32) & 0xFFFFFFFF
            ing.request_stop()
            batches = _drain(ing)
        finally:
            ing.close()
        stats = ing.ingest_stats()
        # forward jump + the following slot's backward step: >= 1 gap,
        # 5 phantom "missing" batches — corruption visible, nothing
        # silently reordered away
        assert stats["workers"]["0"]["seq_gaps"] >= 1
        assert stats["workers"]["0"]["seq_missing"] >= 5
        assert sum(sb.n_records for sb in batches) == len(rec)

    def test_staging_path_skips_bad_slot_identically(self, tmp_path):
        """poll_batches_into (the engine's zero-copy staging dequeue)
        applies the same validation: the refused slot's bytes never
        reach a returned row and the dst row is re-staged by the next
        good batch."""
        ing, q, rec = self._fleet_with_sealed(tmp_path)
        try:
            cell = self._hdr_cell(q, 0)
            n_bad = int(cell[schema.BATCHQ_N_RECORDS_WORD])
            cell[schema.BATCHQ_WIRE_ID_WORD] = 0xBEEF
            ing.request_stop()
            dst = np.zeros((4, 257, schema.RECORD_WORDS), np.uint32)
            total = 0
            deadline = time.monotonic() + 30
            while not ing.exhausted():
                for sb in ing.poll_batches_into(dst, 4):
                    assert int(sb.raw[256, 0]) == sb.n_records
                    total += sb.n_records
                assert time.monotonic() < deadline
                time.sleep(0.002)
            total += sum(sb.n_records
                         for sb in ing.poll_batches_into(dst, 4))
        finally:
            ing.close()
        stats = ing.ingest_stats()
        assert stats["bad_wire_slots"] == 1
        assert total + n_bad == len(rec)

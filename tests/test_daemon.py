"""Daemon integration: C++ fsxd <-> shm rings <-> Python engine.

The no-root, no-NIC end-to-end slice (SURVEY.md §4 "Integration"): the
daemon's --sim backend stands in for the XDP plane, but everything else
— the shm transport, the engine loop, the fused TPU step, the verdict
ring — is the production path.  Verdicts written by the engine must
come back as blacklist suppression inside the daemon.
"""

import json
import subprocess
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import key128
from flowsentryx_tpu.core import schema

REPO = Path(__file__).resolve().parents[1]
FSXD = REPO / "daemon" / "build" / "fsxd"


@pytest.fixture(scope="module")
def fsxd_bin():
    r = subprocess.run(
        ["make", "-C", str(REPO / "daemon")], capture_output=True, text=True
    )
    assert r.returncode == 0, f"daemon build failed:\n{r.stdout}\n{r.stderr}"
    assert FSXD.exists()
    return FSXD


def _rings(tmp_path):
    return str(tmp_path / "feature_ring"), str(tmp_path / "verdict_ring")


def _drain(src, n, chunk=4096, timeout_s=15):
    """At least `n` records off a feature ring, as one array."""
    got, have = [], 0
    deadline = time.monotonic() + timeout_s
    while have < n:
        assert time.monotonic() < deadline, "drain timed out"
        c = src.poll(chunk)
        if len(c):
            got.append(c.copy())
            have += len(c)
        else:
            time.sleep(0.001)
    return np.concatenate(got)


class TestShmTransport:
    def test_ring_roundtrip_records(self, fsxd_bin, tmp_path):
        """Daemon produces exactly --packets records; Python drains them."""
        fring, vring = _rings(tmp_path)
        proc = subprocess.Popen(
            [str(fsxd_bin), "--sim", "--packets", "5000", "--rate", "1e8",
             "--feature-ring", fring, "--verdict-ring", vring, "--seed", "3"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            from flowsentryx_tpu.engine.shm import ShmRingSource

            rec = _drain(ShmRingSource(fring), 5000, chunk=1024)
            assert len(rec) == 5000
            assert rec.dtype == schema.FLOW_RECORD_DTYPE
            assert (rec["saddr"] > 0).all()
            # monotonic sim clock
            ts = rec["ts_ns"].astype(np.int64)
            assert (np.diff(ts) > 0).all()
        finally:
            out, _ = proc.communicate(timeout=15)
        stats = json.loads(out)
        assert stats["produced"] == 5000
        assert stats["dropped_ring_full"] == 0

    @pytest.mark.parametrize("args,digest", [
        (("--seed", "3"),
         "c984663ef1674779a50aa08b5fd1e8afb7ec7ac8d130fca1f0543b4f36e5a586"),
        (("--seed", "2147483999", "--attack-ips", "4096", "--benign-ips",
          "70000", "--attack-fraction", "0.5"),
         "5c79f88ea188c13e93c8adcde0e1e7982bdffc496b8ac638670ce2bd9ea8a85d"),
        (("--seed", "3", "--spoof-fraction", "0"),
         "c984663ef1674779a50aa08b5fd1e8afb7ec7ac8d130fca1f0543b4f36e5a586"),
        (("--seed", "2147483999", "--attack-ips", "4096", "--benign-ips",
          "70000", "--attack-fraction", "0.5", "--spoof-fraction", "0.0"),
         "5c79f88ea188c13e93c8adcde0e1e7982bdffc496b8ac638670ce2bd9ea8a85d"),
        (("--seed", "3", "--key-bits", "32"),
         "c984663ef1674779a50aa08b5fd1e8afb7ec7ac8d130fca1f0543b4f36e5a586"),
    ], ids=["defaults", "wide_pools", "defaults_no_spoofing",
            "wide_pools_no_spoofing", "defaults_32_bit_keys"])
    def test_a_seed_gives_the_records_it_always_gave(self, fsxd_bin,
                                                      tmp_path, args, digest):
        """The sim generator's stream is pinned: its random engine is
        written out in `fsxd.cpp` (`Mt64`, std::mt19937_64 draw for
        draw) and the digests are those of the records the daemon made
        with the library's, before PR 38.  `--spoof-fraction 0` (PR 39)
        is the default and makes no draw of its own, as is `--key-bits
        32`."""
        import hashlib

        from flowsentryx_tpu.engine.shm import ShmRingSource

        fring, vring = _rings(tmp_path)
        proc = subprocess.Popen(
            [str(fsxd_bin), "--sim", "--packets", "20000", "--rate", "1e8",
             "--feature-ring", fring, "--verdict-ring", vring, *args],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            rec = _drain(ShmRingSource(fring), 20000)
        finally:
            proc.communicate(timeout=15)
        assert hashlib.sha256(rec.tobytes()).hexdigest() == digest

    @pytest.mark.parametrize("seed", ["3", "2147483999"])
    def test_spoofed_sources_never_repeat(self, fsxd_bin, tmp_path, seed):
        """`--spoof-fraction 0.75` (ISSUE 39): three quarters of the
        attack records, 0.6 of all, take a source of their own —
        the top bit over a 31-bit permutation of their count — so in
        2^22 records none repeats, none is a pooled source (below
        2^25), key 0 or the engine's invalid key, and the daemon's last
        line counts them."""
        from flowsentryx_tpu.engine.shm import ShmRingSource

        fring, vring = _rings(tmp_path)
        want = 1 << 22
        proc = subprocess.Popen(
            [str(fsxd_bin), "--sim", "--packets", str(1 << 26),
             "--rate", "1e8", "--ring-capacity", str(1 << 20),
             "--spoof-fraction", "0.75", "--attack-ips", "131072",
             "--benign-ips", "131072", "--feature-ring", fring,
             "--verdict-ring", vring, "--seed", seed],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        try:
            src, got, have = ShmRingSource(fring), [], 0
            deadline = time.monotonic() + 60
            while have < want:
                assert time.monotonic() < deadline, "drain timed out"
                c = src.poll(1 << 16)
                got.append(c["saddr"].copy())
                have += len(c)
            proc.terminate()
            out, _ = proc.communicate(timeout=15)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
        saddr = np.concatenate(got)
        spoofed = saddr[saddr >= 1 << 31]
        assert len(np.unique(spoofed)) == len(spoofed)
        assert (spoofed != 0xFFFFFFFF).all()
        pooled = saddr[saddr < 1 << 31]
        assert (pooled > 0).all() and (pooled < 1 << 25).all()
        assert abs(len(spoofed) / len(saddr) - 0.6) < 0.01
        stats = json.loads(out.strip().splitlines()[-1])
        # it made at least the spoofed records that reached the ring
        assert len(spoofed) <= stats["spoofed"] <= stats["produced"]

    def _records6(self, fsxd_bin, tmp_path, seed, n=40000):
        """``n`` 64 B records of ``fsxd --sim --key-bits 128 --v6-fraction
        0.5 --fold-collide 8`` over two shards, and its last line."""
        from flowsentryx_tpu.engine.shm import ShmRingSource

        tmp_path.mkdir()
        fring, vring = _rings(tmp_path)
        proc = subprocess.Popen(
            [str(fsxd_bin), "--sim", "--packets", str(n), "--rate", "1e8",
             "--shards", "2", "--attack-ips", "64", "--benign-ips", "256",
             "--attack-fraction", "0.5", "--key-bits", "128",
             "--v6-fraction", "0.5", "--fold-collide", "8",
             "--ring-capacity", str(1 << 16), "--feature-ring", fring,
             "--verdict-ring", vring, "--seed", seed],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        try:
            srcs = [ShmRingSource(schema.shard_ring_path(fring, k, 2))
                    for k in range(2)]
            got, have = [[], []], 0
            deadline = time.monotonic() + 15
            while have < n:
                assert time.monotonic() < deadline, "drain timed out"
                for k, src in enumerate(srcs):
                    c = src.poll(4096)
                    got[k].append(c.copy())
                    have += len(c)
        finally:
            out, _ = proc.communicate(timeout=15)
        shards = [np.concatenate(g) for g in got]
        return shards, json.loads(out.strip().splitlines()[-1])

    @pytest.mark.parametrize("seed", ["3", "2147483999"])
    def test_128_bit_sources_mix_and_plant(self, fsxd_bin, tmp_path, seed):
        """`--key-bits 128`: 64 B records keyed by the full source, half
        of each pool IPv6 inside 2000::/3 and flagged so, the rest
        ::ffff:a.b.c.d of the pool's IPv4 address; eight IPv6
        attackers whose XOR fold is a distinct benign IPv4 source of the
        stream; a shard a source by `schema.shard_of` of all 128 bits;
        and one seed, one stream."""
        shards, stats = self._records6(fsxd_bin, tmp_path / "a", seed)
        for k, rec in enumerate(shards):
            assert rec.dtype == schema.FLOW_RECORD6_DTYPE
            assert (schema.shard_of(rec["saddr6"], 2) == k).all()
        rec = np.concatenate(shards)
        key, first = np.unique(rec["saddr6"], axis=0, return_index=True)
        v4 = key128.is_v4_mapped(key)
        assert len(key) == 64 + 256 and (~v4).sum() == 32 + 128
        assert stats["v6_sources"] == 160 and stats["planted"] == 8
        first_byte = key[~v4, 0] & 0xFF
        assert ((first_byte >= 0x20) & (first_byte < 0x40)).all()
        assert ((rec["flags"][first] & schema.FLAG_IPV6 > 0) == ~v4).all()
        attack = np.concatenate([
            key[v4, 3] < (1 << 24), np.zeros((~v4).sum(), bool)])
        assert attack.sum() == 32  # the v4 half of the 64 attackers
        folds = key128.fold(key[~v4])
        benign_v4 = key[v4 & (key[:, 3] >= (1 << 24)), 3]
        planted = np.isin(folds, benign_v4)
        assert planted.sum() == 8
        assert len(np.unique(folds[planted])) == 8
        again, _ = self._records6(fsxd_bin, tmp_path / "b", seed)
        for x, y in zip(shards, again):
            assert x.tobytes() == y.tobytes()

    def test_128_bit_verdicts_suppress_the_full_source(self, fsxd_bin,
                                                      tmp_path):
        """A 32 B verdict for a planted IPv6 attacker stops its records
        and not those of the benign IPv4 source its fold names."""
        from flowsentryx_tpu.engine.shm import (
            ShmRingSource, ShmVerdictSink,
        )
        from flowsentryx_tpu.engine.writeback import BlacklistUpdate

        fring, vring = _rings(tmp_path)
        proc = subprocess.Popen(
            [str(fsxd_bin), "--sim", "--duration", "30", "--rate", "2e5",
             "--attack-ips", "2", "--benign-ips", "4",
             "--attack-fraction", "0.5", "--key-bits", "128",
             "--v6-fraction", "0.5", "--fold-collide", "1",
             "--feature-ring", fring, "--verdict-ring", vring,
             "--seed", "5"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        try:
            src = ShmRingSource(fring)
            seen = []
            deadline = time.monotonic() + 10
            while sum(len(g) for g in seen) < 4000:
                assert time.monotonic() < deadline
                seen.append(src.poll(1024).copy())
                time.sleep(0.002)
            key = np.unique(np.concatenate(seen)["saddr6"], axis=0)
            v4 = key128.is_v4_mapped(key)
            folds = key128.fold(key)
            victim = key[v4 & np.isin(key[:, 3], folds[~v4])]
            attacker = key[~v4 & np.isin(folds, victim[:, 3])]
            assert len(victim) == len(attacker) == 1
            sink = ShmVerdictSink(vring)
            assert sink.ring.dtype == schema.VERDICT_RECORD6_DTYPE
            sink.apply(BlacklistUpdate(key=attacker,
                                       until_s=np.full(1, 4e9, np.float32)))
            clean = 0
            deadline = time.monotonic() + 15
            while clean < 3:
                assert time.monotonic() < deadline, \
                    "the attacker's records kept arriving"
                time.sleep(0.05)
                tail = src.poll(1 << 16)["saddr6"]
                if len(tail):
                    hit = (tail == attacker).all(axis=1).any()
                    clean = 0 if hit else clean + 1
                    victim_flows = (tail == victim).all(axis=1).any()
            assert victim_flows
            proc.terminate()
        finally:
            out, _ = proc.communicate(timeout=15)
        stats = json.loads(out.strip().splitlines()[-1])
        assert stats["verdicts"] == 1 and stats["blacklisted"] == 1

    @pytest.mark.parametrize("args,why", [
        (("--key-bits", "64"), "--key-bits must be 32 or 128"),
        (("--v6-fraction", "0.5"), "need --key-bits 128"),
        (("--key-bits", "128", "--fold-collide", "40", "--attack-ips",
          "64", "--v6-fraction", "0.5"), "--fold-collide 40"),
    ], ids=["width", "v6_at_32_bits", "more_plants_than_v6_attackers"])
    def test_128_bit_options_are_checked(self, fsxd_bin, tmp_path, args,
                                         why):
        fring, vring = _rings(tmp_path)
        r = subprocess.run([str(fsxd_bin), "--sim", "--packets", "10",
                            "--feature-ring", fring, "--verdict-ring",
                            vring, *args],
                           capture_output=True, text=True, timeout=10)
        assert r.returncode == 1 and why in r.stderr

    def test_an_unknown_option_ends_the_daemon_at_once(self, fsxd_bin):
        """What the churn cell's driver leans on where the daemon is
        older than `--spoof-fraction`: usage and exit 2, no rings."""
        r = subprocess.run([str(fsxd_bin), "--sim", "--no-such-option",
                            "1"], capture_output=True, text=True, timeout=10)
        assert r.returncode == 2
        assert "--spoof-fraction F" in r.stderr

    @pytest.mark.parametrize("ring_args,slots,writer", [
        ((), 1 << 20, "raw"),        # the default: kVerdictRingSlots
        (("--verdict-ring-capacity", "2"), 2, "sink"),  # < the 4 verdicts
    ], ids=["default_ring", "ring_smaller_than_the_update"])
    def test_verdict_ring_blacklists_in_daemon(self, fsxd_bin, tmp_path,
                                               ring_args, slots, writer):
        """Verdicts written by Python suppress future daemon records:
        pushed raw into the default ring, and through the engine's sink
        (which waits for room) into a ring they do not fit at once."""
        fring, vring = _rings(tmp_path)
        proc = subprocess.Popen(
            [str(fsxd_bin), "--sim", "--duration", "30", "--rate", "2e5",
             "--attack-ips", "4", "--attack-fraction", "0.9", *ring_args,
             "--feature-ring", fring, "--verdict-ring", vring, "--seed", "5"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            from flowsentryx_tpu.engine.shm import (
                ShmRing, ShmRingSource, ShmVerdictSink,
            )
            from flowsentryx_tpu.engine.writeback import BlacklistUpdate

            src = ShmRingSource(fring)
            vsink_ring = ShmRing.wait_for(vring, schema.VERDICT_RECORD_DTYPE)
            assert vsink_ring.capacity == slots

            # identify attack sources from the first records, then "block"
            # them far into the sim future
            first = []
            deadline = time.monotonic() + 10
            while sum(len(g) for g in first) < 2000:
                assert time.monotonic() < deadline
                c = src.poll(1024)
                if len(c):
                    first.append(c.copy())
                else:
                    time.sleep(0.002)
            rec = np.concatenate(first)
            attackers = np.unique(rec["saddr"][rec["saddr"] < (1 << 24)])
            assert len(attackers) == 4

            if writer == "raw":
                v = np.zeros(len(attackers), schema.VERDICT_RECORD_DTYPE)
                v["saddr"] = attackers
                v["until_ns"] = np.uint64(1 << 62)  # far future
                assert vsink_ring.produce(v) == len(v)
            else:
                sink = ShmVerdictSink(vring)
                sink.apply(BlacklistUpdate(
                    key=attackers.astype(np.uint32),
                    until_s=np.full(len(attackers), 4e9, np.float32)))
                assert sink.dropped == 0 and sink.waits == 1

            # after the daemon ingests the verdicts, attack records stop
            # while benign traffic keeps flowing: three polls in a row
            # that hold records and no attacker's (what was already in
            # the ring comes first; no fixed sleep stands for "by now")
            clean = 0
            deadline = time.monotonic() + 15
            while clean < 3:
                assert time.monotonic() < deadline, \
                    "attack records kept arriving after the verdicts"
                assert proc.poll() is None, "daemon ended before the check"
                time.sleep(0.05)
                tail = src.poll(1 << 16)
                if len(tail):
                    hit = np.isin(tail["saddr"], attackers).any()
                    clean = 0 if hit else clean + 1
            proc.terminate()
        finally:
            out, _ = proc.communicate(timeout=15)
        stats = json.loads(out)
        assert stats["verdicts"] == 4
        assert stats["blacklisted"] == 4
        assert stats["suppressed"] > 0

    def test_a_block_ends_when_the_sim_clock_passes_it(self, fsxd_bin,
                                                      tmp_path):
        """A verdict suppresses its source until `until_ns` of the
        generator's own clock and no longer; the lookup that finds it
        expired takes it off the count."""
        from flowsentryx_tpu.engine.shm import ShmRing, ShmRingSource

        fring, vring = _rings(tmp_path)
        proc = subprocess.Popen(
            [str(fsxd_bin), "--sim", "--duration", "30", "--rate", "2e5",
             "--attack-ips", "4", "--attack-fraction", "0.9",
             "--feature-ring", fring, "--verdict-ring", vring, "--seed", "5"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            src = ShmRingSource(fring)
            ring = ShmRing.wait_for(vring, schema.VERDICT_RECORD_DTYPE)
            rec = _drain(src, 2000)
            attackers = np.unique(rec["saddr"][rec["saddr"] < (1 << 24)])
            # two seconds of the generator's clock past what it has
            # made so far: it is free-running, so that is soon
            until = int(rec["ts_ns"].max()) + 2_000_000_000
            v = np.zeros(len(attackers), schema.VERDICT_RECORD_DTYPE)
            v["saddr"] = attackers
            v["until_ns"] = np.uint64(until)
            assert ring.produce(v) == len(v)
            back = np.zeros(0, rec.dtype)
            deadline = time.monotonic() + 15
            while len(np.unique(back["saddr"])) < len(attackers):
                assert time.monotonic() < deadline, "no attacker came back"
                late = _drain(src, 1, chunk=1 << 16)
                late = late[late["ts_ns"] >= until]
                back = np.concatenate(
                    [back, late[np.isin(late["saddr"], attackers)]])
            proc.terminate()
        finally:
            out, _ = proc.communicate(timeout=15)
        stats = json.loads(out)
        assert stats["verdicts"] == 4 and stats["suppressed"] > 0
        assert stats["blacklisted"] == 0

    def test_a_burst_larger_than_the_old_ring_arrives_whole(self, fsxd_bin,
                                                            tmp_path):
        """(c) of ISSUE 32: more verdicts in one update than the 16,384
        slots the ring used to have, written by the engine's sink while
        the daemon serves: every one is counted by the daemon, none is
        dropped, and the daemon's exit takes the ring to its last
        verdict."""
        from flowsentryx_tpu.engine.shm import ShmVerdictSink
        from flowsentryx_tpu.engine.writeback import BlacklistUpdate

        fring, vring = _rings(tmp_path)
        proc = subprocess.Popen(
            [str(fsxd_bin), "--sim", "--pace", "--rate", "1000",
             "--duration", "60",
             "--feature-ring", fring, "--verdict-ring", vring],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        n = 40_000
        try:
            sink = ShmVerdictSink(vring)
            assert sink.ring.capacity == 1 << 20
            sink.apply(BlacklistUpdate(
                key=np.arange(1, n + 1, dtype=np.uint32),
                until_s=np.full(n, 4e9, np.float32)))
            assert sink.dropped == 0
            proc.terminate()
            out, _ = proc.communicate(timeout=15)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
        stats = json.loads(out)
        assert stats["verdicts"] == n
        assert stats["blacklisted"] == n
        assert sink.ring.readable() == 0


class TestEndToEnd:
    def test_engine_over_daemon_blocks_attackers(self, fsxd_bin, tmp_path):
        """Full loop: daemon sim flood → shm → Engine (fused TPU step)
        → ShmVerdictSink → daemon blacklist (BASELINE config 4 shape)."""
        from flowsentryx_tpu.core.config import (
            BatchConfig, FsxConfig, LimiterConfig, TableConfig,
        )
        from flowsentryx_tpu.engine import Engine
        from flowsentryx_tpu.engine.shm import ShmRingSource, ShmVerdictSink

        fring, vring = _rings(tmp_path)
        # duration-based: traffic must keep flowing after the engine's
        # verdicts land so the daemon-side suppression is observable
        proc = subprocess.Popen(
            [str(fsxd_bin), "--sim", "--duration", "8", "--rate", "2e5",
             "--attack-ips", "16", "--attack-fraction", "0.8",
             "--feature-ring", fring, "--verdict-ring", vring, "--seed", "7"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            cfg = FsxConfig(
                table=TableConfig(capacity=1 << 12),
                batch=BatchConfig(max_batch=512, deadline_us=2000),
                limiter=LimiterConfig(pps_threshold=300.0, bps_threshold=1e12,
                                      block_s=1e6),
            )
            src = ShmRingSource(fring)
            sink = ShmVerdictSink(vring)
            eng = Engine(cfg, src, sink, readback_depth=2)
            rep = eng.run(max_seconds=10)
        finally:
            out, _ = proc.communicate(timeout=20)
        stats = json.loads(out)
        # the engine condemned rate-violating attack sources and the
        # daemon honored them (suppression = kernel-map writeback analog)
        assert rep.stats["dropped"] > 0
        assert stats["verdicts"] > 0
        assert stats["blacklisted"] > 0
        assert stats["suppressed"] > 0
        assert sink.dropped == 0
        # engine saw fewer records than the daemon generated (the rest
        # were suppressed in the "kernel")
        assert rep.records < stats["produced"]
        assert rep.records > 0

    def test_paced_replay_produces_at_rate(self, fsxd_bin, tmp_path):
        """--replay FILE --pace: a recorded stream (fsx pcap output)
        replays at --rate in real time instead of at fread speed — the
        'replay an attack capture against the live pipeline' mode."""
        from flowsentryx_tpu.engine.shm import ShmRingSource
        from flowsentryx_tpu.engine.traffic import TrafficGen, TrafficSpec

        rec = TrafficGen(TrafficSpec(seed=2)).next_records(100_000)
        rfile = tmp_path / "records.bin"
        rfile.write_bytes(rec.tobytes())
        fring, vring = _rings(tmp_path)
        rate = 2e4
        proc = subprocess.Popen(
            [str(fsxd_bin), "--replay", str(rfile), "--pace",
             "--rate", str(rate), "--duration", "3",
             "--feature-ring", fring, "--verdict-ring", vring],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            src = ShmRingSource(fring)
            got = []
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and proc.poll() is None:
                chunk = src.poll(4096)
                if len(chunk):
                    got.append(chunk.copy())
                time.sleep(0.002)
            tail = src.poll(100_000)
            if len(tail):
                got.append(tail.copy())
        finally:
            out, _ = proc.communicate(timeout=20)
        stats = json.loads(out.strip().splitlines()[-1])
        drained = np.concatenate(got) if got else rec[:0]
        n = len(drained)
        # ~rate*duration produced, NOT the whole 100k file at once
        # (generous band: shared-CI scheduling skews the pacing clock)
        assert 0.5 * rate * 3 <= stats["produced"] <= 1.5 * rate * 3, stats
        assert n == stats["produced"]  # all forwarded records drained
        # content pins the REPLAY path: drained records are the file's
        # leading records verbatim (sim mode would emit different data)
        np.testing.assert_array_equal(drained, rec[:n])

    def test_paced_throughput_keeps_up(self, fsxd_bin, tmp_path):
        """VERDICT r4 weakness: the shm→batcher→engine path had never
        been driven at rate.  The daemon's --pace mode offers benign
        records at a real-time rate; the engine must consume ≈ all of
        them (no ring loss) without blocking any benign source.  The
        full-rate sweep is scripts/shm_stress.py (it writes
        artifacts/SHMSTRESS_inline.json, which is not kept in the repo);
        this pins the machinery at a CI-friendly load."""
        from flowsentryx_tpu.core.config import (
            BatchConfig, FsxConfig, ModelConfig, TableConfig,
        )
        from flowsentryx_tpu.engine import Engine
        from flowsentryx_tpu.engine.shm import ShmRingSource, ShmVerdictSink

        from flowsentryx_tpu.engine.sources import ArraySource
        from flowsentryx_tpu.engine.writeback import NullSink

        fring, vring = _rings(tmp_path)
        rate = 1e5
        cfg = FsxConfig(
            table=TableConfig(capacity=1 << 14),
            batch=BatchConfig(max_batch=512, deadline_us=10_000),
            model=ModelConfig(vote_k=4, vote_m=2),
        )
        # Build + warm (XLA compile) BEFORE the daemon's fixed real-time
        # window opens: compile takes seconds on a small host and would
        # otherwise consume the paced stream the assertion needs.
        eng = Engine(
            cfg, ArraySource(np.zeros(0, schema.FLOW_RECORD_DTYPE)),
            NullSink(), readback_depth=8,
        )
        eng.warm()
        proc = subprocess.Popen(
            [str(fsxd_bin), "--sim", "--pace", "--duration", "8",
             "--rate", str(rate), "--attack-fraction", "0",
             # per-source ~250 pps: benign-plausible timestamps
             "--benign-ips", str(int(rate / 250)),
             "--feature-ring", fring, "--verdict-ring", vring,
             "--seed", "5"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            src = ShmRingSource(fring)
            sink = ShmVerdictSink(vring)
            eng.reset_stream(src, sink)
            rep = eng.run(max_seconds=6)
        finally:
            proc.communicate(timeout=20)
        # ≥80 % of offered consumed (slack for shared-CI scheduling; a
        # pipeline stall shows up as ~0.5× or worse, not 0.9×)
        assert rep.records_per_s >= 0.8 * rate, rep.records_per_s
        assert rep.blocked_sources == 0
        assert rep.stats["dropped_ml"] == 0


def test_the_64_byte_record_matches_the_schema(tmp_path):
    """`struct fsx_flow_record6` and `struct fsx_verdict_record6` as
    gcc lays out the generated kern/fsx_schema.h, against the numpy
    dtypes they are generated from."""
    src = tmp_path / "offs6.c"
    fields = [("fsx_flow_record6", f)
              for f in schema.FLOW_RECORD6_DTYPE.names] + [
        ("fsx_verdict_record6", f)
        for f in schema.VERDICT_RECORD6_DTYPE.names]
    src.write_text(
        "#include <stdio.h>\n#include <stddef.h>\n"
        "#define FSX_HOST_BUILD 1\n#include \"fsx_schema.h\"\n"
        "int main(void){\n"
        'printf("%zu %zu\\n", sizeof(struct fsx_flow_record6),'
        " sizeof(struct fsx_verdict_record6));\n"
        + "".join(f'printf("%zu\\n", offsetof(struct {s}, {f}));\n'
                  for s, f in fields)
        + "return 0;}\n")
    exe = tmp_path / "offs6"
    subprocess.run(["gcc", "-I", str(REPO / "kern"), str(src), "-o",
                    str(exe)], check=True)
    out = [int(x) for x in subprocess.run(
        [str(exe)], capture_output=True, text=True, check=True).stdout.split()]
    assert out[:2] == [64, 32]
    want = [schema.FLOW_RECORD6_DTYPE.fields[f][1] for f in
            schema.FLOW_RECORD6_DTYPE.names] + [
        schema.VERDICT_RECORD6_DTYPE.fields[f][1]
        for f in schema.VERDICT_RECORD6_DTYPE.names]
    assert out[2:] == want

"""fsxd --bpf: the real kernel seam, end-to-end across processes.

The daemon loads the FSXPROG image of the hand-assembled fast path
through the in-kernel verifier, pins the program and maps under bpffs,
drains the kernel feature ringbuf into the shm ring, and applies
engine verdicts from the verdict shm ring to the kernel blacklist map.
This test plays the other two roles: the NIC (BPF_PROG_TEST_RUN with
crafted packets against the pinned program) and the TPU engine (shm
consumer + verdict producer).

Covers the round-1 review's items 2 (the daemon's kernel-facing half)
and 3 (a verifier-accepted program; docs/VERIFIER.md) with live evidence rather than
compile-gated stubs.  The reference's corresponding path was
`bpftool prog load` typed by hand (/root/reference/TODO.md:282-289).
"""

from __future__ import annotations


import os
import pathlib
import socket
import struct
import subprocess
import time

import numpy as np
import pytest

from flowsentryx_tpu.bpf import loader

pytestmark = pytest.mark.skipif(
    not loader.bpf_available(), reason="bpf(2) not permitted in this container"
)

from flowsentryx_tpu.core import schema  # noqa: E402
from flowsentryx_tpu.engine.shm import ShmRing  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent
FSXD = REPO / "daemon" / "build" / "fsxd"
PIN_DIR = "/sys/fs/bpf/fsx_pytest"


def _bpffs_ready() -> bool:
    if os.path.isdir("/sys/fs/bpf") and os.access("/sys/fs/bpf", os.W_OK):
        # a mounted bpffs accepts pins; probe cheaply
        m = loader.map_create(loader.MAP_TYPE_ARRAY, 4, 8, 1, "probe")
        try:
            m.pin("/sys/fs/bpf/fsx_probe")
            os.unlink("/sys/fs/bpf/fsx_probe")
            return True
        except (loader.BpfError, OSError):
            subprocess.run(["mount", "-t", "bpf", "bpf", "/sys/fs/bpf"],
                           capture_output=True)
            try:
                m.pin("/sys/fs/bpf/fsx_probe")
                os.unlink("/sys/fs/bpf/fsx_probe")
                return True
            except (loader.BpfError, OSError):
                return False
        finally:
            m.close()
    return False


obj_get = loader.obj_get


def ip4(saddr: int, plen: int = 100) -> bytes:
    eth = b"\x02" * 6 + b"\x04" * 6 + b"\x08\x00"
    hdr = bytes([0x45, 0]) + struct.pack(">H", plen - 14) + b"\x00" * 4
    hdr += bytes([64, 17]) + b"\x00\x00" + struct.pack("<I", saddr)
    hdr += b"\x01\x02\x03\x04"
    udp = struct.pack(">HHHH", 1234, 53, plen - 34, 0)
    p = eth + hdr + udp
    return p + b"X" * (plen - len(p))


@pytest.fixture(scope="module")
def fsxd_bin():
    r = subprocess.run(["make", "-C", str(REPO / "daemon")],
                       capture_output=True, text=True)
    assert r.returncode == 0, f"daemon build failed:\n{r.stdout}\n{r.stderr}"
    return FSXD


@pytest.fixture(scope="module")
def prog_image(tmp_path_factory):
    out = tmp_path_factory.mktemp("img") / "fsx_prog.img"
    r = subprocess.run(
        ["python", "-m", "flowsentryx_tpu.bpf.image", str(out),
         "--track-ips=1024", "--ring-bytes=16384"],
        capture_output=True, text=True, cwd=str(REPO))
    assert r.returncode == 0, r.stderr
    return out


def test_daemon_bpf_end_to_end(fsxd_bin, prog_image, tmp_path):
    if not _bpffs_ready():
        pytest.skip("bpffs not mountable in this container")
    subprocess.run(["rm", "-rf", PIN_DIR], check=False)

    fring_path = tmp_path / "fring"
    vring_path = tmp_path / "vring"
    proc = subprocess.Popen(
        [str(fsxd_bin), "--bpf", "none", "--prog-image", str(prog_image),
         "--pin", PIN_DIR, "--duration", "12",
         "--feature-ring", str(fring_path), "--verdict-ring", str(vring_path),
         "--pps-threshold", "5", "--window", "10"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.time() + 5
        while not os.path.exists(f"{PIN_DIR}/prog"):
            assert time.time() < deadline, \
                f"daemon never pinned:\n{proc.stderr.read() if proc.poll() else ''}"
            time.sleep(0.1)
        prog_fd = obj_get(f"{PIN_DIR}/prog")

        # NIC role: flood from one source → kernel limiter blocks at 6
        flood = [loader.prog_test_run(prog_fd, ip4(0xC0A80001))[0]
                 for _ in range(10)]
        assert flood == [2] * 5 + [1] * 5  # 5 PASS, then rate+blacklist

        # benign sources
        for i in range(5):
            assert loader.prog_test_run(prog_fd, ip4(0x0A000100 + i))[0] == 2

        # engine role, feature ingress: daemon must forward kernel
        # ringbuf records into the shm ring
        time.sleep(1.5)
        ring = ShmRing(fring_path, schema.FLOW_RECORD_DTYPE)
        arr = ring.consume(100)
        assert len(arr) == 10  # 5 flood-allowed + 5 benign
        assert {0x0A000100 + i for i in range(5)} <= set(arr["saddr"].tolist())

        # engine role, verdict egress: ML-blacklist a benign source
        vring = ShmRing(vring_path, schema.VERDICT_RECORD_DTYPE)
        v = np.zeros(1, dtype=schema.VERDICT_RECORD_DTYPE)
        v["saddr"] = 0x0A000100
        v["until_ns"] = time.clock_gettime_ns(time.CLOCK_MONOTONIC) + int(5e9)
        vring.produce(v)
        deadline = time.time() + 3
        while time.time() < deadline:
            if loader.prog_test_run(prog_fd, ip4(0x0A000100))[0] == 1:
                break
            time.sleep(0.1)
        assert loader.prog_test_run(prog_fd, ip4(0x0A000100))[0] == 1, \
            "verdict never reached the kernel blacklist map"

        # operator surface: fsx top reads the per-flow/per-IP tables
        # (reference README.md:143-146 "print it in a nice format")
        import contextlib
        import io
        import json as js

        from flowsentryx_tpu import cli

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            assert cli.main(["top", "--pin", PIN_DIR, "--json"]) == 0
        top = js.loads(buf.getvalue())
        by_ip = {r["ip"]: r for r in top["flows"]}
        # same key→dotted-quad convention as blacklist.Entry rendering
        flood_ip = socket.inet_ntoa(struct.pack("<I", 0xC0A80001))
        benign_ip = socket.inet_ntoa(struct.pack("<I", 0x0A000100))
        flood_row = by_ip.get(flood_ip)
        assert flood_row is not None, top
        # stats accumulate for ALLOWED packets only: 5 of the 10 flood
        # packets passed before the limiter tripped
        assert flood_row["pkts"] >= 5
        assert flood_row["dport"] == 53        # host-order display
        assert flood_row["blocked_s"] > 0      # kernel-limiter block
        assert benign_ip in by_ip              # benign source tracked
        assert top["n_blocked"] >= 2           # flood + ML verdict
        # human format renders a header + one line per flow
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            assert cli.main(["top", "--pin", PIN_DIR, "-n", "3"]) == 0
        lines = buf.getvalue().strip().splitlines()
        assert lines[0].split()[:2] == ["ip", "dport"]
        assert len(lines) == 5  # header + 3 rows + summary

        # operator surface: fsx config --set updates the LIVE kernel
        # config map (re-read per packet, effective on the next one)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            assert cli.main(["config", "--pin", PIN_DIR,
                             "--set", "pps_threshold=2"]) == 0
        got = js.loads(buf.getvalue())
        assert got["kernel_config"]["pps_threshold"] == 2
        assert got["kernel_config"]["valid"] == 1  # untouched
        fresh = 0x0A000700  # source unseen so far
        res = [loader.prog_test_run(prog_fd, ip4(fresh))[0]
               for _ in range(5)]
        assert res == [2, 2, 1, 1, 1]  # new threshold, next packet
        # non-settable fields refuse
        assert cli.main(["config", "--pin", PIN_DIR,
                         "--set", "hash_salt=1"]) == 1

        # operator surface: fsx monitor appends JSONL history + alerts
        hist = tmp_path / "history.jsonl"
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            assert cli.main(["monitor", "--pin", PIN_DIR,
                             "--interval", "0.2", "--count", "2",
                             "--out", str(hist),
                             "--alert-blacklist", "1"]) == 0
        ticks = [js.loads(ln) for ln in
                 buf.getvalue().strip().splitlines()]
        assert len(ticks) == 2
        assert ticks[0]["kernel"]["stats"]["allowed"] > 0
        assert "per_s" in ticks[1]          # deltas from tick 2 on
        # absolute-gauge alert fires on the FIRST tick (one-shot cron
        # usage) and on later ones
        for tk in ticks:
            assert any("blacklist size" in a
                       for a in tk.get("alerts", []))
        assert len(hist.read_text().strip().splitlines()) == 2

        # delta-based drop-rate alert: pump a blacklisted source while
        # the monitor ticks, so dropped_blacklist climbs between
        # snapshots
        import threading

        stop = threading.Event()

        def pump():
            while not stop.is_set():
                loader.prog_test_run(prog_fd, ip4(0xC0A80001), repeat=50)
                time.sleep(0.01)

        th = threading.Thread(target=pump, daemon=True)
        th.start()
        try:
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                assert cli.main(["monitor", "--pin", PIN_DIR,
                                 "--interval", "0.4", "--count", "3",
                                 "--alert-drop-pps", "10"]) == 0
        finally:
            stop.set()
            th.join(timeout=5)
        ticks = [js.loads(ln) for ln in
                 buf.getvalue().strip().splitlines()]
        assert any("drop rate" in a for tk in ticks[1:]
                   for a in tk.get("alerts", []))
    finally:
        proc.terminate()
        out, err = proc.communicate(timeout=10)
        subprocess.run(["rm", "-rf", PIN_DIR], check=False)
    # exit JSON: the daemon observed the forwarding + verdict
    import json
    stats = json.loads(out.strip().splitlines()[-1])
    assert stats["produced"] >= 10
    assert stats["verdicts"] == 1
    assert stats["dropped_rate"] >= 1


@pytest.fixture(scope="module")
def compact_prog_image(tmp_path_factory):
    out = tmp_path_factory.mktemp("imgc") / "fsx_prog_c.img"
    r = subprocess.run(
        ["python", "-m", "flowsentryx_tpu.bpf.image", str(out),
         "--track-ips=1024", "--ring-bytes=16384", "--compact"],
        capture_output=True, text=True, cwd=str(REPO))
    assert r.returncode == 0, r.stderr
    return out


def test_daemon_bpf_compact_end_to_end(fsxd_bin, compact_prog_image, tmp_path):
    """fsxd --compact with a compact-emit image: 16 B kernel-quantized
    records arrive in the shm ring and the ShmRingSource auto-detects
    the format for the engine's precompact path."""
    if not _bpffs_ready():
        pytest.skip("bpffs not mountable in this container")
    subprocess.run(["rm", "-rf", PIN_DIR], check=False)

    fring_path = tmp_path / "fring_c"
    vring_path = tmp_path / "vring_c"
    proc = subprocess.Popen(
        [str(fsxd_bin), "--bpf", "none", "--compact",
         "--prog-image", str(compact_prog_image),
         "--pin", PIN_DIR, "--duration", "10",
         "--feature-ring", str(fring_path), "--verdict-ring", str(vring_path),
         "--pps-threshold", "1000", "--window", "1"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.time() + 5
        while not os.path.exists(f"{PIN_DIR}/prog"):
            assert time.time() < deadline, \
                f"daemon never pinned:\n{proc.stderr.read() if proc.poll() else ''}"
            time.sleep(0.1)
        prog_fd = obj_get(f"{PIN_DIR}/prog")

        for i in range(8):
            assert loader.prog_test_run(prog_fd, ip4(0x0A000200 + i))[0] == 2

        time.sleep(1.5)
        from flowsentryx_tpu.engine.shm import ShmRingSource

        src = ShmRingSource(fring_path, timeout_s=3)
        assert src.precompact  # auto-detected 16 B records
        arr = src.poll(100)
        assert len(arr) == 8
        assert {0x0A000200 + i for i in range(8)} == set(arr["w0"].tolist())
        # every record carries the UDP flag in word 3
        assert ((arr["w3"] >> 11) & 0x1F == schema.FLAG_UDP).all()

        # operator surface: fsx status --pin reads live kernel counters
        import json as js

        from flowsentryx_tpu import cli

        import io
        import contextlib

        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            assert cli.main(["status", "--feature-ring", str(fring_path),
                             "--verdict-ring", str(vring_path),
                             "--pin", PIN_DIR]) == 0
        status = js.loads(out.getvalue())
        assert status["feature_ring"]["record_size"] == 16
        assert status["kernel"]["stats"]["allowed"] >= 8
        assert status["kernel"]["blacklist_entries"] == 0
    finally:
        proc.send_signal(2)
        try:
            proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            proc.kill()
        subprocess.run(["rm", "-rf", PIN_DIR], check=False)

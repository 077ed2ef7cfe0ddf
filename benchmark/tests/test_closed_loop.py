"""Run by hand (``python3 -m pytest benchmark/tests/test_closed_loop.py -q``,
about two minutes on the CPU; not part of the repo's tier-1 tests): the
closed loop of the saturated cells.

* ``benchmark/governor.py`` against a real free-running ``fsxd --sim``:
  into rings nobody reads the daemon stops near ``high_water`` with
  nothing dropped and ends promptly when told to; with a reader that
  drains, every record forwarded arrives, in order; the same daemon
  with ``--pace`` and no governor sheds into full rings (the open loop);
* the saturated cell rehearsed end to end: ``failed`` 0, ``correct``
  true, ``gen.blocked.tput`` on the traced line and above 0;
* ``BENCHMARK.json`` names only cells, readers and files that exist.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import governor, harness, trafficgen  # noqa: E402

CLOSED_CELL = "c4-syn-mix.saturate"
HIGH, CAPACITY, CHUNK = 8192, 1 << 21, 2048


STARTED: list[subprocess.Popen] = []


@pytest.fixture(autouse=True)
def no_child_outlives_its_test():
    """A case that fails half way leaves a daemon (held, perhaps) and a
    governor behind: end whatever a case started."""
    yield
    for proc in STARTED:
        if proc.poll() is None:
            proc.send_signal(signal.SIGCONT)
            proc.kill()
            proc.wait()
    STARTED.clear()


def start_fsxd(tmp: Path, *extra: str, capacity: int = CAPACITY):
    proc = subprocess.Popen(
        [str(harness.build_fsxd()), "--sim", "--shards", "2",
         "--rate", "1000000", "--packets", str(1 << 40),
         "--attack-fraction", "0.5", "--attack-ips", "64",
         "--benign-ips", "256", "--ring-capacity", str(capacity),
         "--feature-ring", str(tmp / "fring"),
         "--verdict-ring", str(tmp / "vring"), "--seed", "7", *extra],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    STARTED.append(proc)
    paths = [tmp / "fring.0", tmp / "fring.1", tmp / "vring"]
    deadline = time.monotonic() + 10
    while not all(p.exists() and p.stat().st_size > governor.HDR_SIZE
                  for p in paths):
        assert time.monotonic() < deadline, "fsxd made no rings"
        time.sleep(0.001)
    return proc, paths


def start_governor(tmp: Path, proc, paths):
    proc.send_signal(signal.SIGSTOP)
    gov = subprocess.Popen(
        [sys.executable, governor.__file__, "--pid", str(proc.pid),
         "--high-water", str(HIGH), "--low-water", str(HIGH - HIGH // 64),
         "--poll-us", "500", "--status", str(tmp / "status"),
         "--verdict-ring", str(paths[2]), *map(str, paths[:2])],
        stdout=subprocess.PIPE, text=True)
    STARTED.append(gov)
    return gov


def end(gov, proc) -> tuple[dict, dict, float]:
    gov.send_signal(signal.SIGTERM)
    g = json.loads(gov.communicate(timeout=10)[0].strip().splitlines()[-1])
    t0 = time.monotonic()
    proc.send_signal(signal.SIGCONT)
    proc.send_signal(signal.SIGTERM)
    out = proc.communicate(timeout=10)[0]
    return g, json.loads(out.strip().splitlines()[-1]), \
        time.monotonic() - t0


def test_into_rings_nobody_reads_the_daemon_waits_and_sheds_nothing(tmp_path):
    proc, paths = start_fsxd(tmp_path)
    gov = start_governor(tmp_path, proc, paths)
    cursors = [governor.Cursors(str(p)) for p in paths[:2]]
    time.sleep(1.0)
    fill = [c.unread() for c in cursors]
    time.sleep(0.5)
    live = governor.read_status(tmp_path / "status")
    # held: whatever it made before the first hold, and not a record more
    assert fill == [c.unread() for c in cursors]
    assert HIGH <= max(fill) < CAPACITY
    g, last, took = end(gov, proc)
    assert last["dropped_ring_full"] == 0
    assert g["holds"] >= 1 and g["blocked_ns"] > 0.9 * 1.0e9
    assert 0 < live["blocked_ns"] <= g["blocked_ns"] <= g["governed_ns"]
    assert took < 2.0


def test_with_a_reader_every_forwarded_record_arrives_in_order(tmp_path):
    from flowsentryx_tpu.engine.shm import ShmRing

    proc, paths = start_fsxd(tmp_path)
    gov = start_governor(tmp_path, proc, paths)
    rings = [ShmRing(p, trafficgen.FLOW_RECORD) for p in paths[:2]]
    got, fill = [[], []], []
    t_end = time.monotonic() + 1.5
    while time.monotonic() < t_end:
        fill.append(max(r.readable() for r in rings))
        for k, r in enumerate(rings):
            got[k].append(np.array(r.consume(2048)["ts_ns"]))
        time.sleep(0.002)  # a reader slower than the daemon: it is held
    g, last, _ = end(gov, proc)
    for k, r in enumerate(rings):
        while len(rec := r.consume(1 << 16)):
            got[k].append(np.array(rec["ts_ns"]))
    ts = [np.concatenate(x) for x in got]
    assert last["dropped_ring_full"] == 0 and g["holds"] >= 1
    # the fuller ring is kept at the mark: what a 0.5 ms poll lets through
    assert HIGH - 8 * CHUNK < np.median(fill[len(fill) // 2:]) \
        < HIGH + 64 * CHUNK
    assert sum(map(len, ts)) == last["produced"] - last["suppressed"] > HIGH
    for t in ts:
        assert (np.diff(t.astype(np.int64)) > 0).all()


def test_paced_into_full_rings_the_open_loop_sheds(tmp_path):
    proc, paths = start_fsxd(tmp_path, "--pace", capacity=4096)
    time.sleep(0.5)
    proc.send_signal(signal.SIGTERM)
    last = json.loads(proc.communicate(timeout=10)[0].strip().splitlines()[-1])
    assert last["dropped_ring_full"] > 0


@pytest.fixture(scope="module")
def rehearsed():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CLOSED_CELL,
         "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    return lines[-1], next(ln["generator"] for ln in lines
                           if "generator" in ln)


def test_the_saturated_cell_rehearses_closed_loop(rehearsed):
    r, gen = rehearsed
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and gen["dropped_ring_full"] == 0
    assert gen["governor"]["holds"] >= 1
    assert 0 < r["metrics"]["gen.blocked.tput"]["value"] <= 100
    assert "gen.shortfall.tput" not in r["metrics"]
    assert r["metrics"]["dispatch.occupancy.tput"]["value"] > 90


def test_benchmark_json_names_only_what_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bench["workloads"]}
    for name in cells:
        assert (ROOT / "benchmark/workloads" / f"{name}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
        assert (ROOT / "benchmark/metrics" / f"{m['name']}.py").is_file()
    readers = {p.stem for p in (ROOT / "benchmark/metrics").glob("*.py")}
    assert readers == {m["name"] for m in
                       bench["end_to_end"] + bench["per_layer"]}

"""What the benchmark puts round the system under test: the engine as
``fsx serve`` assembles it, held in-process, with a tap on each side.

From the program this takes the engine, its report (counters), its
``on_reap`` hook and nothing else.  The taps are the benchmark's own
eyes: :class:`SourceTap` copies every sealed batch the engine stages
(16 B a record) so the reference can replay exactly what was dispatched,
and :class:`SinkTap` copies every block on its way to the verdict ring.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import subprocess
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE = HERE / ".cache"


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by name — how a later PR's driver or
    metric is found without an edit here."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"benchmark: no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, group by group."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


# -- fsxd --------------------------------------------------------------------

def build_fsxd() -> Path:
    """``fsxd`` built from ``daemon/`` into ``benchmark/.cache/`` under a
    hash of its sources (as ``chip_smoke.py:195-209`` builds it, but the
    result outlasts the run).  A binary found anywhere else is never
    used."""
    src = sorted(p for p in (ROOT / "daemon").iterdir() if p.is_file())
    src.append(ROOT / "kern" / "fsx_schema.h")
    if len(src) < 2:
        raise SystemExit("benchmark: daemon/ sources not found")
    h = hashlib.sha256()
    for p in src:
        h.update(p.name.encode())
        h.update(p.read_bytes())
    build = CACHE / f"fsxd-{h.hexdigest()[:16]}"
    binary = build / "fsxd"
    if not binary.is_file():
        build.mkdir(parents=True, exist_ok=True)
        r = subprocess.run(
            ["make", "-B", "-C", str(ROOT / "daemon"), f"BUILD={build}"],
            capture_output=True, text=True)
        if r.returncode != 0 or not binary.is_file():
            raise SystemExit(f"benchmark: fsxd build failed: "
                             f"{r.stderr[-800:]}")
    return binary


# -- the engine, as _cmd_serve assembles it -----------------------------------

def engine_config(config: dict):
    """The configuration file's groups as the program's ``FsxConfig``."""
    from flowsentryx_tpu.core.config import FsxConfig

    vote = config["vote"]
    return FsxConfig.from_dict({
        "limiter": config["limiter"],
        "model": {"name": config["model"]["name"],
                  "threshold": config["model"]["threshold"],
                  "ml_block_s": vote["ml_block_s"],
                  "vote_k": vote["vote_k"], "vote_m": vote["vote_m"],
                  "vote_decay_s": vote["vote_decay_s"]},
        "table": config["table"],
        "batch": config["batch"],
    })


def check_artifact(config: dict, params) -> None:
    """The artifact the engine serves must be the numbers the
    configuration file states (the reference reads only the file)."""
    m = config["model"]
    for k in load_module("models", m["name"]).FIELDS:
        got = np.asarray(getattr(params, k))
        if not np.allclose(got.astype(np.float64),
                           np.asarray(m[k], np.float64), rtol=1e-6):
            raise SystemExit(
                f"benchmark: artifact {m['artifact']} field {k} = "
                f"{got.tolist()} differs from the configuration's {m[k]}")


def build_engine(config: dict, source, sink, import_s: float, compiles):
    """The calls ``_cmd_serve`` makes for ``--config --artifact [--feature-ring
    --verdict-ring --ingest-workers N | --records] --mega auto``
    (``flowsentryx_tpu/cli.py:1419-1573``): artifact by the model
    family's loader (:1489-1493), ``Engine(cfg, source, sink, params,
    mega_n=...)`` (:1551-1562), the boot stamps (:1563-1564) and
    ``eng.warm()`` (:1583-1592, done by the caller so that it is timed)."""
    from flowsentryx_tpu.engine import Engine
    from flowsentryx_tpu.models.registry import load_artifact

    cfg = engine_config(config)
    params = load_artifact(cfg.model.name,
                           str(ROOT / config["model"]["artifact"]))
    check_artifact(config, params)
    eng = Engine(cfg, source, sink, params=params,
                 mega_n=config.get("mega") or 0)
    eng.boot_import_s = round(import_s, 4)
    eng.boot_jax_compiles = compiles
    return eng


# -- taps ----------------------------------------------------------------------

class SourceTap:
    """A sealed-batch source with a copy of everything it hands over.

    Delegates the whole protocol to the real source; its own part is
    ``poll_batches_into``, where each staged batch's valid rows, base and
    seal stamps are kept for the reference and the latency reading.

    In set-up it can also stand in for the source: :meth:`begin_prefill`
    takes an iterator of ``(words[n, 4], base_rel_us)`` sealed batches
    (the configuration's resident population) and hands those to the
    engine, in the staging protocol's own form, until it runs dry; the
    engine then sees an exhausted source and its ``run()`` returns.
    Prefill batches are counted (``n_records``, ``t_enqueue``) but not
    copied: the driver regenerates them for the reference."""

    provides_sealed = True

    def __init__(self, real, max_batch: int):
        self._real = real
        self._b = max_batch
        self._prefill = None
        self._prefilling = False
        self.prefill_batches = 0
        self.words: list[np.ndarray] = []      # [n, 4] u32 per batch
        self.base_us: list[int] = []
        self.worker: list[int] = []
        self.t_enqueue: list[float] = []       # every batch, prefill too
        self.n_records: list[int] = []
        self.records = 0

    def __getattr__(self, name):
        return getattr(self._real, name)

    def begin_prefill(self, batches) -> None:
        self._prefill, self._prefilling = iter(batches), True

    def end_prefill(self) -> None:
        self._prefill, self._prefilling = None, False

    def exhausted(self) -> bool:
        if self._prefilling:
            return self._prefill is None
        return self._real.exhausted()

    def _stage_prefill(self, dst, max_batches):
        from flowsentryx_tpu.ingest.sharded import SealedBatch

        out = []
        while self._prefill is not None and len(out) < min(max_batches,
                                                           len(dst)):
            nxt = next(self._prefill, None)
            if nxt is None:
                self._prefill = None
                break
            words, base = nxt
            row = dst[len(out)].reshape(self._b + 1, -1)
            n = len(words)
            row[:n] = words
            row[self._b] = 0
            row[self._b, 0] = n
            row[self._b, 1] = base & 0xFFFFFFFF
            row[self._b, 2] = base >> 32
            now = time.perf_counter()
            out.append(SealedBatch(raw=dst[len(out)], n_records=n,
                                   t_enqueue=now, t_seal=now, worker=0,
                                   seq=0))
            self.t_enqueue.append(now)
            self.n_records.append(n)
            self.prefill_batches += 1
        return out

    def poll_batches_into(self, dst, max_batches, pop_timer=None,
                          stage_timer=None):
        if self._prefilling:
            return self._stage_prefill(dst, max_batches)
        out = self._real.poll_batches_into(
            dst, max_batches, pop_timer=pop_timer, stage_timer=stage_timer)
        for sb in out:
            raw = sb.raw.reshape(self._b + 1, -1)
            meta = raw[self._b]
            n = int(meta[0])
            self.words.append(raw[:n].copy())
            self.base_us.append(int(meta[1]) | int(meta[2]) << 32)
            self.worker.append(sb.worker)
            self.t_enqueue.append(sb.t_enqueue)
            self.n_records.append(n)
            self.records += n
        return out


class SinkTap:
    """A verdict sink with a copy of every block on its way through.
    ``real`` may arrive late (the verdict ring exists only once the
    daemon runs): :meth:`attach` sets it."""

    def __init__(self, real=None):
        self.real = real
        self.t0_ns = 0
        self.key: list[np.ndarray] = []
        self.until_s: list[np.ndarray] = []

    def attach(self, real) -> None:
        self.real = real

    def apply(self, update) -> None:
        if len(update.key):
            self.key.append(np.array(update.key, np.uint32))
            self.until_s.append(np.array(update.until_s, np.float32))
        if self.real is not None:
            if hasattr(self.real, "t0_ns"):
                self.real.t0_ns = self.t0_ns
            self.real.apply(update)

    def blocks(self):
        if not self.key:
            return np.empty(0, np.uint32), np.empty(0, np.float32)
        return np.concatenate(self.key), np.concatenate(self.until_s)


class ReapLog:
    """The engine's ``on_reap`` hook: when each in-flight entry's verdicts
    had been sunk.  Entries arrive in dispatch order, so walking the
    source tap's batch list beside them gives every batch its own
    first-record → verdict-sunk time on the host clock."""

    def __init__(self, tap: SourceTap | None):
        self.tap = tap
        self.next_batch = 0
        self.lat_s: list[float] = []
        self.weight: list[int] = []
        self.t_done: list[float] = []
        self.sunk = 0

    def __call__(self, n_records: int, t_done: float) -> None:
        self.sunk += n_records
        if self.tap is None:
            return
        left = n_records
        while left > 0:
            i = self.next_batch
            n = self.tap.n_records[i]
            self.lat_s.append(t_done - self.tap.t_enqueue[i])
            self.weight.append(n)
            self.t_done.append(t_done)
            left -= n
            self.next_batch = i + 1


def weighted_percentile(values, weights, q: float) -> float:
    v, w = np.asarray(values, np.float64), np.asarray(weights, np.float64)
    order = np.argsort(v)
    cum = np.cumsum(w[order])
    return float(v[order][np.searchsorted(cum, q / 100.0 * cum[-1])])


def window_percentile(ctx, q: float) -> float | None:
    """Percentile ``q`` (ms), over every record whose verdict was sunk in
    the window, of first-record-in-its-batch -> verdict sunk.  A batch's
    records share one time, so it is weighted by records."""
    a, b = ctx.snap0["reaped"], ctx.snap1["reaped"]
    if b - a < 1:
        return None
    return 1e3 * weighted_percentile(ctx.reaps.lat_s[a:b],
                                     ctx.reaps.weight[a:b], q)


def occupancy(ctx) -> float | None:
    """How full the window's dispatched batches were (%): records over
    batches x ``max_batch``."""
    d_rec = ctx.snap1["rep"]["records"] - ctx.snap0["rep"]["records"]
    d_bat = ctx.snap1["rep"]["batches"] - ctx.snap0["rep"]["batches"]
    if d_bat <= 0:
        return None
    return 100.0 * d_rec / (d_bat * ctx.config["batch"]["max_batch"])


def device_idle(ctx) -> float | None:
    """1 - the union of device-busy intervals over the traced slice (%)."""
    t = ctx.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def generator_shortfall(ctx) -> float | None:
    """How far the paced generator fell short of its schedule (%): 1 -
    produced / (rate x its lifetime), over its whole life (warm-up and
    the start of the drain included, not the window alone)."""
    g = ctx.gen_final
    if not g.get("produced"):
        return None
    return 100.0 * (1.0 - g["produced"] / (g["rate"] * g["elapsed_s"]))


def traced_records(ctx) -> int:
    """Records the engine dispatched in the traced slice."""
    t = ctx.trace
    return t["snap1"]["rep"]["records"] - t["snap0"]["rep"]["records"]


class Stopwatch:
    def __init__(self):
        self.t0 = time.perf_counter()

    def lap(self) -> float:
        """Seconds since the last lap (or the start)."""
        t = time.perf_counter()
        dt, self.t0 = t - self.t0, t
        return dt

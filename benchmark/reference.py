"""The plain reference: what the configuration says a stream of records
must produce, in straightforward numpy and float32.

It shares no code with ``flowsentryx_tpu/`` and takes nothing the program
made: the classifier's numbers come from the configuration's own file,
the records from what the tap saw handed to the engine.  One flow-state
row per SOURCE in dense arrays (no hash table: the configurations keep
the table at an eighth full, so that a new source finds all of its 8
probes taken about once in 2^24 inserts; that, and two new sources
picking one slot in one batch, are the lower reading of ``blocks_gap``
and ``counters_gap``, PERF.md §2).

Semantics, per sealed batch (the spec is the repo's documented wire and
step contracts — ``core/schema.py`` compact16 comment block,
``ops/fused.py`` module docstring — re-derived here, not imported):

1. compact16 decode: key, 8 u8 features, length in 8-byte units, µs
   delta from the batch base; time in f32 seconds from the stream epoch.
2. classifier: the artifact's quantised pipeline on the wire's own u8
   features (the wire carries the model's input quantisation).
3. aggregate by key; one state transition per (source, batch):
   blacklist gate, limiter, young-flow ML vote with decay, block.
4. per-record verdict counts; newly blocked (key, until) pairs.

The classifier is the model family's own file under ``models/``; its
``precision="int4"`` is the control: the same pipeline with weights and
activations cut to 4 bits, the step below the int8 the configuration
states.  It has to come out as not correct.
"""

from __future__ import annotations

import numpy as np

from benchmark import harness

F32 = np.float32

PASS, DROP_BLACKLIST, DROP_RATE, DROP_ML = 0, 1, 2, 3
COUNTER_NAMES = ("allowed", "dropped_blacklist", "dropped_rate",
                 "dropped_ml")

#: compact16 field widths (the wire's spec).
LEN8_MAX = 0x7FF
DT_US_MAX = 0xFFFF
SPAN_NS = 65_000_000  # a batch seals before a record 65 ms past its base


def load_limiter(kind: str):
    """``benchmark/limiters/<kind>.py`` — one limiter's transition."""
    return harness.load_module("limiters", kind)


# -- compact16 wire ----------------------------------------------------------

def quantise_records(rec: np.ndarray, model: dict, base_ns: int) -> np.ndarray:
    """48 B flow records -> ``[n, 4]`` compact16 words (the inline path's
    seal, which the reference does for itself)."""
    x = rec["feat"].astype(F32)
    if model["log1p"]:
        x = np.log1p(x)
    q = np.clip(np.rint(x / F32(model["in_scale"])) + model["in_zp"], 0, 255)
    q = q.astype(np.uint32)
    out = np.empty((len(rec), 4), np.uint32)
    out[:, 0] = rec["saddr"]
    out[:, 1] = q[:, 0] | q[:, 1] << 8 | q[:, 2] << 16 | q[:, 3] << 24
    out[:, 2] = q[:, 4] | q[:, 5] << 8 | q[:, 6] << 16 | q[:, 7] << 24
    len8 = np.minimum((rec["pkt_len"].astype(np.uint32) + 4) >> 3, LEN8_MAX)
    dt = rec["ts_ns"].astype(np.int64) - np.int64(base_ns)
    dt_us = np.clip(dt // 1000, 0, DT_US_MAX).astype(np.uint32)
    out[:, 3] = len8 | (rec["flags"].astype(np.uint32) & 0x1F) << 11 \
        | dt_us << 16
    return out


def seal_stream(rec: np.ndarray, max_batch: int, t0_ns: int, model: dict):
    """Cut a record stream into sealed batches by the batcher's stated
    rule (full, or the next record 65 ms past the batch's first) and
    yield ``(words[n, 4], base_rel_us)``.  ``rec`` must end on a batch
    boundary of the caller's choosing: the tail is sealed as it is."""
    ts = rec["ts_ns"].astype(np.int64)
    pos, n = 0, len(rec)
    while pos < n:
        base = int(ts[pos])
        take = min(max_batch, n - pos)
        late = np.flatnonzero(ts[pos:pos + take] - base >= SPAN_NS)
        if late.size:
            take = int(late[0])
        chunk = rec[pos:pos + take]
        yield (quantise_records(chunk, model, base),
               max(0, base - t0_ns) // 1000)
        pos += take


# -- classifier --------------------------------------------------------------

def load_model(name: str):
    """``benchmark/models/<name>.py`` — one model family's plain scorer
    (``FIELDS``, ``OPS_PER_RECORD``, ``score(q, model, precision)``)."""
    return harness.load_module("models", name)


# -- the step ----------------------------------------------------------------

class Reference:
    """Flow state for ``n_sources`` dense source ids, stepped a sealed
    batch at a time.  ``ids`` maps a batch's keys to dense ids (built by
    :func:`dense_ids` over every key the run dispatched)."""

    def __init__(self, config: dict, n_sources: int,
                 precision: str = "int8"):
        self.cfg = config
        self.model = config["model"]
        self.lim = config["limiter"]
        self.limiter = load_limiter(self.lim["kind"])
        self.score = load_model(self.model["name"]).score
        self.precision = precision
        z = lambda: np.zeros(n_sources, F32)  # noqa: E731
        self.present = np.zeros(n_sources, bool)
        self.last_seen, self.rec_seen = z(), z()
        self.ml_votes, self.blocked_until = z(), z()
        self.lim_state = self.limiter.new_state(n_sources)
        self.counts = np.zeros(4, np.int64)
        self.records = 0
        self.block_key: list[np.ndarray] = []
        self.block_until: list[np.ndarray] = []

    def step(self, words: np.ndarray, base_rel_us: int,
             ids: np.ndarray) -> None:
        """One sealed batch: ``words`` ``[n, 4]`` u32 valid rows."""
        n = len(words)
        if n == 0:
            return
        m, lim, mdl = self.model, self.lim, self.cfg["vote"]
        # 1. decode (f32, the wire's stated recombination)
        key = words[:, 0].copy()
        key[key == 0] = np.uint32(0xFFFFFFFE)
        w1, w2, w3 = words[:, 1], words[:, 2], words[:, 3]
        q = np.stack([w1 & 255, (w1 >> 8) & 255, (w1 >> 16) & 255, w1 >> 24,
                      w2 & 255, (w2 >> 8) & 255, (w2 >> 16) & 255, w2 >> 24],
                     axis=1)
        pkt_len = ((w3 & LEN8_MAX) << 3).astype(F32)
        base = (F32(base_rel_us >> 32) * F32(4294.967296)
                + F32(base_rel_us & 0xFFFFFFFF) * F32(1e-6))
        ts = base + (w3 >> 16).astype(F32) * F32(1e-6)
        # 2. classify
        mal = self.score(q, m, self.precision) > F32(m["threshold"])
        # 3. aggregate by source
        order = np.argsort(ids, kind="stable")
        sid = ids[order]
        head = np.empty(n, bool)
        head[0] = True
        np.not_equal(sid[1:], sid[:-1], out=head[1:])
        starts = np.flatnonzero(head)
        uid = sid[starts]
        pkts = np.diff(np.append(starts, n)).astype(F32)
        byts = np.add.reduceat(pkt_len[order], starts).astype(F32)
        rep_ts = np.maximum.reduceat(ts[order], starts).astype(F32)
        ml_count = np.add.reduceat(mal[order].astype(F32), starts
                                   ).astype(F32)
        rep_key = key[order][starts]
        # 4. state in; a source's first batch starts from zeros
        new = ~self.present[uid]
        blocked_until = np.where(new, F32(0), self.blocked_until[uid])
        rec_seen = np.where(new, F32(0), self.rec_seen[uid])
        ml_votes = np.where(new, F32(0), self.ml_votes[uid])
        last_seen = np.where(new, F32(0), self.last_seen[uid])
        already = blocked_until > rep_ts
        over = self.limiter.apply(self.lim_state, uid, new, pkts, byts,
                                  rep_ts, lim)
        over_rate = over & ~already
        ml_hit = ml_count > 0
        mature = rec_seen >= F32(mdl["vote_k"])
        if mdl["vote_decay_s"] > 0:
            dt = np.maximum(rep_ts - last_seen, F32(0))
            ml_votes = ml_votes * np.exp2(-dt / F32(mdl["vote_decay_s"]),
                                          dtype=F32)
        votes_new = np.minimum(
            ml_votes + np.where(mature, ml_count, F32(0)), F32(1e6))
        burst = (pkts > mdl["vote_k"]) & (ml_count >= mdl["vote_m"])
        vote_ok = (votes_new >= F32(mdl["vote_m"])) | burst
        over_ml = ml_hit & vote_ok & ~already & ~over_rate
        ml_drop_only = ml_hit & ~vote_ok & ~already & ~over_rate
        new_until = np.where(
            over_rate, rep_ts + F32(lim["block_s"]),
            np.where(over_ml, rep_ts + F32(mdl["ml_block_s"]),
                     blocked_until)).astype(F32)
        # 5. state out
        self.present[uid] = True
        self.last_seen[uid] = rep_ts
        self.rec_seen[uid] = rec_seen + pkts
        self.ml_votes[uid] = np.where(over_ml, F32(0), votes_new)
        self.blocked_until[uid] = new_until
        # 6. per-record verdict counts
        ipk, iml = pkts.astype(np.int64), ml_count.astype(np.int64)
        c = self.counts
        c[DROP_BLACKLIST] += ipk[already].sum()
        c[DROP_RATE] += ipk[over_rate].sum()
        c[DROP_ML] += ipk[over_ml].sum() + iml[ml_drop_only].sum()
        passed = ~(already | over_rate | over_ml | ml_drop_only)
        c[PASS] += ipk[passed].sum() \
            + (ipk[ml_drop_only] - iml[ml_drop_only]).sum()
        self.records += n
        newly = over_rate | over_ml
        if newly.any():
            self.block_key.append(rep_key[newly])
            self.block_until.append(new_until[newly])

    def blocks(self) -> tuple[np.ndarray, np.ndarray]:
        if not self.block_key:
            return np.empty(0, np.uint32), np.empty(0, F32)
        return np.concatenate(self.block_key), np.concatenate(self.block_until)


def dense_ids(keys: np.ndarray) -> tuple[np.ndarray, int]:
    """Every dispatched key -> a dense id, and the id count."""
    uniq, inv = np.unique(keys, return_inverse=True)
    return inv.astype(np.int32), len(uniq)


# -- the comparison ----------------------------------------------------------

def blocks_gap(ref_key, ref_until, got_key, got_until,
               tol_s: float = 1e-3) -> tuple[float, dict]:
    """Share of block events that do not pair up: the n-th block of a
    source in the reference against the n-th that reached the verdict
    ring, paired when their expiries agree within ``tol_s``."""
    def ranked(key, until):
        order = np.lexsort((until, key))
        k, u = key[order].astype(np.uint64), until[order]
        first = np.empty(len(k), bool)
        if len(k):
            first[0] = True
            np.not_equal(k[1:], k[:-1], out=first[1:])
        idx = np.arange(len(k))
        rank = idx - np.maximum.accumulate(np.where(first, idx, 0))
        return (k << np.uint64(20)) | rank.astype(np.uint64), u

    rc, ru = ranked(np.asarray(ref_key), np.asarray(ref_until, F32))
    gc, gu = ranked(np.asarray(got_key), np.asarray(got_until, F32))
    both, ri, gi = np.intersect1d(rc, gc, assume_unique=True,
                                  return_indices=True)
    far = int((np.abs(ru[ri].astype(np.float64)
                      - gu[gi].astype(np.float64)) > tol_s).sum())
    only_ref, only_got = len(rc) - len(both), len(gc) - len(both)
    detail = {"ref_blocks": len(rc), "ring_blocks": len(gc),
              "only_ref": only_ref, "only_ring": only_got, "far": far}
    return (only_ref + only_got + far) / max(len(rc), 1), detail


def counters_gap(ref_counts, got: dict) -> tuple[float, dict]:
    """Widest gap of the four verdict counters, as a share of records."""
    total = max(int(np.sum(ref_counts)), 1)
    gaps = {name: int(got[name]) - int(ref_counts[i])
            for i, name in enumerate(COUNTER_NAMES)}
    return max(abs(g) for g in gaps.values()) / total, gaps

"""What decides ``correct``: the plain reference replays every batch the
timed path dispatched, from the empty table (the resident population's
prefill first), and these numbers are held to their limits (PERF.md §2
gives the readings each limit was set from).  The two limits that are
not exact live in the configuration file (``correct_limits``).

* ``blocks_gap``   — share of block events that do not pair up between
  the reference and what reached the verdict sink;
* ``counters_gap`` — widest gap of the four verdict counters
  (allowed / dropped_blacklist / dropped_rate / dropped_ml), as a share
  of the records served;
* ``records_unaccounted`` — records the generator forwarded that were
  neither served nor counted in a named drop counter, after the rings
  were drained to the last record (exact: 0);
* ``batches_gap``  — sealed batches the engine counts against what the
  reference replayed (exact: 0);
* whatever the cell's transport owes besides (the driver's
  ``transport_compared``; each exact: 0).  For the shm rings:
  ``ingest_words_differ`` — sealed rows that differ from the reference's
  own quantise of the raw ring records; ``verdict_ring_differ`` — blocks
  read back from the verdict ring's memory that differ from what the
  engine handed to the sink, blocks handed over that the ring's cursor
  does not count, verdicts the daemon counted that its cursor does not;
  ``verdict_ring_dropped`` — blocks that did not fit the ring.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference

#: ingest fail-opens counted in whole batches (quarantined batches are
#: counted by their records)
BATCH_FAIL_OPENS = ("dropped_tail_batches", "dropped_emit_batches",
                    "bad_wire_slots")


def ingest_failed(rep: dict, max_batch: int) -> int:
    """Records the ingest plane failed open on, from the report: whole
    batches at ``max_batch``, quarantined records as counted, sequence
    gaps as missing batches."""
    ing = rep.get("ingest")
    if not ing:
        return 0
    n = sum(ing.get(k, 0) for k in BATCH_FAIL_OPENS) * max_batch
    n += ing.get("quarantined_records", 0)
    n += sum(w.get("seq_missing", 0) for w in ing["workers"].values()
             ) * max_batch
    return int(n)


def failed_records(config: dict, snap0: dict, snap1: dict,
                   end: dict) -> int:
    """Records of the window that got no verdict: ring-full drops (the
    daemon's count, known at its end), ingest fail-opens and
    ``route_drop``.  Backlog is not a failure."""
    b = config["batch"]["max_batch"]
    return (end["gen"]["dropped_ring_full"]
            + ingest_failed(end["rep"], b) - ingest_failed(snap0["rep"], b)
            + snap1["rep"]["route_drop"] - snap0["rep"]["route_drop"])


def judge(config: dict, driver, sink, end: dict,
          precision: str = "int8") -> dict:
    limits = config["correct_limits"]
    batches = list(driver.dispatched(config))
    keys = (np.concatenate([w[:, 0] for w, _ in batches])
            if batches else np.empty(0, np.uint32))
    ids, n_src = reference.dense_ids(keys)
    ref = reference.Reference(config, max(n_src, 1), precision)
    pos = 0
    for words, base_us in batches:
        ref.step(words, base_us, ids[pos:pos + len(words)])
        pos += len(words)
    ref_key, ref_until = ref.blocks()
    got_key, got_until = sink.blocks()
    bgap, bdetail = reference.blocks_gap(ref_key, ref_until,
                                         got_key, got_until)
    cgap, cdetail = reference.counters_gap(ref.counts, end["rep"]["stats"])
    rep = end["rep"]
    max_batch = config["batch"]["max_batch"]
    served = rep["records"]
    forwarded = end["gen"]["forwarded"]
    unaccounted = abs(forwarded - served - rep["route_drop"]
                      - ingest_failed(rep, max_batch)
                      - end["gen"]["backlog"])
    n_nonempty = sum(1 for w, _ in batches if len(w))
    compared = {
        "blocks_gap": {"value": bgap, "limit": limits["blocks_gap"]},
        "counters_gap": {"value": cgap, "limit": limits["counters_gap"]},
        "records_unaccounted": {"value": int(unaccounted), "limit": 0},
        "batches_gap": {"value": abs(rep["batches"] - n_nonempty),
                        "limit": 0},
    }
    detail = {}
    for name, c in driver.transport_compared(config, sink).items():
        detail[name] = c.pop("detail", None)
        compared[name] = c
    ok = all(c["value"] <= c["limit"] for c in compared.values())
    ok = ok and ref.records == served and len(ref_key) > 0
    detail.update({"blocks": bdetail, "counters": cdetail,
                   "ref_records": ref.records, "served": served,
                   "forwarded": forwarded, "sources": n_src})
    return {"correct": ok, "compared": compared, "detail": detail}

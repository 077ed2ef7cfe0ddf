"""Shared schemas: the single source of truth for every plane.

The reference splits its data layout across ``src/fsx_struct.h`` (map
value structs, ``fsx_struct.h:11-22``), the feature list buried in the
training script (``model/model.py:117``), and implicit conventions in
``src/fsx_kern.c``.  Here one module defines:

* the 8-feature vector layout (identical feature semantics to the
  reference's ``feature_list``, ``model/model.py:117``),
* the per-flow record the kernel pushes through the feature ring
  (successor of the never-implemented ``src/fsx_kern_ml.c`` egress),
* the streaming per-flow statistics the kernel keeps to estimate the
  flow-level features (the reference never solved train/serve skew —
  its in-kernel plan stopped at a comment block, ``fsx_kern_ml.c:1-17``),
* the device-resident per-IP limiter state (successor of
  ``struct ip_stats {pps,bps,track_time}``, ``fsx_struct.h:17-22``,
  extended with sliding-window and token-bucket state that the
  reference only specified, ``README.md:153-162``),
* global stats (successor of ``struct stats {allowed,dropped}``,
  ``fsx_struct.h:11-15``) and verdict codes.

``kern/fsx_schema.h`` is *generated* from this module by
:mod:`flowsentryx_tpu.core.codegen` so the C and JAX sides can never
drift.
"""

from __future__ import annotations

import enum
from typing import TYPE_CHECKING, NamedTuple

import numpy as np

# jax is imported lazily inside the functions that build device arrays:
# this module is the wire-contract ground truth for EVERY process in the
# pipeline, including the ingest drain workers (flowsentryx_tpu/ingest/)
# which are pure-numpy and must spawn in ~0.3 s, not pay the multi-second
# jax import for dtypes and integer pack functions.
if TYPE_CHECKING:  # annotations only; `from __future__ import annotations`
    import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Feature vector
# ---------------------------------------------------------------------------

#: Feature names, in model-input order.  Semantics match the reference's
#: ``feature_list`` (``model/model.py:117``): CICIDS2017 flow-level
#: statistics.  The kernel computes streaming estimates of these (see
#: FlowStats below); the offline trainer computes them exactly from CSVs.
#: The 8-wide feature vector.  Slots 0-2 and 5-7 mirror the reference's
#: CICIDS selection (``model.py:117``); slots 3 and 4 originally held
#: packet_length_variance and average_packet_size — both redundant with
#: their neighbours (variance = std², avg ≈ mean) — and are redefined
#: as flow-age features the slow-attack class needs (VERDICT r4 #6;
#: ``model.py:117``'s list is a reference limitation, not a spec):
#: flow duration in ms and packet rate in pps×1000, both free from
#: ``fsx_flow_stats``' first/last timestamps and count.  The wire
#: layout (8×u32 raw, 8×minifloat compact) is unchanged.
FEATURE_NAMES: tuple[str, ...] = (
    "destination_port",
    "packet_length_mean",
    "packet_length_std",
    "flow_duration_ms",
    "flow_pps_x1000",
    "fwd_iat_mean",
    "fwd_iat_std",
    "fwd_iat_max",
)

NUM_FEATURES: int = len(FEATURE_NAMES)  # 8


class Feature(enum.IntEnum):
    """Index of each feature within the 8-wide vector."""

    DST_PORT = 0
    PKT_LEN_MEAN = 1
    PKT_LEN_STD = 2
    FLOW_DUR_MS = 3
    FLOW_PPS_X1000 = 4
    FWD_IAT_MEAN = 5
    FWD_IAT_STD = 6
    FWD_IAT_MAX = 7


# ---------------------------------------------------------------------------
# Flow record: the kernel → user wire format (feature ring entries)
# ---------------------------------------------------------------------------

#: Record flag bits (``flags`` field of the flow record).
FLAG_IPV6 = 1 << 0
FLAG_TCP_SYN = 1 << 1
FLAG_TCP = 1 << 2
FLAG_UDP = 1 << 3
FLAG_ICMP = 1 << 4

#: numpy structured dtype of one ring entry.  Field order/padding matches
#: the generated C struct ``struct fsx_flow_record`` exactly (packed,
#: 48 bytes).  10 Mpps × 48 B = 480 MB/s over the ring — within both
#: per-CPU ringbuf and PCIe budgets (SURVEY.md §7.4).
#:
#: Features are u32, not f32: eBPF has no FPU (``fsx_kern_ml.c:3-6``),
#: so the kernel emits integer estimates (ports, bytes, µs — all
#: integral quantities, saturated at 2^32-1) and the host batcher casts
#: to float32 once per record in :func:`decode_records`.
FLOW_RECORD_DTYPE = np.dtype(
    [
        ("ts_ns", "<u8"),       # bpf_ktime_get_ns() at packet arrival
        ("saddr", "<u4"),       # IPv4 source addr, or 32-bit fold of IPv6
        ("pkt_len", "<u2"),     # wire length of this packet
        ("ip_proto", "u1"),     # IPPROTO_*
        ("flags", "u1"),        # FLAG_* bits
        ("feat", "<u4", (NUM_FEATURES,)),  # streaming feature estimates
    ]
)
FLOW_RECORD_SIZE = FLOW_RECORD_DTYPE.itemsize  # 48
assert FLOW_RECORD_SIZE == 48

#: The ring record of a deployment that keys sources by all 128 bits
#: (``TableConfig.key_bits`` 128; upstream ``src/fsx_struct.h:9``
#: ``__u128``): the 48 B record with the full source in place of the
#: u32 one, padded to 64 B.  ``saddr6`` is the address's 16 wire bytes
#: read as four little-endian u32 words, as the kernel loads them; an
#: IPv4 source is carried as ``::ffff:a.b.c.d`` (RFC 4291 §2.5.5.2), so
#: its word 3 is the 48 B record's ``saddr``.
FLOW_RECORD6_DTYPE = np.dtype(
    [
        ("ts_ns", "<u8"),
        ("saddr6", "<u4", (4,)),  # full source address, wire words
        ("pkt_len", "<u2"),
        ("ip_proto", "u1"),
        ("flags", "u1"),
        ("feat", "<u4", (NUM_FEATURES,)),
        ("_pad", "<u4"),
    ]
)
FLOW_RECORD6_SIZE = FLOW_RECORD6_DTYPE.itemsize  # 64
assert FLOW_RECORD6_SIZE == 64

#: Words 0-2 of a v4-mapped source ``::ffff:0:0/96`` (wire words).
V4_MAPPED_PREFIX = (0, 0, 0xFFFF0000)


def key_words(key_bits: int) -> int:
    """u32 words of one flow key: 1 for 32-bit keys, 4 for 128-bit."""
    return key_bits // 32


#: Streaming per-flow statistics the kernel keeps (one entry per tracked
#: flow) to derive the 8 features online.  Welford-free: we keep sums and
#: sums-of-squares in integer nanosecond / byte units and let the feature
#: derivation divide once per emitted record.
FLOW_STATS_FIELDS: tuple[tuple[str, str], ...] = (
    ("pkt_count", "u64"),
    ("byte_sum", "u64"),
    ("byte_sq_sum", "u64"),
    ("first_ts_ns", "u64"),
    ("last_ts_ns", "u64"),
    ("iat_sum_ns", "u64"),
    # IAT sum-of-squares is accumulated in MICROsecond^2 units: a 1 s gap
    # in ns^2 is 1e18, so ~18 such gaps would wrap a u64; in us^2 it is
    # 1e12, good for ~1.8e7 seconds of worst-case gaps per flow.
    ("iat_sq_sum_us2", "u64"),
    ("iat_max_ns", "u64"),
    ("dst_port", "u16"),
)


#: ``struct fsx_ip_state`` — the kernel-side per-source-IP fast-path
#: counters (successor of ``struct ip_stats``, ``fsx_struct.h:17-22``,
#: extended with sliding-window + token-bucket state, README.md:153-162).
#: Integer units only (no floats in eBPF); tokens ×1000 for precision.
#: The *device*-side mirror is :class:`IpTableState` below — richer
#: (float32, blacklist merged in) because the TPU plane has no eBPF
#: constraints; the two are intentionally distinct layouts.
IP_STATE_FIELDS: tuple[tuple[str, str], ...] = (
    ("win_start_ns", "u64"),
    ("win_pps", "u64"),
    ("win_bps", "u64"),
    ("prev_pps", "u64"),
    ("prev_bps", "u64"),
    ("tokens_milli", "u64"),
    ("tok_ts_ns", "u64"),
    ("tok_bytes", "u64"),
)

#: ``struct fsx_stats`` — kernel-side global counters, kept in a
#: PER_CPU array map (race-free increments; user space aggregates —
#: the improvement proposed at ``fsx_kern.c:253-257``).  The host-side
#: :class:`GlobalStats` additionally tracks ``batches``, which is a
#: TPU-plane concept with no kernel meaning — intentionally absent here.
KERNEL_STATS_FIELDS: tuple[tuple[str, str], ...] = (
    ("allowed", "u64"),
    ("dropped_blacklist", "u64"),
    ("dropped_rate", "u64"),
    ("dropped_ml", "u64"),
    ("dropped_rule", "u64"),
    # Two-tier escalation bands (kernel-distilled classifier,
    # flowsentryx_tpu/distill/): confident-benign records whose ringbuf
    # emit was suppressed, and uncertain records escalated to the TPU
    # tier.  Confident-attack drops land in ``dropped_ml`` above —
    # the field existed for exactly this purpose since the seed.
    ("ml_pass", "u64"),
    ("ml_escalated", "u64"),
)


# ---------------------------------------------------------------------------
# Kernel-distilled classifier (the two-tier escalation protocol)
# ---------------------------------------------------------------------------
#
# ``struct fsx_ml_model`` is the hot-swappable map value the distiller
# (flowsentryx_tpu/distill/) compiles a LogRegParams artifact into.  The
# XDP-side scorer (bpf/progs.py fn_ml_score) is integer-only and
# MODEL-SHAPE-FIXED: pushing a new blob into ``ml_model_map`` swaps
# weights/boundaries/thresholds live, with no program reload.
#
#   valid       nonzero once a model has been pushed; the ARRAY map's
#               zero fill means "no model" and the stage becomes a
#               no-op (every record escalates, exactly the pre-ML path)
#   _reserved   alignment/future flags
#   acc_drop    s64: drop band — s >= acc_drop (s = sum w[i]*q[i])
#   acc_pass    s64: silent-pass band — s <= acc_pass
#   w           s32[8] (int8 weights widened; two's complement in u32)
#   qbase       u32[8]: q_i(0), the quantized value of a zero feature
#   bounds_m1   u32[8*255]: per (feature, rank) quantization boundary
#               minus one, sorted ascending per feature, padded with
#               0xFFFFFFFF.  The kernel's rank loop computes
#               q_i(x) = qbase[i] + popcount over (x > bounds_m1) —
#               BIT-EXACT with the engine's f32 input observer because
#               the distiller derives each boundary from the exact
#               device-side quantization chain by bisection.
#
# The acc thresholds fold the input zero-point in: the JAX lane's
# accumulator is sum (q-zp)*w = s - zp*sum(w), so the distiller shifts
# the thresholds by zp*sum(w) and the kernel never multiplies by zp.

ML_BOUNDS_PER_FEATURE = 255  # one boundary per reachable quant step
ML_MODEL_VALID_OFFSET = 0
ML_MODEL_FLAGS_OFFSET = 4
ML_MODEL_ACC_DROP_OFFSET = 8
ML_MODEL_ACC_PASS_OFFSET = 16
ML_MODEL_W_OFFSET = 24
ML_MODEL_QBASE_OFFSET = 56
ML_MODEL_BOUNDS_OFFSET = 88
ML_MODEL_SIZE = ML_MODEL_BOUNDS_OFFSET + 4 * 8 * ML_BOUNDS_PER_FEATURE  # 8248

#: fn_ml_score return codes (the band split; FSX_ML_BAND_* in C).
ML_BAND_PASS = 0       # confident benign: XDP_PASS, emit suppressed
ML_BAND_ESCALATE = 1   # uncertain: emit the record, TPU tier decides
ML_BAND_DROP = 2       # confident attack: blacklist + XDP_DROP
ML_BAND_DISABLED = 3   # no model pushed: behave exactly pre-ML

# ---------------------------------------------------------------------------
# Machine-readable struct layouts (the cross-layer contract surface)
# ---------------------------------------------------------------------------
#
# Everything below the kernel/user seam speaks PACKED structs whose
# single source of truth is this module: codegen renders them into
# kern/fsx_schema.h (compiled by the C daemon and the BPF C twin),
# progs.py bakes their offsets into bytecode immediates, and the ingest
# decoders read them back.  ``struct_layouts()`` exposes those layouts
# as data so the static contract checker (``flowsentryx_tpu.bpf.
# contracts``, surfaced as ``fsx check``) can diff every layer against
# this one definition instead of each pair drifting independently.

_TYPE_SIZES = {"u64": 8, "u32": 4, "u16": 2, "u8": 1}


class FieldLayout(NamedTuple):
    """One field of a packed struct: byte offset + element size/count."""

    name: str
    offset: int
    size: int       # size of ONE element
    count: int = 1  # > 1 for array fields


class StructLayout(NamedTuple):
    """A packed struct: total size plus per-field offsets."""

    name: str
    size: int
    fields: tuple[FieldLayout, ...]

    def offset_of(self, field: str) -> int:
        for f in self.fields:
            if f.name == field:
                return f.offset
        raise KeyError(f"{self.name} has no field {field!r}")


def _layout_from_fields(
    cname: str, fields: tuple[tuple[str, str], ...]
) -> StructLayout:
    out, off = [], 0
    for name, tp in fields:
        size = _TYPE_SIZES[tp]
        out.append(FieldLayout(name, off, size))
        off += size
    return StructLayout(cname, off, tuple(out))


def _layout_from_dtype(cname: str, dt: np.dtype) -> StructLayout:
    out = []
    for name in dt.names:
        ft, off = dt.fields[name][:2]
        if ft.subdtype is not None:
            base, shape = ft.subdtype
            out.append(FieldLayout(name, off, base.itemsize, shape[0]))
        else:
            out.append(FieldLayout(name, off, ft.itemsize))
    return StructLayout(cname, dt.itemsize, tuple(out))


def struct_layouts() -> dict[str, StructLayout]:
    """Every packed struct of the kernel/user/device seam, keyed by its
    C name — the layouts codegen generates, progs.py bakes, and the
    decoders parse.  ``fsx check`` diffs all of them against this."""
    from flowsentryx_tpu.core.config import FsxConfig

    shm_hdr = StructLayout(
        "fsx_shm_ring_hdr", SHM_HDR_SIZE, (
            FieldLayout("magic", 0, 8),
            FieldLayout("capacity", SHM_CAPACITY_OFFSET, 8),
            FieldLayout("record_size", SHM_RECORD_SIZE_OFFSET, 8),
            FieldLayout("_meta_pad", 24, 8, 5),
            FieldLayout("head", SHM_HEAD_OFFSET, 8),
            FieldLayout("_head_pad", SHM_HEAD_OFFSET + 8, 8, 7),
            FieldLayout("tail", SHM_TAIL_OFFSET, 8),
            FieldLayout("_tail_pad", SHM_TAIL_OFFSET + 8, 8, 7),
        ))
    ml_model = StructLayout(
        "fsx_ml_model", ML_MODEL_SIZE, (
            FieldLayout("valid", ML_MODEL_VALID_OFFSET, 4),
            FieldLayout("_reserved", ML_MODEL_FLAGS_OFFSET, 4),
            FieldLayout("acc_drop", ML_MODEL_ACC_DROP_OFFSET, 8),
            FieldLayout("acc_pass", ML_MODEL_ACC_PASS_OFFSET, 8),
            FieldLayout("w", ML_MODEL_W_OFFSET, 4, NUM_FEATURES),
            FieldLayout("qbase", ML_MODEL_QBASE_OFFSET, 4, NUM_FEATURES),
            FieldLayout("bounds_m1", ML_MODEL_BOUNDS_OFFSET, 4,
                        NUM_FEATURES * ML_BOUNDS_PER_FEATURE),
        ))
    return {
        "fsx_config": _layout_from_fields(
            "fsx_config",
            tuple((n, t) for n, t, _ in FsxConfig.KERNEL_CONFIG_FIELDS)),
        "fsx_ml_model": ml_model,
        "fsx_ip_state": _layout_from_fields("fsx_ip_state",
                                            IP_STATE_FIELDS),
        "fsx_flow_stats": _layout_from_fields("fsx_flow_stats",
                                              FLOW_STATS_FIELDS),
        "fsx_stats": _layout_from_fields("fsx_stats",
                                         KERNEL_STATS_FIELDS),
        "fsx_flow_record": _layout_from_dtype("fsx_flow_record",
                                              FLOW_RECORD_DTYPE),
        "fsx_compact_record": _layout_from_dtype("fsx_compact_record",
                                                 COMPACT_RECORD_DTYPE),
        "fsx_verdict_record": _layout_from_dtype("fsx_verdict_record",
                                                 VERDICT_RECORD_DTYPE),
        "fsx_flow_record6": _layout_from_dtype("fsx_flow_record6",
                                               FLOW_RECORD6_DTYPE),
        "fsx_verdict_record6": _layout_from_dtype("fsx_verdict_record6",
                                                  VERDICT_RECORD6_DTYPE),
        "fsx_shm_ring_hdr": shm_hdr,
    }


# ---------------------------------------------------------------------------
# Stateless firewall rules (the reference's planned "basic firewall",
# README.md:70-74: config-file rules to drop certain packets)
# ---------------------------------------------------------------------------

#: Kernel rule map capacity (exact + wildcard (proto,dport) entries).
MAX_RULES = 1024
#: Rule action codes (map value).
RULE_DROP = 1


def pack_rule_key(proto: int, dport: int) -> int:
    """Rule-map key: ``(l4_proto << 16) | dport`` in HOST order, with 0
    as the wildcard in either position — the exact packing the kernel
    twins compute per packet."""
    return ((proto & 0xFF) << 16) | (dport & 0xFFFF)


# ---------------------------------------------------------------------------
# Shared-memory rings (daemon <-> engine transport)
# ---------------------------------------------------------------------------

#: Magic for the mmap'd SPSC ring segments the C++ daemon and the Python
#: engine share.  Layout (generated into C as struct fsx_shm_ring_hdr):
#: one 128-byte header — magic/capacity/record_size, then head (producer
#: cursor) and tail (consumer cursor) on separate cache lines — followed
#: by ``capacity`` fixed-size records.  Single-producer single-consumer;
#: cursors are monotonically increasing record counts (mod capacity for
#: the slot index), which distinguishes full from empty without a spare
#: slot.  x86-TSO plain loads/stores are sufficient on the Python side;
#: the C++ side uses acquire/release atomics.
SHM_MAGIC = 0x46535852494E4731  # "FSXRING1"
SHM_HDR_SIZE = 192              # 3 cache lines: meta / head / tail
SHM_CAPACITY_OFFSET = 8         # u64: record slots, power of two
SHM_RECORD_SIZE_OFFSET = 16     # u64: bytes per record
SHM_HEAD_OFFSET = 64            # u64: producer cursor (records written)
SHM_TAIL_OFFSET = 128           # u64: consumer cursor (records read)

# -- Sealed-batch queues (ingest worker -> engine transport) ---------------
#
# The sharded ingest subsystem (flowsentryx_tpu/ingest/) moves SEALED
# wire buffers — not raw records — from each drain worker to the engine
# over one SPSC shared-memory queue per worker.  A queue reuses the ring
# header geometry above (magic/capacity/"record"-size, head and tail on
# their own cache lines) with `capacity` fixed-size batch SLOTS, plus a
# control block in the spare bytes of the meta cache line (all u64,
# plain-store published under the same x86-TSO discipline as the
# cursors; each field has exactly one writer):
#
#   HBEAT     worker-written CLOCK_MONOTONIC ns, bumped every drain
#             loop — the engine's liveness signal (stall detection).
#   FIRST_TS  worker-written: absolute ts_ns of the first record this
#             shard saw (0 = none yet).  Input to the t0 handshake.
#   T0        engine-written: the agreed epoch t0_ns.  Workers buffer
#             records until it is published — every worker must seal
#             batches against ONE epoch or cross-shard timestamps (and
#             the device flow windows built on them) would skew.
#   STOP      engine-written: nonzero asks the worker to drain its ring
#             to empty, flush the partial batch, and exit cleanly.
#   WSTATE    worker-written lifecycle: SPAWNING -> RUNNING -> DONE
#             (clean exit) / FAILED (crashed with a traceback).
#
# Each slot is an 8-word header followed by one wire buffer
# ``[max_batch+1, words]`` (raw48 or compact16, `wire_id` says which):
#
#   word 0/1  seq lo/hi    1-based per-worker batch sequence number —
#                          the engine detects gaps (corruption or a
#                          worker restart) instead of silently
#                          misordering flow updates.
#   word 2    n_records    valid records (mirrors the meta row).
#   word 3    wire_id      WIRE_ID_* of the payload.
#   word 4/5  seal ns lo/hi  CLOCK_MONOTONIC at seal (queue-residency
#                          and e2e accounting; same clock as
#                          time.perf_counter on Linux).
#   word 6    fill_dur_us  first-record-arrival -> seal duration.
#   word 7    ring_wait_us the batch's first record's wait in the feature
#                          ring: publish (as the worker's cursor log
#                          saw it) -> first-record-arrival; saturating.

SHM_BATCHQ_MAGIC = 0x4653584241545131  # "FSXBATQ1"
SHM_HBEAT_OFFSET = 24
SHM_FIRST_TS_OFFSET = 32
SHM_T0_OFFSET = 40
SHM_STOP_OFFSET = 48
SHM_WSTATE_OFFSET = 56
#: u64, producer-written (lives on the producer-cursor cache line, same
#: writer side): sealed batches the worker gave up enqueueing during
#: stop-drain because the queue stayed full past its bounded wait.  The
#: worker un-burns the batch's seq first, so a seq gap remains a pure
#: corruption/restart signal and this counter is the ONLY place such a
#: loss shows up.
SHM_EMIT_DROP_OFFSET = 72
#: u64s, worker-written beside EMIT_DROP (ingest/worker.py
#: ``CursorLog``): batches whose first record's cursor was older than
#: the worker's log of the ring's producer cursor, so that their ring
#: wait is clamped to the log's oldest entry (an understatement), and
#: how many times the worker has looked at that cursor (the log's
#: observation interval is the worker's wall over this count).
SHM_RING_WAIT_CLAMPED_OFFSET = 80
SHM_RING_OBSERVES_OFFSET = 88
#: u64 pair, creator-written BEFORE the worker spawns (read-only
#: thereafter, so the one-writer rule holds trivially): the worker's
#: idle backoff policy.  SPIN_US is the budget of busy-spin polling
#: after the ring goes empty (wakeup latency at high rates — a sleeping
#: worker adds a whole scheduler quantum to the next record's path);
#: IDLE_US is the sleep once the spin budget is exhausted (idle cores
#: stop burning).  0 means "worker default" — a bare queue created by
#: tests keeps the pre-backoff behavior.  They live on the consumer
#: cache line: written once at create, never contended.
SHM_SPIN_US_OFFSET = 136
SHM_IDLE_US_OFFSET = 144

WSTATE_SPAWNING = 0
WSTATE_RUNNING = 1
WSTATE_DONE = 2
WSTATE_FAILED = 3

BATCHQ_SLOT_HDR_WORDS = 8
#: Named slot-header word indices (the seal block above).  The seal
#: stamp pair is the per-record latency plane's measurement anchor
#: (ISSUE 11): every record of a sealed batch is timestamped at shm
#: seal by its worker (words 4/5, CLOCK_MONOTONIC ns — the same clock
#: as ``time.perf_counter`` on Linux), with word 6 recovering the
#: batch's first-record arrival and word 7 that record's wait in the
#: feature ring before it; ``SealedBatchQueue.peek_batches`` surfaces
#: the header and the engine's sink section closes the seal→verdict
#: and arrival→verdict intervals against it.
BATCHQ_SEQ_LO_WORD = 0
BATCHQ_SEQ_HI_WORD = 1
BATCHQ_N_RECORDS_WORD = 2
BATCHQ_WIRE_ID_WORD = 3
BATCHQ_SEAL_NS_LO_WORD = 4
BATCHQ_SEAL_NS_HI_WORD = 5
BATCHQ_FILL_DUR_US_WORD = 6
BATCHQ_RING_WAIT_US_WORD = 7
WIRE_ID_RAW48 = 0
WIRE_ID_COMPACT16 = 1
WIRE_ID_COMPACT28 = 2

# -- cluster gossip/status shm layout (flowsentryx_tpu/cluster/) ------------
# Same 192 B header geometry and x86-TSO plain-store cursor protocol as
# the rings above.  A gossip mailbox slot is a 4-word header (seq lo/hi,
# entry count, reserved) followed by one [2K+4]-word compact verdict
# wire (ops/fused.py layout — decode_verdict_wire reads it unchanged).

SHM_GOSSIP_MAGIC = 0x465358474F535331   # "FSXGOSS1"
GOSSIP_SLOT_HDR_WORDS = 4

#: Live shard-handoff mailbox (cluster/rebalance.py): the VerdictMailbox
#: SPSC geometry with ROW payloads — each slot is a 4-word u32 header
#: (seq lo/hi, row count, slot kind) followed by ``rows_per_slot``
#: packed table rows of ``1 + NUM_TABLE_COLS`` u32 words (key, then the
#: f32 state columns bit-cast).  ``row_words`` rides the file header's
#: 4th u64 so a geometry mismatch between donor and recipient is
#: structurally impossible.  The stream ends with one SEAL slot whose
#: payload carries the total row count (u64 split) and a CRC32 over the
#: shipped bytes in ship order — the recipient refuses a short or torn
#: stream instead of staging it.
SHM_HANDOFF_MAGIC = 0x4653584844464631  # "FSXHDFF1"
HANDOFF_SLOT_HDR_WORDS = 4
HANDOFF_KIND_ROWS = 0
HANDOFF_KIND_SEAL = 1

#: Engine-side handoff phase acks (STATUS_HANDOFF_OFFSET encoding
#: ``handoff_id * 8 + HP_*``; cluster/rebalance.py state machine).
HP_SHIPPED = 1     # donor: span rows published + sealed
HP_STAGED = 2      # recipient: stream verified + spooled crash-safe
HP_DROPPED = 3     # donor: observed the flip, span rows dropped
HP_INSERTED = 4    # recipient: observed the flip, staged rows inserted

# -- multi-host gossip datagram layout (cluster/transport.py) ---------------
# One UDP datagram per verdict wire: a 9-word u32 header followed by the
# SAME [2K+4]-word compact verdict wire the shm mailboxes carry (564 B
# at K=64 — comfortably under any MTU, so a wire is never fragmented by
# us).  The u64 sequence and the u64 t0-wall epoch are split across two
# u32 words exactly like the VerdictMailbox slot header — the split/
# reassembly is test-pinned across the 2^32 word boundary on both
# transports.
NET_PKT_MAGIC = 0x4653584E              # "FSXN"
NET_MAGIC_WORD = 0
NET_KIND_WORD = 1
NET_HOST_WORD = 2                       # sender host id
NET_RANK_WORD = 3                       # sender engine rank (or NET_RANK_BEACON)
NET_SEQ_LO_WORD = 4                     # u64 per-peer wire seq, lo half
NET_SEQ_HI_WORD = 5
NET_COUNT_WORD = 6                      # verdicts in the wire payload
NET_T0_WALL_LO_WORD = 7                 # sender's epoch wall stamp, lo half
NET_T0_WALL_HI_WORD = 8
NET_PKT_HDR_WORDS = 9
#: datagram kinds: verdict wire, peer-discovery handshake (HELLO is
#: retried with exponential backoff, WELCOME acknowledges), and the
#: supervisor federation liveness beacon.
NET_KIND_WIRE = 1
NET_KIND_HELLO = 2
NET_KIND_WELCOME = 3
NET_KIND_BEACON = 4
#: the rank word of a supervisor beacon (not an engine endpoint)
NET_RANK_BEACON = 0xFFFFFFFF

#: Per-engine cluster status block (supervisor <-> engine lifecycle).
#: One writer side per field, cache-line-split by writer exactly like
#: the ring cursors: ENGINE-written fields live on the 64-byte line at
#: 64.., SUPERVISOR-written fields on the line at 128.. — so the
#: plain-store single-writer premise holds per line, not just per
#: field.  The writer sides are registered (and AST-enforced) in
#: sync/contracts.py CTL_WRITERS.
SHM_STATUS_MAGIC = 0x4653585354415431   # "FSXSTAT1"
SHM_STATUS_SIZE = 192
STATUS_RANK_OFFSET = 8                  # u64, creator-written geometry
# engine-written line
STATUS_HBEAT_OFFSET = 64                # u64 CLOCK_MONOTONIC ns
STATUS_STATE_OFFSET = 72                # u64 CSTATE_*
STATUS_BATCHES_OFFSET = 80              # u64 batches served (monitor)
STATUS_RECORDS_OFFSET = 88              # u64 records served (monitor)
#: Engine process id, stamped at boot (cluster/runner.py).  A
#: re-attaching supervisor (``boot(adopt=True)``) owns no Process
#: handles for ranks it did not spawn — pid + os.kill(pid, 0) +
#: heartbeat age is how it re-derives liveness from the plane alone.
STATUS_PID_OFFSET = 96
#: Engine-side handoff progress ack: ``handoff_id * 8 + HP_*`` phase
#: (cluster/rebalance.py state machine).  0 = no handoff touched.
STATUS_HANDOFF_OFFSET = 104
#: Engine's echo of the last shard-assignment generation it converged
#: on (reloaded layout.json + applied its side of the flip).  The
#: supervisor lifts the fence only once every live rank's ack matches
#: the stamped generation.
STATUS_LAYOUT_ACK_OFFSET = 112
# supervisor-written line
STATUS_STOP_OFFSET = 128                # u64 drain-and-exit request
STATUS_GEN_OFFSET = 136                 # u64 restart generation
STATUS_T0_OFFSET = 144                  # u64 shared cluster epoch (ns)
#: CLOCK_REALTIME ns stamped at the SAME instant as the monotonic t0
#: above.  Monotonic clocks are per-host (each restarts at its own
#: boot), so the single-host byte-identical-untils trick cannot cross
#: hosts; the wall stamp is what lets a received verdict wire be
#: rebased tx-epoch -> rx-epoch (cluster/transport.py).  0 = no
#: network leg (single-host fleets never stamp it).
STATUS_T0_WALL_OFFSET = 152             # u64 CLOCK_REALTIME ns at t0
#: Current shard-assignment generation (cluster/rebalance.py): the
#: supervisor stamps it on every rank AFTER atomically publishing the
#: matching layout.json — the layout-generation flip rule.  Engines
#: observe the stamp between run chunks, reload the layout, apply
#: their side of the flip (donor drops the span, recipient inserts its
#: staged rows) and echo via STATUS_LAYOUT_ACK_OFFSET.
STATUS_LAYOUT_GEN_OFFSET = 160
#: Active handoff id (nonzero = a span is FENCED: producers route no
#: new records for the moving shards — they fall to the kernel tier,
#: counted — until the flip commits or the handoff aborts to 0).
STATUS_FENCE_OFFSET = 168

CSTATE_SPAWNING = 1
CSTATE_SERVING = 2
CSTATE_DONE = 3
CSTATE_FAILED = 4
#: Local serving finished, gossip still quiescing: the engine's LAST
#: publish happened-before this store (TSO), so a peer that reads
#: DRAINING + an idle mailbox has provably merged everything this
#: engine will ever say — the co-terminating-drain convergence signal.
CSTATE_DRAINING = 5


def wire_id_of(wire: str) -> int:
    return {WIRE_COMPACT16: WIRE_ID_COMPACT16,
            WIRE_COMPACT28: WIRE_ID_COMPACT28}.get(wire, WIRE_ID_RAW48)


def shard_ring_path(base: str, shard: int, n_shards: int) -> str:
    """Feature-ring path of one shard — the naming contract with
    ``fsxd --shards N`` (and the sharded test producers).  N=1 keeps
    the unsuffixed path so one worker can front an unsharded daemon."""
    return str(base) if n_shards <= 1 else f"{base}.{shard}"


def shard_of(saddr, n_shards: int):
    """Shard index of a folded source address — the IP-hash affinity
    both producers use (Fibonacci hash; mirrors ``fsx_shard_of`` in the
    daemon).  Keeping a flow's records on ONE shard preserves their
    relative order through the parallel ingest stage, matching the
    kernel's per-CPU production semantics.  ``[n, 4]`` keys are first
    mixed to one word (:func:`shard_word6`)."""
    saddr = np.asarray(saddr)
    if saddr.ndim == 2:
        saddr = shard_word6(saddr)
    h = (saddr.astype(np.uint64) * np.uint64(2654435761)) >> np.uint64(16)
    return (h % np.uint64(n_shards)).astype(np.uint32)


def shard_word6(key) -> np.ndarray:
    """One u32 of a ``[n, 4]`` key for the shard hash (mirrors
    ``fsx_shard_word6`` in the daemon): every word multiplied by its
    own odd constant, so that no plain XOR fold of the address decides
    the shard."""
    k = np.asarray(key, np.uint32)
    with np.errstate(over="ignore"):
        return (k[:, 0] * np.uint32(0xC2B2AE3D)
                ^ k[:, 1] * np.uint32(0x85EBCA77)
                ^ k[:, 2] * np.uint32(0x9E3779B1) ^ k[:, 3])


#: One verdict-ring entry (engine -> daemon): newly blacklisted source.
VERDICT_RECORD_DTYPE = np.dtype(
    [
        ("saddr", "<u4"),      # folded source address
        ("_pad", "<u4"),
        ("until_ns", "<u8"),   # blacklist expiry, kernel clock ns
    ]
)
VERDICT_RECORD_SIZE = VERDICT_RECORD_DTYPE.itemsize  # 16

#: The verdict-ring entry of a 128-bit-keyed deployment: the full
#: source (wire words, as ``FLOW_RECORD6_DTYPE``), padded to 32 B.
VERDICT_RECORD6_DTYPE = np.dtype(
    [
        ("saddr6", "<u4", (4,)),  # full source address, wire words
        ("until_ns", "<u8"),      # blacklist expiry, kernel clock ns
        ("_pad", "<u8"),
    ]
)
VERDICT_RECORD6_SIZE = VERDICT_RECORD6_DTYPE.itemsize  # 32


# ---------------------------------------------------------------------------
# Verdicts
# ---------------------------------------------------------------------------


class Verdict(enum.IntEnum):
    """Why a packet/flow was passed or dropped.

    Successor of the reference's implicit XDP_PASS/XDP_DROP split
    (``fsx_kern.c:210-214,335,346``) with the drop *cause* made explicit
    so stats can attribute drops (the reference could not).
    """

    PASS = 0
    DROP_BLACKLIST = 1   # source already blacklisted (fsx_kern.c:189-216)
    DROP_RATE = 2        # rate limiter threshold exceeded (fsx_kern.c:308-312)
    DROP_ML = 3          # classifier scored the flow malicious


# ---------------------------------------------------------------------------
# Device-side state (JAX pytrees)
# ---------------------------------------------------------------------------


class TableCol(enum.IntEnum):
    """Column index of each per-flow f32 quantity inside
    ``IpTableState.state`` (one ``[capacity, NUM_TABLE_COLS]`` matrix —
    see the class docstring for why a matrix beats 12 separate arrays
    on TPU)."""

    LAST_SEEN = 0      # f32 s; drives stale-slot reclamation (LRU analog)
    WIN_START = 1      # f32 s; current fixed/sliding window start
    WIN_PPS = 2        # f32; packets in current window
    WIN_BPS = 3        # f32; bytes in current window
    PREV_PPS = 4       # f32; previous window packets (sliding)
    PREV_BPS = 5       # f32; previous window bytes (sliding)
    TOKENS = 6         # f32; token-bucket level (packets)
    TOK_TS = 7         # f32 s; last token refill time
    TOK_BYTES = 8      # f32; byte-bucket level (bandwidth dimension)
    REC_SEEN = 9       # f32; records seen (young-flow ML vote age)
    ML_VOTES = 10      # f32; malicious-scored mature records
    BLOCKED_UNTIL = 11  # f32 s; 0 = not blacklisted (fsx_kern.c:193-204)


NUM_TABLE_COLS = len(TableCol)


class IpTableState(NamedTuple):
    """Per-IP state table resident on device, ``[capacity]`` rows.

    Successor of the reference's three LRU hash maps (``fsx_kern.c:64-94``:
    ``ip_stats_map``, ``blacklist_v4``, ``blacklist_v6``) merged into one
    open-addressing table so a single gather serves the blacklist check,
    the limiter update, and the verdict writeback.  Rows are sharded
    across the device mesh by slot index (= by IP hash).

    The twelve per-flow f32 quantities live in ONE ``[capacity, 12]``
    matrix (``state``, columns named by :class:`TableCol`) rather than
    twelve separate arrays: the hot path touches a flow's row with a
    single 48 B-contiguous gather and a single scatter — one HBM
    transaction each way instead of twelve scattered ones, which is the
    difference between latency-bound and bandwidth-shaped table access
    on TPU.  Named column views are exposed as read-only properties so
    reporting/tests keep field-style access.  On the device a view is a
    table-wide COPY (``state[:, c]`` is a strided slice into a fresh
    ``[capacity]`` array: 256 MB and 3.35 ms at 2^26 rows on a v5e,
    whatever the batch holds), so a step never takes one: it reads
    ``state[slot]`` or ``state[slot, c]`` by gather and writes by
    scatter (``tests/test_fused.py::TestStepNeverTakesATableColumn``,
    ``tests/test_chip_compile.py``).

    All times are float32 seconds on a process-relative clock; counters
    are float32 (exactly representable well past any 1-second window's
    packet count).
    """

    #: [capacity] uint32, or [capacity, 4] for 128-bit keys
    #: (``TableConfig.key_bits``: one 16 B row a slot, so one gather
    #: index fetches a whole key); 0 (all words) = empty slot sentinel
    key: jnp.ndarray
    state: jnp.ndarray  # [capacity, NUM_TABLE_COLS] f32 (TableCol columns)

    @property
    def capacity(self) -> int:
        return self.key.shape[0]

    # -- read-only column views: reporting and tests ONLY.  Each is a
    #    table-wide copy on the device; the step gathers from the
    #    matrix instead (class docstring) --------------------------------
    def _col(self, c: "TableCol") -> jnp.ndarray:
        return self.state[..., int(c)]

    @property
    def last_seen(self):
        return self._col(TableCol.LAST_SEEN)

    @property
    def win_start(self):
        return self._col(TableCol.WIN_START)

    @property
    def win_pps(self):
        return self._col(TableCol.WIN_PPS)

    @property
    def win_bps(self):
        return self._col(TableCol.WIN_BPS)

    @property
    def prev_pps(self):
        return self._col(TableCol.PREV_PPS)

    @property
    def prev_bps(self):
        return self._col(TableCol.PREV_BPS)

    @property
    def tokens(self):
        return self._col(TableCol.TOKENS)

    @property
    def tok_ts(self):
        return self._col(TableCol.TOK_TS)

    @property
    def tok_bytes(self):
        return self._col(TableCol.TOK_BYTES)

    @property
    def rec_seen(self):
        return self._col(TableCol.REC_SEEN)

    @property
    def ml_votes(self):
        return self._col(TableCol.ML_VOTES)

    @property
    def blocked_until(self):
        return self._col(TableCol.BLOCKED_UNTIL)

    def with_columns(self, **cols: jnp.ndarray) -> "IpTableState":
        """New table with named columns replaced wholesale (tests /
        state surgery; the hot path never uses this)."""
        state = self.state
        for name, v in cols.items():
            state = state.at[:, int(TableCol[name.upper()])].set(v)
        return self._replace(state=state)


#: Legacy per-column field names, in TableCol order — the checkpoint
#: format (one array per column) predates the matrix layout and stays
#: column-per-key so old snapshots load unchanged.
TABLE_COLUMN_NAMES = tuple(c.name.lower() for c in TableCol)


def make_table(capacity: int, key_bits: int = 32) -> IpTableState:
    """Fresh, empty state table with ``capacity`` slots (power of two)
    and keys of ``key_bits`` (32 or 128)."""
    if capacity & (capacity - 1):
        raise ValueError(f"capacity must be a power of two, got {capacity}")
    import jax.numpy as jnp

    kw = key_words(key_bits)
    return IpTableState(
        key=jnp.zeros((capacity,) if kw == 1 else (capacity, kw),
                      jnp.uint32),
        state=jnp.zeros((capacity, NUM_TABLE_COLS), jnp.float32),
    )


class GlobalStats(NamedTuple):
    """Global counters (successor of ``struct stats``, ``fsx_struct.h:11-15``).

    The reference bumps ``allowed``/``dropped`` with racy plain increments
    (``fsx_kern.c:210,332,342``); here updates are functional reductions,
    race-free by construction, and drop causes are attributed.

    Each counter is a ``[2]`` uint32 ``(lo, hi)`` pair updated with
    :func:`u64_add` — a 64-bit count without ``jax_enable_x64`` (int32
    would wrap after ~3.5 minutes at the 10 Mpps design rate; the
    kernel-side ``struct fsx_stats`` is u64 for the same reason).
    Read with :func:`stat_value`.
    """

    allowed: jnp.ndarray            # [2] uint32 (lo, hi)
    dropped_blacklist: jnp.ndarray  # [2] uint32
    dropped_rate: jnp.ndarray       # [2] uint32
    dropped_ml: jnp.ndarray         # [2] uint32
    batches: jnp.ndarray            # [2] uint32
    #: Rows the sharded step's owners ran the flow core on, summed over
    #: the mesh, once a non-empty batch: each owner takes the smallest
    #: of its static row counts that holds the flows it received
    #: (:func:`flowsentryx_tpu.parallel.step.owner_flow_step`).  Over
    #: ``batches × n × n·C`` it is the share of the worst-case owner
    #: buffer the core ran on.  A pure donated passthrough on one device.
    owner_rows: jnp.ndarray         # [2] uint32
    #: Valid records whose 128-bit source lies outside
    #: ``::ffff:0:0/96`` (an IPv6 source), and flows newly blocked with
    #: such a source.  Counted where the table keys by 128 bits
    #: (``TableConfig.key_bits``); a pure donated passthrough at 32, so
    #: the 32-bit step keeps the graph it had.
    v6_records: jnp.ndarray         # [2] uint32
    v6_blocks: jnp.ndarray          # [2] uint32
    #: Idle flows freed by the in-step aging epoch
    #: (:func:`flowsentryx_tpu.ops.fused.evict_idle_epoch`;
    #: ``TableConfig.evict_ttl_s``), whichever of its two forms the
    #: backend was given.  Stays zero — a pure donated
    #: passthrough — when eviction is disabled, so pre-eviction graphs
    #: and parity baselines are unchanged.
    evicted: jnp.ndarray            # [2] uint32
    #: Non-empty batches whose probe read ``last_seen``: some valid key
    #: had neither a match nor an empty slot among its probes, so
    #: staleness could decide
    #: (:func:`flowsentryx_tpu.ops.hashtable.probe_slots`; under a mesh,
    #: on any shard).  ``stale_reads / batches`` is the share of steps
    #: that pay the second table gather: near 0 while the table has
    #: room, toward 1 as it fills.
    stale_reads: jnp.ndarray        # [2] uint32
    #: Flows of a batch that ended it with no row: no match, empty or
    #: reclaimable slot among their probes, or the slot they chose went
    #: to another new flow of the same batch.  Such a flow carries no
    #: limiter state (fail-open), is classified record by record and
    #: votes within its batch (:func:`flowsentryx_tpu.ops.fused._flow_core`).
    #: ``untracked`` over the flows served is what the table's load
    #: costs: about ``load ** probes`` of the new flows.  Counted where
    #: the aging sweep is compiled in (``TableConfig.evict_ttl_s`` > 0)
    #: and, like ``evicted``, a pure donated passthrough elsewhere: the
    #: two small operations it adds to a step were enough to tip the
    #: benchmark's ``c5-l34-1m.saturate`` (no aging, two ring shards
    #: whose block-and-return waves drift apart) into running one
    #: shard dry in 5 untraced runs of 11 against 1 of 11 without them
    #: (PERF.md section 6, PR 39), so a table with no aging keeps the
    #: graph it had.
    untracked: jnp.ndarray          # [2] uint32

    @property
    def dropped(self) -> int:
        """Total drops (host-side read)."""
        return (
            stat_value(self.dropped_blacklist)
            + stat_value(self.dropped_rate)
            + stat_value(self.dropped_ml)
        )

    def to_dict(self) -> dict:
        d = {f: stat_value(getattr(self, f)) for f in self._fields}
        d["dropped"] = self.dropped
        return d


def u64_add(field: jnp.ndarray, inc: jnp.ndarray) -> jnp.ndarray:
    """Add a non-negative scalar to a ``[2]`` uint32 (lo, hi) counter,
    with carry — jit-safe 64-bit accumulation on a 32-bit-only backend."""
    import jax.numpy as jnp

    inc = inc.astype(jnp.uint32)
    lo = field[0] + inc
    carry = (lo < field[0]).astype(jnp.uint32)
    return jnp.stack([lo, field[1] + carry])


def stat_value(field: jnp.ndarray) -> int:
    """Host-side read of a (lo, hi) counter as a python int."""
    f = np.asarray(field)
    return int(f[0]) + (int(f[1]) << 32)


def make_stats() -> GlobalStats:
    # Distinct arrays per field — see make_table's donation note.
    import jax.numpy as jnp

    return GlobalStats(*(jnp.zeros((2,), jnp.uint32)
                         for _ in GlobalStats._fields))


class FeatureBatch(NamedTuple):
    """One micro-batch of flow records, decoded to device-friendly SoA.

    Produced by the host batcher from raw ``FLOW_RECORD_DTYPE`` bytes.
    ``valid`` masks ragged tails (batches are padded to a static size so
    every shape under ``jit`` stays static).
    """

    key: jnp.ndarray      # [B] uint32 source address / fold, or [B, 4]
    feat: jnp.ndarray     # [B, 8] f32
    pkt_len: jnp.ndarray  # [B] f32 bytes
    ts: jnp.ndarray       # [B] f32 seconds (process-relative)
    valid: jnp.ndarray    # [B] bool


#: Number of 32-bit words per flow record (48 B / 4).
RECORD_WORDS = FLOW_RECORD_SIZE // 4  # 12


def encode_raw(buf: np.ndarray, batch_size: int, t0_ns: int) -> np.ndarray:
    """Pack ring records into the device wire format: ``[B+1, 12]`` uint32.

    Rows ``0..B-1`` are the raw 48-byte records reinterpreted as 12
    little-endian u32 words (zero-copy view + one memcpy); row ``B`` is a
    metadata row ``(n_valid, t0_lo, t0_hi, 0...)``.  All field extraction
    and integer→float casts then run *on device* (:func:`decode_raw`
    inside the jitted step) — at 10 Mpps the host's only per-packet cost
    is the memcpy, and the batch crosses PCIe as ONE contiguous buffer.

    The production engine writes ring records directly into the first
    ``B`` rows of a preallocated ``[B+1, 12]`` array and only updates the
    metadata row per batch, skipping even this memcpy.
    """
    n = min(len(buf), batch_size)
    out = np.zeros((batch_size + 1, RECORD_WORDS), np.uint32)
    if n:
        out[:n] = buf[:n].view(np.uint32).reshape(n, RECORD_WORDS)
    out[batch_size, 0] = n
    out[batch_size, 1] = t0_ns & 0xFFFFFFFF
    out[batch_size, 2] = (t0_ns >> 32) & 0xFFFFFFFF
    return out


def decode_raw(raw) -> "FeatureBatch":
    """Device-side decode of :func:`encode_raw`'s wire format (jit-inlined).

    Timestamps: ``ts_ns`` is u64 (boot-relative, ``bpf_ktime_get_ns``)
    split across words 0 (lo) and 1 (hi).  There is no u64 on a 32-bit
    jit backend, so the relative-seconds conversion runs in f32 as
    ``(hi - t0_hi)·2^32·1e-9 + (lo·1e-9 - t0_lo·1e-9)``: each term is a
    few seconds in magnitude, giving ~0.5 µs worst-case error — three
    orders of magnitude below the 1 s limiter windows.
    """
    import jax.numpy as jnp

    words = raw[:-1]
    meta = raw[-1]
    n = meta[0].astype(jnp.int32)
    t0_lo = meta[1].astype(jnp.float32)
    t0_hi = meta[2]
    lo = words[:, 0]
    hi = words[:, 1]
    dhi = (hi - t0_hi).astype(jnp.int32).astype(jnp.float32)
    ts = dhi * np.float32(4.294967296) + (
        lo.astype(jnp.float32) * np.float32(1e-9) - t0_lo * np.float32(1e-9)
    )
    w3 = words[:, 3]
    return FeatureBatch(
        key=words[:, 2],
        feat=words[:, 4:12].astype(jnp.float32),
        pkt_len=(w3 & np.uint32(RANGE_PKT_LEN_MAX)).astype(jnp.float32),
        ts=ts,
        valid=jnp.arange(words.shape[0]) < n,
    )


def raw_proto_flags(raw) -> tuple:
    """(ip_proto, flags) u32 vectors from the wire format, for consumers
    that need the L4 breakdown (stats attribution, per-proto policy)."""
    w3 = raw[:-1, 3]
    return ((w3 >> np.uint32(16)) & np.uint32(RANGE_PROTO_MAX),
            w3 >> np.uint32(24))


# ---------------------------------------------------------------------------
# Compact wire format: 16 B/record host→device (the bandwidth-critical hop)
# ---------------------------------------------------------------------------
#
# The 48 B flow record is the *kernel→user* contract (full-fidelity u32
# features, u64 timestamps).  The *host→device* hop is the bandwidth-
# critical one — at 10 Mpps the 48 B record needs 480 MB/s of link — and
# the classifier immediately requantizes features to 8 bits anyway
# (models/logreg.py ``_quantize_u8``), so shipping 32-bit features
# across PCIe buys nothing.  The compact format quantizes in the host
# batcher (or, eventually, in the kernel: both encoders are integer-only
# shift/mask ops, eBPF-expressible) and decodes on device inside the
# jitted step:
#
#   word 0: saddr (folded source, as in the 48 B record)
#   word 1: feat_q[0..3]   u8 each
#   word 2: feat_q[4..7]   u8 each
#   word 3: bits 0-10   pkt_len in 8-byte units, round-to-nearest,
#                       saturated (covers jumbo frames; ≤0.4 % error
#                       on the bps limiter)
#           bits 11-15  FLAG_* bits
#           bits 16-31  ts delta from the batch base, µs, saturated
#                       (batches flush every ``deadline_us`` ≤ 200 µs
#                       under BatchConfig defaults — far inside the
#                       65 ms field range)
#
# Metadata row: ``(n_valid, base_rel_us_lo, base_rel_us_hi, 0)`` where
# ``base_rel_us`` is the batch base timestamp relative to the engine
# epoch ``t0_ns``, in µs — split across two u32s and recombined in f32
# on device exactly like :func:`decode_raw`'s u64 trick.
#
# Feature quantization is per-artifact, chosen by the model's domain:
#
# * ``model`` mode (preferred): the wire carries the classifier's OWN
#   input quantization — ``q = clip(round(t(feat)/in_scale) + in_zp,
#   0, 255)`` where ``t`` is the artifact's feature transform (identity
#   or log1p).  The on-device dequant inverts ``t``, and the
#   classifier's input observer then reproduces the same ``q``.  For
#   identity-transform artifacts (the reference's golden model) this is
#   exact small-integer f32 arithmetic, so scores and verdicts are
#   BIT-IDENTICAL to the 48 B path.  For ``log1p`` artifacts, host
#   ``np.log1p`` vs device ``expm1∘log1p`` can round differently at
#   quant-step boundaries, so scores may differ by ±1 output quant step
#   (~1/256) on boundary-straddling flows — tested to ≥99 % exact-score
#   agreement in tests/test_fused.py.  Kernel-side emission needs one
#   fixed-point reciprocal multiply per feature (integer-only).
# * ``minifloat`` mode (model-independent): u8 "e5m3" — values 0-8
#   verbatim, above that a bit-length exponent plus the 3 bits under
#   the MSB, round-to-nearest — covering the full u32 range with
#   ≤6.25 % relative error.  Integer-only (msb + shifts), so the
#   kernel feature extractor can emit it without floats, and any model
#   artifact can consume it.

COMPACT_RECORD_WORDS = 4
COMPACT_RECORD_SIZE = COMPACT_RECORD_WORDS * 4  # 16

#: The sealed row of a 128-bit-keyed deployment (``compact28``): the
#: source's 4 wire words, then compact16's words 1-3 unchanged.  The
#: metadata row is compact16's.  Its own ``WIRE_ID_COMPACT28``, so a
#: sealed batch states its layout.
COMPACT28_RECORD_WORDS = 7

WIRE_RAW48 = "raw48"
WIRE_COMPACT16 = "compact16"
WIRE_COMPACT28 = "compact28"
#: The quantised wires: a µs delta from the batch base in the last word
COMPACT_WIRES = (WIRE_COMPACT16, WIRE_COMPACT28)


def wire_words(wire: str) -> int:
    """u32 words in one row of ``wire``."""
    return {WIRE_RAW48: RECORD_WORDS, WIRE_COMPACT16: COMPACT_RECORD_WORDS,
            WIRE_COMPACT28: COMPACT28_RECORD_WORDS}[wire]

# -- declared field-width / value-range constants ---------------------------
#
# ONE source of truth for the magic widths of the wire formats: the
# encode/decode/quantize paths below mask and clip with these names, and
# the ``fsx ranges`` prover (flowsentryx_tpu/ranges/seeds.py) seeds its
# input intervals from the SAME names — so what the prover assumes about
# a field is, by construction, what the runtime enforces.

#: u8 quantized-feature ceiling (both wire quantizers clip here).
RANGE_FEAT_Q8_MAX = 255
#: u16 wire-length field of the 48 B record (``pkt_len``).
RANGE_PKT_LEN_MAX = 0xFFFF
#: u8 IPPROTO field packed into raw w3 bits 16-23.
RANGE_PROTO_MAX = 0xFF
#: the 5 FLAG_* bits of compact w3 (bits 11-15).
RANGE_FLAGS_MAX = 0x1F
#: 11-bit pkt_len/8 field of compact w3 (bits 0-10; covers jumbo frames).
RANGE_LEN8_MAX = 0x7FF
#: 16-bit compact ts delta field (µs from the batch base; bits 16-31).
RANGE_DT_US_MAX = 0xFFFF
#: Declared deployment-horizon bound (seconds) on boot-relative ns
#: stamps (``bpf_ktime_get_ns`` / the engine epoch ``t0_ns``): ~48.5
#: days.  Not enforced per record — it is the range registry's declared
#: assumption about how long one serving process lives, bounding the
#: u64 timestamp HI words the split-word decodes see.  A redeploy past
#: the horizon restarts the epoch.
RANGE_DEPLOY_HORIZON_S = 1 << 22
#: Declared cross-host epoch-skew bound (seconds) on REBASED verdict
#: wires (cluster/transport.py): after tx-epoch -> rx-epoch rebase, the
#: wire's device-clock `now` word must land within this many seconds of
#: the receiver's own clock.  The honest contributors — NTP wall-clock
#: skew (ms), network transit (ms), gossip-tick batching (ms) — sum to
#: well under a second, so 60 s only ever trips on a LYING epoch: a
#: peer re-publishing a pre-reboot t0_wall, a corrupted stamp, a host
#: with no clock discipline at all.  Such wires are dropped and counted
#: (``epoch_skew_dropped``), never applied: a default block TTL is 10 s,
#: so a verdict 60 s out of frame is already expired — applying it
#: under a broken rebase would block innocent sources at wrong times.
RANGE_EPOCH_SKEW_S = 60


def quantize_feat_model(
    feat: np.ndarray, in_scale: float, in_zp: int, log1p: bool
) -> np.ndarray:
    """u32 → u8 with the classifier's own input quantizer (host,
    vectorized).  Round-half-to-even matches torch observer semantics
    (models/logreg.py ``_quantize_u8``)."""
    x = feat.astype(np.float32)
    if log1p:
        x = np.log1p(x)
    q = np.rint(x / np.float32(in_scale)) + in_zp
    return np.clip(q, 0, RANGE_FEAT_Q8_MAX).astype(np.uint32)


def _minifloat_ref(feat: np.ndarray) -> np.ndarray:
    """Reference e5m3 encoder (the spec; builds the hot-path LUT and
    anchors the equivalence tests): values ≤ 8 verbatim; above,
    ``q = 8·e + m̂`` with ``feat ≈ (8 + m̂)·2^(e-1)``."""
    f = feat.astype(np.uint64)
    bl = np.zeros(f.shape, np.int64)
    tmp = f.copy()
    for s in (32, 16, 8, 4, 2, 1):  # branch-free bit-length
        big = tmp >= (np.uint64(1) << np.uint64(s))
        bl = np.where(big, bl + s, bl)
        tmp = np.where(big, tmp >> np.uint64(s), tmp)
    bl += (tmp > 0)  # the residual top bit
    e = np.maximum(bl - 4, 0).astype(np.uint64)  # f in [8·2^e, 16·2^e)
    # rounded leading-4-bit mantissa in [8, 16]; 16 carries into e+1
    # (shift kept in-range for e=0: where() evaluates both branches)
    safe = np.maximum(e, np.uint64(1)) - np.uint64(1)
    r = np.where(e > 0, (f >> safe) + np.uint64(1), f * 2) >> 1
    e = np.where(r == 16, e + 1, e)
    r = np.where(r == 16, np.uint64(8), r)
    q = np.where(bl <= 3, f, (e + np.uint64(1)) * 8 + (r - 8))
    return np.minimum(q, RANGE_FEAT_Q8_MAX).astype(np.uint32)


#: Concatenated encode tables: ``[0, 2^16)`` maps f directly,
#: ``[2^16, 2^16 + 2^20)`` maps ``f >> 12`` for f ≥ 2^16 — valid
#: because the encoder's rounding bit sits at position e-1 ≥ 12 there,
#: so the low 12 bits can never influence the result.  Built lazily
#: (once per process) from the reference encoder, so equivalence is by
#: construction.
_MINIFLOAT_LUT: np.ndarray | None = None


def _minifloat_lut() -> np.ndarray:
    global _MINIFLOAT_LUT
    if _MINIFLOAT_LUT is None:
        lo = _minifloat_ref(np.arange(1 << 16, dtype=np.uint64))
        hi = _minifloat_ref(np.arange(1 << 20, dtype=np.uint64) << 12)
        _MINIFLOAT_LUT = np.concatenate([lo, hi]).astype(np.uint8)
    return _MINIFLOAT_LUT


def _minifloat_q8(f: np.ndarray) -> np.ndarray:
    """LUT encode → u8 (the seal hot path; explicit u32 scalars keep
    the index math in 4-byte lanes on the common u32 feature input)."""
    if f.dtype == np.uint32:
        idx = np.where(f < np.uint32(1 << 16), f,
                       (f >> np.uint32(12)) + np.uint32(1 << 16))
    else:
        # The LUT covers the u32 domain.  Lanes >= 2^32 (including
        # signed negatives wrapped by the cast) must still encode
        # exactly as the reference / C fsx_minifloat8 (u64) do — the
        # ramp to the 255 clamp is gradual above 2^32, not a constant —
        # so route those (cold, u64-counter-mirror only) lanes through
        # the reference encoder instead of indexing out of bounds.
        f = f.astype(np.uint64)
        big = f >= np.uint64(1 << 32)
        safe = np.minimum(f, np.uint64((1 << 32) - 1))
        idx = np.where(safe < np.uint64(1 << 16), safe,
                       (safe >> np.uint64(12)) + np.uint64(1 << 16))
        out = _minifloat_lut()[idx]
        if big.any():
            out = out.copy()
            out[big] = _minifloat_ref(f[big]).astype(np.uint8)
        return out
    return _minifloat_lut()[idx]


def quantize_feat_minifloat(feat: np.ndarray) -> np.ndarray:
    """u32 → u8 e5m3, round-to-nearest (see :func:`_minifloat_ref` for
    the spec).  One-gather LUT hot path: this runs per record×feature
    in every compact16 seal, and at Mpps rates the ~25 full-array
    passes of the branch-free reference were the single largest host
    cost in the ingest stage."""
    return _minifloat_q8(np.asarray(feat)).astype(np.uint32)


def _dequant_feat_model(q, in_scale: float, in_zp: int, log1p: bool):
    import jax.numpy as jnp

    x = (q.astype(jnp.float32) - np.float32(in_zp)) * np.float32(in_scale)
    if log1p:
        x = jnp.expm1(x)
    return x


def _dequant_feat_minifloat(q):
    import jax.numpy as jnp

    qf = q.astype(jnp.int32)
    e = qf // 8 - 1
    m = qf % 8
    big = (np.float32(8.0) + m.astype(jnp.float32)) * jnp.exp2(
        e.astype(jnp.float32)
    )
    return jnp.where(qf < 8, qf.astype(jnp.float32), big)


def model_quant_args(params) -> dict:
    """Wire-quantizer kwargs for ``model`` mode, read off a params
    pytree that carries ``in_scale``/``in_zp`` (and optionally
    ``log1p``) — e.g. :class:`flowsentryx_tpu.models.logreg.LogRegParams`."""
    return dict(
        feat_mode="model",
        in_scale=float(np.asarray(params.in_scale)),
        in_zp=int(np.asarray(params.in_zp)),
        log1p=bool(int(np.asarray(getattr(params, "log1p", 0)))),
    )


def wire_quant_for(params) -> dict:
    """Best wire-quantizer for an arbitrary params pytree: the model's
    own input observer when the artifact exposes one (bit-exact), else
    the model-independent minifloat."""
    if hasattr(params, "in_scale"):
        return model_quant_args(params)
    return dict(feat_mode="minifloat")


def compact_pack(
    rec: np.ndarray,
    base_ns: int,
    *,
    feat_mode: str = "minifloat",
    in_scale: float = 1.0,
    in_zp: int = 0,
    log1p: bool = False,
) -> np.ndarray:
    """Vectorized pack of flow records → ``[n, 4]`` compact words
    (shared by :func:`encode_compact` and the incremental batcher);
    ``FLOW_RECORD6_DTYPE`` records → ``[n, 7]`` compact28 words, the
    four key words first."""
    n = len(rec)
    kw = 4 if "saddr6" in rec.dtype.names else 1
    out = np.empty((n, kw + 3), np.uint32)
    if feat_mode == "model":
        q8 = quantize_feat_model(
            rec["feat"], in_scale, in_zp, log1p).astype(np.uint8)
    elif feat_mode == "minifloat":
        q8 = _minifloat_q8(rec["feat"])
    else:
        raise ValueError(f"unknown feat_mode {feat_mode!r}")
    if kw == 1:
        out[:, 0] = rec["saddr"]
    else:
        out[:, :kw] = rec["saddr6"]
    # [n, 8] u8 reinterpreted as [n, 2] u32 IS the little-endian byte
    # pack q0|q1<<8|…  (the shm seam already requires x86-TSO, so LE is
    # given) — one view instead of six shift/or passes per seal.
    qw = np.ascontiguousarray(q8).view(np.uint32)
    out[:, kw] = qw[:, 0]
    out[:, kw + 1] = qw[:, 1]
    len8 = np.minimum((rec["pkt_len"].astype(np.uint32) + 4) >> 3,
                      RANGE_LEN8_MAX)
    # records can arrive slightly out of order; clamp below base to 0
    dt = rec["ts_ns"].astype(np.int64) - np.int64(base_ns)
    dt_us = np.clip(dt // 1000, 0, RANGE_DT_US_MAX).astype(np.uint32)
    out[:, kw + 2] = (len8
                      | (rec["flags"].astype(np.uint32) & RANGE_FLAGS_MAX)
                      << 11
                      | dt_us << 16)
    return out


def encode_compact(
    buf: np.ndarray,
    batch_size: int,
    t0_ns: int,
    *,
    feat_mode: str = "minifloat",
    in_scale: float = 1.0,
    in_zp: int = 0,
    log1p: bool = False,
) -> np.ndarray:
    """Pack ring records into the compact wire format: ``[B+1, 4]`` u32.

    Same contract as :func:`encode_raw` (``t0_ns`` = engine epoch;
    decoded ``ts`` is seconds relative to it) at a third of the bytes.
    Pass ``**model_quant_args(params)`` for bit-exact ``model`` mode.
    """
    n = min(len(buf), batch_size)
    out = np.zeros((batch_size + 1, COMPACT_RECORD_WORDS), np.uint32)
    base_ns = int(t0_ns)
    if n:
        rec = buf[:n]
        base_ns = int(rec["ts_ns"].min())
        span_ns = int(rec["ts_ns"].max()) - base_ns
        if span_ns >= 65_536_000:  # dt_us 65535 is still exact; clip starts here
            # The MicroBatcher seals early at this boundary; direct
            # callers get a loud signal instead of silent saturation
            # (clipped deltas would distort on-device IAT/rate math).
            import warnings

            warnings.warn(
                f"encode_compact: record span {span_ns / 1e6:.1f} ms "
                "exceeds the 65.535 ms compact ts range; deltas beyond "
                "it saturate (use the MicroBatcher or split the batch)",
                stacklevel=2,
            )
        out[:n] = compact_pack(rec, base_ns, feat_mode=feat_mode,
                               in_scale=in_scale, in_zp=in_zp, log1p=log1p)
    base_rel_us = max(0, (base_ns - int(t0_ns))) // 1000
    out[batch_size, 0] = n
    out[batch_size, 1] = base_rel_us & 0xFFFFFFFF
    out[batch_size, 2] = (base_rel_us >> 32) & 0xFFFFFFFF
    return out


def decode_compact(
    raw,
    *,
    feat_mode: str = "minifloat",
    in_scale: float = 1.0,
    in_zp: int = 0,
    log1p: bool = False,
) -> "FeatureBatch":
    """Device-side decode of :func:`encode_compact` (jit-inlined), and
    of the compact28 wire: a row of ``kw + 3`` words holds a ``kw``-word
    key (``[B]`` for compact16, ``[B, 4]`` for compact28).

    ``base_rel_us`` splits across two u32 words; the f32 recombination
    ``hi·2^32·1e-6 + lo·1e-6 + dt·1e-6`` keeps every term small enough
    that worst-case error (~0.3 ms at hours of uptime) stays three
    orders of magnitude below the 1 s limiter windows.
    """
    import jax.numpy as jnp

    words = raw[:-1]
    meta = raw[-1]
    n = meta[0].astype(jnp.int32)
    base = (meta[2].astype(jnp.float32) * np.float32(4294.967296)
            + meta[1].astype(jnp.float32) * np.float32(1e-6))
    kw = raw.shape[-1] - 3
    w1, w2, w3 = words[:, kw], words[:, kw + 1], words[:, kw + 2]
    q8 = RANGE_FEAT_Q8_MAX  # the byte lanes carry u8 quantized features
    q = jnp.stack(
        [
            w1 & q8, (w1 >> 8) & q8, (w1 >> 16) & q8, w1 >> 24,
            w2 & q8, (w2 >> 8) & q8, (w2 >> 16) & q8, w2 >> 24,
        ],
        axis=1,
    )
    if feat_mode == "model":
        feat = _dequant_feat_model(q, in_scale, in_zp, log1p)
    elif feat_mode == "minifloat":
        feat = _dequant_feat_minifloat(q)
    else:
        raise ValueError(f"unknown feat_mode {feat_mode!r}")
    return FeatureBatch(
        key=words[:, 0] if kw == 1 else words[:, :kw],
        feat=feat,
        pkt_len=((w3 & np.uint32(RANGE_LEN8_MAX))
                 << np.uint32(3)).astype(jnp.float32),
        ts=base + (w3 >> np.uint32(16)).astype(jnp.float32) * np.float32(1e-6),
        valid=jnp.arange(words.shape[0]) < n,
    )


def compact_flags(raw):
    """FLAG_* bits vector from the compact wire format."""
    return (raw[:-1, -1] >> np.uint32(11)) & np.uint32(RANGE_FLAGS_MAX)


#: One KERNEL-emitted compact record (struct fsx_compact_record): the
#: same four words as a compact wire row, except word 3's ts field is
#: the kernel's (ktime_ns/1000) & 0xFFFF — a wrapped µs stamp the host
#: unwraps (:func:`unwrap_kernel_ts16`) and rebases per batch.
COMPACT_RECORD_DTYPE = np.dtype(
    [("w0", "<u4"), ("w1", "<u4"), ("w2", "<u4"), ("w3", "<u4")]
)
assert COMPACT_RECORD_DTYPE.itemsize == COMPACT_RECORD_SIZE


def unwrap_kernel_ts16(w3: np.ndarray, now_ns: int) -> np.ndarray:
    """Recover absolute kernel-clock timestamps (ns, u64) from the
    wrapped 16-bit µs stamps of kernel-emitted compact records.

    Valid while records are drained within 65.5 ms of emission (ring
    sizing + drain cadence enforce this; a staler record lands up to
    n·65.5 ms late — bounded skew, never corruption)."""
    now_us = np.uint64(now_ns // 1000)
    ts16 = (w3 >> np.uint32(16)).astype(np.uint64)
    return (now_us
            - ((now_us - ts16) & np.uint64(RANGE_DT_US_MAX))
            ) * np.uint64(1000)


def decode_records(buf: np.ndarray, batch_size: int, t0_ns: int) -> FeatureBatch:
    """Decode ``FLOW_RECORD_DTYPE`` entries into a padded :class:`FeatureBatch`.

    ``buf`` may hold fewer than ``batch_size`` records; the tail is
    zero-padded and masked via ``valid``.

    ``t0_ns`` is mandatory and must be a *recent* kernel timestamp
    (``bpf_ktime_get_ns`` is boot-relative): timestamps are stored as
    float32 seconds relative to ``t0_ns``, and float32 spacing at 1e6 s
    magnitude is ~0.06 s — far too coarse for 1 s limiter windows.
    Records stamped slightly before ``t0_ns`` yield small negative
    times (signed arithmetic; no uint64 wrap).
    """
    import jax.numpy as jnp

    n = min(len(buf), batch_size)
    key = np.zeros((batch_size,), np.uint32)
    feat = np.zeros((batch_size, NUM_FEATURES), np.float32)
    pkt_len = np.zeros((batch_size,), np.float32)
    ts = np.zeros((batch_size,), np.float32)
    valid = np.zeros((batch_size,), bool)
    if n:
        rec = buf[:n]
        key[:n] = rec["saddr"]
        feat[:n] = rec["feat"].astype(np.float32)  # u32 wire → f32 model input
        pkt_len[:n] = rec["pkt_len"]
        ts[:n] = (rec["ts_ns"].astype(np.int64) - np.int64(t0_ns)) * 1e-9
        valid[:n] = True
    return FeatureBatch(
        key=jnp.asarray(key), feat=jnp.asarray(feat),
        pkt_len=jnp.asarray(pkt_len), ts=jnp.asarray(ts),
        valid=jnp.asarray(valid),
    )

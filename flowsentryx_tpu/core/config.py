"""Configuration system.

The reference hard-codes all policy as magic constants in the kernel
program — ``blocked_for_time = 10`` s, ``pps_threshold = 1000``,
``bps_threshold = 125000000`` (``src/fsx_kern.c:308-310``) — with a
comment that disagrees with the code (``fsx_kern.c:303-307``), and lists
"config files" as future work (``README.md:70-74,142-145``,
``TODO.md:60-61``).  This module is that promised config system:

* typed, validated dataclasses for every knob,
* JSON round-trip for files / CLI overrides,
* :func:`pack_kernel_config` — serializes the policy subset into the
  fixed binary layout of the kernel's BPF config map (generated as
  ``struct fsx_config`` in ``kern/fsx_schema.h``), replacing the
  reference's compile-time constants with a runtime-updatable map.

Configs are hashable (frozen) so they can be closed over by ``jit``-ed
functions as static arguments.
"""

from __future__ import annotations

import dataclasses
import enum
import json
import struct
import typing
from dataclasses import dataclass, field
from typing import Any


class LimiterKind(enum.Enum):
    """Which rate-limiter algorithm guards a flow.

    The reference implements only FIXED_WINDOW (``fsx_kern.c:243-263``)
    and *specifies* sliding-window and token-bucket
    (``README.md:153-162``); all three are first-class here.
    """

    FIXED_WINDOW = "fixed_window"
    SLIDING_WINDOW = "sliding_window"
    TOKEN_BUCKET = "token_bucket"


@dataclass(frozen=True)
class LimiterConfig:
    """Rate-limiter policy (successor of ``fsx_kern.c:303-312``)."""

    kind: LimiterKind = LimiterKind.FIXED_WINDOW
    pps_threshold: float = 1000.0       # fsx_kern.c:309
    bps_threshold: float = 125_000_000.0  # fsx_kern.c:310 (125 MB/s ≈ 1 Gbit/s)
    window_s: float = 1.0               # fsx_kern.c:243 (1e9 ns window)
    bucket_rate_pps: float = 1000.0     # token refill rate (packets/s)
    bucket_burst: float = 2000.0        # token bucket depth (packets)
    #: Byte dimension of the token bucket (the spec rate-limits
    #: bandwidth as well as packets, README.md:153-162).  Both zero =
    #: byte dimension disabled (packet-count only); defaults mirror the
    #: window limiters' byte threshold.  One zero without the other is
    #: rejected: burst with no refill would permanently block a source
    #: after its first burst, refill with no depth can never admit.
    bucket_rate_bps: float = 125_000_000.0   # byte refill rate (bytes/s)
    bucket_burst_bytes: float = 250_000_000.0  # byte bucket depth
    block_s: float = 10.0               # fsx_kern.c:308 blacklist TTL

    def __post_init__(self) -> None:
        if self.window_s <= 0:
            raise ValueError("window_s must be positive")
        if self.block_s < 0:
            raise ValueError("block_s must be non-negative")
        if min(self.pps_threshold, self.bps_threshold,
               self.bucket_rate_pps, self.bucket_burst,
               self.bucket_rate_bps, self.bucket_burst_bytes) < 0:
            raise ValueError("thresholds must be non-negative")
        if (self.bucket_rate_bps == 0) != (self.bucket_burst_bytes == 0):
            raise ValueError(
                "bucket_rate_bps and bucket_burst_bytes must be both "
                "zero (byte dimension off) or both positive"
            )


#: L4 protocol names accepted in rules (number literals also work).
_PROTO_CODES = {"any": 0, "icmp": 1, "tcp": 6, "udp": 17, "icmpv6": 58}


@dataclass(frozen=True)
class RuleConfig:
    """One stateless-firewall drop rule — the reference's planned
    "basic firewall ... config files ... rules to drop certain packets"
    (``README.md:70-74``), enforced in the kernel data plane before any
    per-IP state is touched.

    ``proto``/``dport`` of 0 (or ``"any"``) are wildcards; at least one
    must be concrete.  Matching precedence per packet: exact
    (proto, dport), then (proto, any-port), then (any-proto, dport).
    """

    proto: str | int = "any"   # "tcp"/"udp"/"icmp"/"icmpv6"/number/"any"
    dport: int = 0             # 0 = any
    action: str = "drop"

    def __post_init__(self) -> None:
        if self.action != "drop":
            raise ValueError(f"unknown rule action {self.action!r}")
        if not 0 <= self.dport <= 65535:
            raise ValueError("dport must be 0..65535")
        if self.proto_code() == 0 and self.dport == 0:
            raise ValueError("a rule needs a concrete proto or dport")

    def proto_code(self) -> int:
        if isinstance(self.proto, int):
            if not 0 <= self.proto <= 255:
                raise ValueError("proto number must be 0..255")
            return self.proto
        try:
            return _PROTO_CODES[self.proto.lower()]
        except KeyError:
            raise ValueError(f"unknown protocol {self.proto!r}") from None

    def key(self) -> int:
        from flowsentryx_tpu.core import schema

        return schema.pack_rule_key(self.proto_code(), self.dport)


@dataclass(frozen=True)
class ModelConfig:
    """Classifier selection + decision policy."""

    name: str = "logreg_int8"
    threshold: float = 0.5              # sigmoid cutoff (model.py:205-208)
    quantized: bool = True
    ml_block_s: float = 10.0            # blacklist TTL for ML-flagged sources
    #: Young-flow vote (found serving the kernel path: a flow's first
    #: records carry no variance/IAT mass and can score malicious, so
    #: without a vote EVERY benign source eventually gets
    #: ML-blacklisted).  A flow's
    #: malicious-scored records count as votes only once the engine has
    #: seen ``vote_k`` records from it (the kernel emits every packet
    #: while a flow is young, fsx_kern.c:163-165, so maturity arrives
    #: within the first k packets); an ML block needs ``vote_m`` votes.
    #: Flows the table cannot track (arbitration loss / full table —
    #: an attacker must not escape detection by filling the table) use
    #: a batch-local form: > vote_k records in the batch with >= vote_m
    #: scored malicious (tracked flows get this burst rule too, so a
    #: dense single-batch flood can't hide behind its youth).  Votes
    #: decay with a ``vote_decay_s`` half-life and reset when a block
    #: fires — an isolated borderline mis-score hours ago must not
    #: leave a benign flow permanently one record from a block.
    #: ``vote_k=0, vote_m=1`` restores the immediate pre-vote behavior.
    vote_k: int = 4
    vote_m: int = 2
    vote_decay_s: float = 60.0  # vote half-life; 0 = no decay

    def __post_init__(self) -> None:
        if self.vote_k < 0:
            raise ValueError("vote_k must be >= 0")
        if self.vote_m < 1:
            raise ValueError("vote_m must be >= 1")
        if self.vote_decay_s < 0:
            raise ValueError("vote_decay_s must be >= 0")


@dataclass(frozen=True)
class TableConfig:
    """Per-IP state table sizing.

    ``capacity`` supersedes the reference's ``MAX_TRACK_IPS = 100000``
    LRU cap (``fsx_struct.h:7``); default 2^20 ≈ 1M concurrent source
    IPs (BASELINE config 5).  ``probes`` bounds the open-addressing
    probe sequence (static for XLA).  ``stale_s``: slots idle longer
    than this may be reclaimed on insert — the analog of
    ``BPF_MAP_TYPE_LRU_HASH`` eviction (``fsx_kern.c:66``).
    """

    capacity: int = 1 << 20
    probes: int = 8
    stale_s: float = 30.0
    #: Hash salt mixed into slot probing AND owner routing
    #: (ops/hashtable.hash_u32).  0 = deterministic/unsalted (tests,
    #: reproducible runs); ``fsx serve`` draws a random boot-time salt
    #: so an attacker cannot precompute table-slot collisions or aim
    #: every flow at one owner device (the exposure the unsalted hash
    #: created — the reference's kernel LRU maps have no analog, their
    #: hashing is kernel-internal and already seeded).  Carried in
    #: checkpoints so a restored table's slot layout stays valid, and in
    #: the packed kernel-config blob for config-file deployments that
    #: fix the salt explicitly (see ``KERNEL_CONFIG_FIELDS``).
    salt: int = 0
    #: In-step aging: slots idle longer than ``evict_ttl_s`` (device-
    #: clock seconds since last_seen, still-valid blacklist entries
    #: exempt) are freed IN-GRAPH by a rolling sweep — each batch the
    #: step opens by sweeping one ``capacity/evict_every``-row window,
    #: the window base advancing with the batch counter, so every row
    #: is re-examined once per ``evict_every`` batches
    #: (``ops/fused.evict_idle_epoch``; shard-local on a mesh, no new
    #: collectives or D2H, constant per-batch cost: the window is a
    #: slice of the table where the step is lowered for a TPU, a
    #: gather and a victim-only scatter elsewhere — chosen at
    #: lowering, not here).  0 disables the
    #: sweep entirely: the staged step graphs are then unchanged from
    #: the pre-eviction era (stale-slot reclamation on insert still
    #: works as before), which is what keeps parity baselines
    #: byte-identical.  Distinct from ``stale_s`` (reclaim-on-insert
    #: eligibility): reclamation frees a slot only when a new flow
    #: happens to probe it; eviction bounds table occupancy under
    #: churn whether or not the slot is re-probed.
    evict_ttl_s: float = 0.0
    #: Batches per full sweep cycle: each batch sweeps
    #: ``ceil(capacity / evict_every)`` rows, and a row idle past the
    #: ttl is freed within one cycle of crossing it.  What a window
    #: costs is the backend's (``ops/fused.evict_window``): on XLA:CPU
    #: keep it to hundreds of rows; on a TPU a 2^17-row window is
    #: under a tenth of a millisecond.
    evict_every: int = 64
    #: Chips the rows are split over by the owner hash (the top bits of
    #: the salted key hash, ``parallel/step.py``): each holds
    #: ``capacity / shards`` rows, and the engine serves through the
    #: sharded step over a mesh of that many devices
    #: (``parallel.make_mesh``).  1 is the one-device table and its
    #: programs.  A checkpoint records it as ``n_shards``; ``fsx serve
    #: --mesh N`` sets it.
    shards: int = 1
    #: Width of a flow key: 32 (an IPv4 source, or the XOR fold of an
    #: IPv6 one: ``kern/parsing.h`` ``fsx_fold_ip6``) or 128 (the full
    #: source, IPv4 as ``::ffff:a.b.c.d``; upstream ``src/fsx_struct.h:9``
    #: ``__u128``).  At 128 the table's key is ``[capacity, 4]`` u32,
    #: the probe hashes and compares all four words, the aggregate's
    #: sort orders by the whole key and a block names the whole source,
    #: so two sources that share a fold never share a row, a vote or a
    #: block.  Resolved when a step is traced; 128 runs the one-chip
    #: sealed path (``fsxd --key-bits 128`` rings, the ``compact28``
    #: wire) and is refused where that width is not built yet.
    key_bits: int = 32

    def __post_init__(self) -> None:
        if self.capacity & (self.capacity - 1) or self.capacity <= 0:
            raise ValueError("capacity must be a power of two")
        if self.key_bits not in (32, 128):
            raise ValueError(f"key_bits must be 32 or 128, got "
                             f"{self.key_bits!r}")
        if self.key_bits == 128 and self.shards > 1:
            raise ValueError(
                "key_bits 128 needs shards 1: the sharded step's owner "
                "hash and routing (parallel/step.py) carry 32-bit keys")
        if self.key_bits == 128 and self.evict_ttl_s > 0:
            raise ValueError(
                "key_bits 128 needs evict_ttl_s 0: the in-step aging "
                "sweep (ops/fused.py evict_idle_epoch) frees 32-bit "
                "keys")
        if self.shards < 1 or self.shards & (self.shards - 1):
            raise ValueError("shards must be a power of two >= 1 (the "
                             "owner is read off the hash's top bits)")
        if self.capacity % self.shards:
            raise ValueError("capacity must divide by shards")
        if self.capacity > 1 << 29:
            # the packed arbitration sort key (slot*2 + priority bit,
            # parked at 2*capacity) must fit int32
            raise ValueError("capacity must be <= 2^29")
        if self.probes < 1:
            raise ValueError("probes must be >= 1")
        if not 0 <= self.salt < 1 << 32:
            raise ValueError("salt must fit in u32")
        if self.evict_ttl_s < 0:
            raise ValueError("evict_ttl_s must be >= 0 (0 disables)")
        if self.evict_every < 1:
            raise ValueError("evict_every must be >= 1")


@dataclass(frozen=True)
class BatchConfig:
    """Micro-batcher policy: flush at ``max_batch`` records or after
    ``deadline_us``, whichever first (SURVEY.md §7.2: "2048 vectors or
    200 µs")."""

    max_batch: int = 2048
    deadline_us: int = 200
    #: Slots in the compact device→host verdict wire (ops/fused.py
    #: ``pack_verdict_wire``): the step compacts newly-blocked
    #: ``(key, until)`` pairs into a fixed ``[verdict_k]`` buffer plus a
    #: count, so the steady-state readback is O(verdict_k) bytes instead
    #: of 8 B/record.  A batch blocking more than ``verdict_k`` flows
    #: sets the wire's overflow flag and the engine falls back to the
    #: full-array fetch for that batch — a block is never lost, it just
    #: costs the old readback once.  0 disables compaction entirely
    #: (every batch fetches the full ``[B]`` arrays — the pre-compaction
    #: wire, kept for parity tests and measurement baselines).
    verdict_k: int = 64
    #: Engine pipe depth: how many batches may be dispatched-but-unsunk
    #: before the dispatch thread blocks on the sink (the backpressure
    #: bound engine/engine.py waits on).  Must be >= 1 — a zero-depth
    #: pipe can never dispatch, it deadlocks the loop on its first
    #: batch.  ``Engine(readback_depth=...)`` overrides per instance.
    readback_depth: int = 8

    def __post_init__(self) -> None:
        if self.max_batch <= 0 or self.deadline_us <= 0:
            raise ValueError("max_batch and deadline_us must be positive")
        if not isinstance(self.verdict_k, int):
            # a float K silently changes the jit cache key per config
            # load AND miscomputes the [2K+4] wire length downstream
            raise ValueError("verdict_k must be an int")
        if self.verdict_k < 0:
            raise ValueError("verdict_k must be >= 0 (0 disables compaction)")
        if self.verdict_k > self.max_batch:
            # at most max_batch flows can block per batch, so slots past
            # that can never fill — a config asking for them is a typo'd
            # K (or B), not a bigger wire
            raise ValueError(
                f"verdict_k ({self.verdict_k}) must be <= max_batch "
                f"({self.max_batch}): a batch cannot block more flows "
                "than it has records")
        if self.readback_depth < 1:
            raise ValueError("readback_depth must be >= 1 (the pipe "
                             "needs at least one in-flight batch)")


@dataclass(frozen=True)
class FsxConfig:
    """Root config."""

    limiter: LimiterConfig = field(default_factory=LimiterConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    table: TableConfig = field(default_factory=TableConfig)
    batch: BatchConfig = field(default_factory=BatchConfig)
    #: Stateless firewall rules (kernel plane; RuleConfig docstring)
    rules: tuple[RuleConfig, ...] = ()
    interface: str = "eth0"             # XDP attach point

    def __post_init__(self) -> None:
        from flowsentryx_tpu.core import schema

        if len(self.rules) > schema.MAX_RULES:
            raise ValueError(f"at most {schema.MAX_RULES} rules")
        keys = [r.key() for r in self.rules]
        if len(set(keys)) != len(keys):
            raise ValueError("duplicate (proto, dport) rule")

    # -- JSON round-trip ----------------------------------------------------

    def to_dict(self) -> dict[str, Any]:
        def enc(obj: Any) -> Any:
            if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
                return {f.name: enc(getattr(obj, f.name))
                        for f in dataclasses.fields(obj)}
            if isinstance(obj, enum.Enum):
                return obj.value
            if isinstance(obj, (list, tuple)):
                return [enc(x) for x in obj]
            return obj

        return enc(self)

    def to_json(self, **kw: Any) -> str:
        return json.dumps(self.to_dict(), indent=2, **kw)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "FsxConfig":
        def dec(tp: type, v: Any) -> Any:
            origin = typing.get_origin(tp)
            if origin in (tuple, list):
                elem = typing.get_args(tp)[0]
                return tuple(dec(elem, x) for x in v)
            if dataclasses.is_dataclass(tp):
                hints = typing.get_type_hints(tp)
                names = {f.name for f in dataclasses.fields(tp)}
                kwargs = {}
                for k, val in v.items():
                    if k not in names:
                        raise KeyError(f"unknown config key {k!r} for {tp.__name__}")
                    kwargs[k] = dec(hints[k], val)
                return tp(**kwargs)
            if isinstance(tp, type) and issubclass(tp, enum.Enum):
                return tp(v)
            return v

        return dec(cls, d)

    @classmethod
    def from_json(cls, s: str) -> "FsxConfig":
        return cls.from_dict(json.loads(s))

    # -- kernel config map --------------------------------------------------

    #: ``struct fsx_config`` fields, in wire order.  The C struct in
    #: ``kern/fsx_schema.h`` is GENERATED from this tuple (codegen.py),
    #: and the pack format below is derived from it, so the three views
    #: cannot drift.
    KERNEL_CONFIG_FIELDS: typing.ClassVar[tuple[tuple[str, str, str], ...]] = (
        ("limiter_kind", "u32", "FSX_LIMITER_*"),
        ("valid", "u32", "nonzero once a config has been pushed; the"
         " all-zero ARRAY-map default means \"no config yet\" (fail open)"),
        ("pps_threshold", "u64", "packets per window"),
        ("bps_threshold", "u64", "bytes per window"),
        ("window_ns", "u64", ""),
        ("block_ns", "u64", "blacklist TTL"),
        ("bucket_rate_pps", "u64", "token refill rate (packets/s)"),
        ("bucket_burst", "u64", "token bucket depth (packets)"),
        ("bucket_rate_bps", "u64", "byte-bucket refill rate (bytes/s);"
         " 0 with 0 depth = byte dimension off"),
        ("bucket_burst_bytes", "u64", "byte bucket depth (bytes)"),
        ("rule_count", "u64", "number of stateless firewall rules pushed"
         " into rule_map; 0 skips the rule lookups entirely"),
        ("hash_salt", "u64", "salt for user-plane slot/owner hashing"
         " (low 32 bits used).  No kernel-side consumer exists: BPF maps"
         " hash internally with their own seed.  Carried in the blob so"
         " a deployment that FIXES the salt in its config file presents"
         " one value to both planes; a serve-drawn random salt is"
         " user-plane only"),
    )

    KERNEL_CONFIG_FMT = "<" + "".join(
        {"u32": "I", "u64": "Q"}[t] for _, t, _ in KERNEL_CONFIG_FIELDS
    )
    KERNEL_CONFIG_SIZE = struct.calcsize(KERNEL_CONFIG_FMT)  # 88

    _KIND_CODE = {
        LimiterKind.FIXED_WINDOW: 0,
        LimiterKind.SLIDING_WINDOW: 1,
        LimiterKind.TOKEN_BUCKET: 2,
    }

    def pack_kernel_config(self) -> bytes:
        """Binary blob for the kernel's config array map (index 0).

        Integer units (packets, bytes, nanoseconds) because eBPF has no
        floats (``fsx_kern_ml.c:3-6``).
        """
        lim = self.limiter
        return struct.pack(
            self.KERNEL_CONFIG_FMT,
            self._KIND_CODE[lim.kind],
            1,  # valid: distinguishes a pushed config from the map's zero fill
            int(lim.pps_threshold),
            int(lim.bps_threshold),
            int(lim.window_s * 1e9),
            int(lim.block_s * 1e9),
            int(lim.bucket_rate_pps),
            int(lim.bucket_burst),
            int(lim.bucket_rate_bps),
            int(lim.bucket_burst_bytes),
            len(self.rules),
            int(self.table.salt),
        )

    def rule_entries(self) -> list[tuple[int, int]]:
        """``(key, action)`` pairs for the kernel rule map (key packing
        in :func:`flowsentryx_tpu.core.schema.pack_rule_key`)."""
        from flowsentryx_tpu.core import schema

        return [(r.key(), schema.RULE_DROP) for r in self.rules]


DEFAULT_CONFIG = FsxConfig()

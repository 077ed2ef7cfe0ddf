"""Device-resident open-addressing IP state table.

Successor of the reference's three ``BPF_MAP_TYPE_LRU_HASH`` maps
(``fsx_kern.c:64-94``) as a key vector + one ``[capacity, 12]`` state
matrix (:class:`~flowsentryx_tpu.core.schema.IpTableState`) that lives
in HBM and is updated in place via donated buffers.  Design constraints
that shaped it (SURVEY.md §7.4.2):

* **Static shapes, bounded probes.**  Open addressing with a
  compile-time probe count ``P``: lookup is one ``[R, P]`` gather (the
  keys) + a reduction — no data-dependent loops and nothing
  table-wide, so XLA vectorizes it flat and its cost follows the
  batch, not the capacity.  A second ``[R, P]`` gather (``last_seen``
  out of the state matrix) is taken only by a batch in which some key
  has neither a match nor an empty slot among its probes: nothing
  else can be decided by staleness.
* **Batch-internal collision resolution.**  Two distinct keys in one
  micro-batch can select the same slot (hash collision on insert); a
  sort-based arbitration picks exactly one winner per slot
  (found-key beats stale-reclaimer) and marks the rest untracked for
  this batch (they still get classified — losing a limiter update for
  one batch is the bounded-error analog of the reference's LRU
  silently evicting attackers, SURVEY.md §5.3).
* **Stale reclamation ≈ LRU.**  Slots idle longer than
  ``TableConfig.stale_s`` are reclaimed by inserts, approximating the
  kernel map's LRU eviction without global bookkeeping.

Keys are uint32 (IPv4 address or 32-bit fold of IPv6); 0 and
0xFFFFFFFF are reserved (empty slot / invalid sentinel) — neither is a
routable unicast source.  A table with ``TableConfig.key_bits`` 128
keys by the full source instead: ``[capacity, 4]`` u32, all-zero empty
and all-ones invalid (``::`` and ``ffff:...:ffff`` are no unicast
source either); the probe hashes and compares all four words.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from flowsentryx_tpu.core.config import TableConfig
from flowsentryx_tpu.core.schema import IpTableState, TableCol

# numpy scalar, not jnp (see agg.INVALID_KEY note).
EMPTY_KEY = np.uint32(0)


def hash_u32(k: jnp.ndarray, salt: int = 0) -> jnp.ndarray:
    """Murmur3 finalizer — avalanches all 32 bits (uint32 wraparound).

    ``salt`` (``TableConfig.salt``) is xor-mixed ahead of the finalizer
    so its avalanche spreads the salt over every output bit: with a
    random boot-time salt, slot/owner positions are unpredictable to an
    attacker who knows the hash function (adversarial-collision
    defense; parallel/step.py module docstring)."""
    k = k.astype(jnp.uint32) ^ jnp.uint32(salt)
    k ^= k >> 16
    k *= jnp.uint32(0x85EBCA6B)
    k ^= k >> 13
    k *= jnp.uint32(0xC2B2AE35)
    k ^= k >> 16
    return k


def hash_key(key: jnp.ndarray, salt: int = 0, xor0: int = 0
             ) -> jnp.ndarray:
    """:func:`hash_u32` of a ``[R]`` key xor ``xor0``, or a chain of it
    over the four words of a ``[R, 4]`` one (``xor0`` on the first):
    each word is mixed into the hash of the words before it, so every
    bit of the source moves the result and sources whose XOR folds
    agree hash apart."""
    if key.ndim == 1:
        return hash_u32(key ^ jnp.uint32(xor0) if xor0 else key, salt)
    h = hash_u32(key[:, 0] ^ jnp.uint32(xor0), salt)
    for i in range(1, key.shape[1]):
        h = hash_u32(h ^ key[:, i], salt)
    return h


class SlotAssignment(NamedTuple):
    """Result of resolving a batch of flow keys against the table."""

    slot: jnp.ndarray      # [R] int32 table row (garbage where ~tracked)
    found: jnp.ndarray     # [R] bool: key already present
    inserted: jnp.ndarray  # [R] bool: claimed an empty/stale slot
    tracked: jnp.ndarray   # [R] bool: found | inserted (and won arbitration)
    read_seen: jnp.ndarray  # [] bool: ProbeResult.read_seen of the probe


class ProbeResult(NamedTuple):
    """Per-key slot selection, BEFORE any batch-internal arbitration."""

    slot: jnp.ndarray    # [R] int32 selected table row
    found: jnp.ndarray   # [R] bool: exact key match at slot
    usable: jnp.ndarray  # [R] bool: match, empty, or stale-reclaimable
    read_seen: jnp.ndarray  # [] bool: the probe read ``last_seen``
    #                      (``GlobalStats.stale_reads`` counts these)


def probe_slots(
    table: IpTableState,
    key: jnp.ndarray,
    valid: jnp.ndarray,
    now: jnp.ndarray,
    cfg: TableConfig,
) -> ProbeResult:
    """Double-hashed probe + claim-priority selection for each key.

    THE one copy of the probe math: :func:`assign_slots` (per-flow,
    sharded path) and the single-sort fused step (per-packet) both call
    it, so their slot decisions cannot drift — the cross-path parity
    test relies on bit-identical selection.

    Probe sequence: ``(h1 + p·step) mod N`` with an odd ``step`` from a
    second salted hash — odd steps generate the full ring for
    power-of-two ``N``, so probes don't clump under adversarial floods.
    Claim priority per key: exact match > first empty > earliest stale
    reclaimable; selection is ``argmin`` over a priority score.  The
    candidates' keys come from ``table.key`` in one ``[R, P]`` gather.
    Their ``last_seen`` — a second one, from the state matrix at
    ``(slot, LAST_SEEN)`` — is read only where it can decide: a stale
    candidate scores above every match and every empty slot, so it
    wins only for a key that has neither among its probes.  A batch
    with no such valid key (nearly every batch of a table that is not
    near full) skips the read, and ``found``, ``usable`` and the
    ``slot`` of every usable row are what the read would have given;
    ``read_seen`` says which it was.  Both branches return ``[R, P]``
    and never the table (``ops/fused.py``'s note on conditionals that
    carry it).  The function takes the TABLE, never a column of it:
    ``table.last_seen`` is a table-wide copy on the device
    (:class:`IpTableState`), paid every step whatever the batch
    holds."""
    n = table.key.shape[0]
    mask = jnp.uint32(n - 1)
    p = cfg.probes

    # a [R, 4] key (TableConfig.key_bits 128): the step hash's chain
    # starts from the first word's golden-ratio xor, as the 32-bit one
    # does from the key's
    h1 = hash_key(key, cfg.salt)
    step = hash_key(key, cfg.salt, xor0=0x9E3779B9) | jnp.uint32(1)
    offs = jnp.arange(p, dtype=jnp.uint32)  # [P]
    slots = (h1[:, None] + offs[None, :] * step[:, None]) & mask  # [R, P]
    slots = slots.astype(jnp.int32)

    cand_key = table.key[slots]               # [R, P] (or [R, P, 4]) gather
    if key.ndim == 2:
        match = jnp.all(cand_key == key[:, None, :], axis=2)
        empty = jnp.all(cand_key == EMPTY_KEY, axis=2)
    else:
        match = cand_key == key[:, None]
        empty = cand_key == EMPTY_KEY
    read_seen = jnp.any(valid & ~jnp.any(match | empty, axis=1))

    def stale_by_last_seen():
        cand_seen = table.state[slots, int(TableCol.LAST_SEEN)]  # [R, P]
        return (~match) & (~empty) & (now - cand_seen > cfg.stale_s)

    stale = jax.lax.cond(read_seen, stale_by_last_seen,
                         lambda: jnp.zeros(slots.shape, bool))

    # Priority score per candidate (lower = better):
    #   match  -> 0 + probe index        (prefer earliest probe)
    #   empty  -> P + probe index
    #   stale  -> 2P + probe index       (prefer earliest, not stalest:
    #                                     cheaper and just as correct)
    #   else   -> 4P (unusable)
    probe_idx = jnp.arange(p, dtype=jnp.int32)[None, :]
    score = jnp.where(
        match, probe_idx,
        jnp.where(empty, p + probe_idx,
                  jnp.where(stale, 2 * p + probe_idx, 4 * p)),
    )
    # the winner's score and slot by a select over the P candidates
    # (a take_along_axis is a gather of [R] indices: on the chip each
    # cost a thirtieth of the stage for values it has at hand)
    best = jnp.argmin(score, axis=1)  # [R], first minimum
    best_score = jnp.min(score, axis=1)
    slot = jnp.max(jnp.where(probe_idx == best[:, None], slots, 0), axis=1)

    found = valid & (best_score < p)
    usable = valid & (best_score < 4 * p)
    return ProbeResult(slot=slot, found=found, usable=usable,
                       read_seen=read_seen)


def assign_slots(
    table: IpTableState,
    rep_key: jnp.ndarray,
    rep_valid: jnp.ndarray,
    now: jnp.ndarray,
    cfg: TableConfig,
) -> SlotAssignment:
    """Find-or-claim a table slot for each representative key (probe
    math shared with the fused step via :func:`probe_slots`, which
    reads ``table`` by gather only)."""
    n = table.key.shape[0]
    r = rep_key.shape[0]

    pr = probe_slots(table, rep_key, rep_valid, now, cfg)
    slot, found, usable = pr.slot, pr.found, pr.usable
    inserted = usable & ~found

    # --- batch-internal arbitration: one winner per claimed slot -----------
    # Distinct keys may claim the same empty/stale slot.  One sort over
    # a PACKED key — slot*2 + (0 if found else 1) — orders by slot with
    # found-first inside each slot group (a flow that FOUND its key
    # always beats one reclaiming that slot as stale; same-key
    # collisions are impossible: agg yields distinct reps).  Packing
    # replaces the previous two-pass lexsort with a single sort pass —
    # the sort is the arbitration's whole cost on TPU.  Ties among
    # same-priority claimants break arbitrarily (exactly one wins,
    # which is all correctness needs).  The parked sentinel 2n must
    # also fit int32, so capacity <= 2^29 (enforced by TableConfig; a
    # 2^29-row table is already ~26 GB of state).
    slot_for_sort = jnp.where(usable, slot, jnp.int32(n))  # park unusable at n
    packed = slot_for_sort * 2 + (~found).astype(jnp.int32)
    order = jnp.argsort(packed)
    sorted_slot = slot_for_sort[order]
    head = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_slot[1:] != sorted_slot[:-1]]
    )
    is_winner_sorted = head & (sorted_slot < n)
    winner = jnp.zeros((r,), bool).at[order].set(is_winner_sorted)

    tracked = usable & winner
    inserted = inserted & winner
    found = found & winner
    return SlotAssignment(slot=slot, found=found, inserted=inserted,
                          tracked=tracked, read_seen=pr.read_seen)

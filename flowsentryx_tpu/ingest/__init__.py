"""Sharded parallel host ingest: break the single-threaded drain ceiling.

The inline serving loop tops out where one Python thread tops out: the
decode/seal stage between ``ShmRingSource.poll`` and the dispatch costs
per RECORD, the dispatch per batch (the split on the chip host: not
measured yet).  The fix is the standard per-packet-ML answer (Taurus,
FENXI):
shard the host ingest stage and pipeline it away from the accelerator
dispatch loop.

Architecture::

    kernel / fsxd --shards N          (IP-hash fan-out, per-CPU analog)
        ├── shm feature ring shard 0 ──► drain worker 0 ─┐ sealed-batch
        ├── shm feature ring shard 1 ──► drain worker 1 ─┤ SPSC queues
        │   ...                                          │ (engine/shm.py
        └── shm feature ring shard N-1 ► drain worker N-1┘  SealedBatchQueue)
                                                  │
                                engine: dequeue → dispatch → reap

* Each **drain worker** (:mod:`.worker`) is a separate pure-numpy
  process owning ONE ring shard: it drains, decodes, quantizes, and
  seals complete ``[B+1, words]`` wire buffers, so the engine's hot
  loop never touches a raw record again.
* Records fan out by IP hash (``schema.shard_of``): a flow's records
  stay on one shard, preserving their relative order end-to-end —
  the same affinity the kernel's per-CPU ringbuf production gives.
* The **engine** consumes sealed batches round-robin through
  :class:`~flowsentryx_tpu.ingest.sharded.ShardedIngest`; a worker
  crash fails open (remaining shards keep serving, the kernel limiter
  covers the dead shard's flows), a stop request drains every ring to
  empty before the workers exit.
"""

from flowsentryx_tpu.ingest.sharded import (  # noqa: F401
    SealedBatch,
    SeqTracker,
    ShardedIngest,
)

#!/usr/bin/env python3
"""Ring backpressure for a free-running ``fsxd --sim``: the closed loop.

    python3 benchmark/governor.py --pid <fsxd> --high-water <records>
        --low-water <records> --poll-us <us> --status <file>
        --verdict-ring <path> <feature ring> [<feature ring> ...]

``fsxd --sim`` without ``--pace`` stamps its records from its own counter
(record time: ``--rate`` fixes each source's rate there, whatever the wall
clock does) and produces as fast as it can; what does not fit a ring it
drops.  This process makes it wait instead: it watches the rings' cursors
and holds the daemon (``SIGSTOP``) once any feature ring holds
``high_water`` records, and lets it go (``SIGCONT``) once all hold under
``low_water``.  A held daemon's record clock stands still, so the traffic
the engine sees (suppressed share, blocks a wave, the batches' make-up) is
a function of the rings' depth in record time and not of the engine's
speed, and nothing is shed: the rings are made well larger than
``high_water`` (the workload's ``ring_capacity``), and the room between is
what a late poll may cost.

``low_water`` is well under ``high_water`` where the shards are unequal.
The engine takes sealed batches from the shards' workers in turn, so the
shard that gets less of what the daemon forwards runs empty whatever the
mark (and a mark on the emptier shard sends the fuller one away: its
blocks land later, its sources are suppressed for less, it gets more
still).  The worker of an empty shard seals part-filled batches at the
deadline when the daemon is let go, about one batch's slots a hold: few
long holds cost less than many short ones (PERF.md section 4).

The driver holds the daemon from the moment its rings exist until this
process is up, so it is started held.

A held daemon reads no verdicts either, so it is never held while more
than half its verdict ring is unread: the engine's sink waits on that ring,
and two waits on each other would end in the sink's give-up.

It touches neither JAX nor numpy (it starts in tens of milliseconds and
shares no interpreter lock with the engine: an engine that stalls does
not stall the backpressure).  Every poll it writes four numbers to
``--status`` (nanoseconds held, nanoseconds alive, holds, longest gap
between two polls in nanoseconds), which the driver reads into its
snapshots; at ``SIGTERM`` it lets the daemon go, prints them as one JSON
line and ends.
"""

from __future__ import annotations

import argparse
import json
import mmap
import os
import signal
import struct
import sys
import time

#: shm ring header (kern/fsx_schema.h ``struct fsx_shm_ring_hdr``)
CAPACITY_OFFSET, HEAD_OFFSET, TAIL_OFFSET, HDR_SIZE = 8, 64, 128, 192
STATUS = struct.Struct("<4Q")  # held_ns, alive_ns, holds, longest_poll_ns


class Cursors:
    """The header of one shm ring, read-only."""

    def __init__(self, path: str):
        with open(path, "rb") as f:
            self.mm = mmap.mmap(f.fileno(), HDR_SIZE, prot=mmap.PROT_READ)

    def capacity(self) -> int:
        """Slots; 0 until the ring's maker has written its header."""
        return struct.unpack_from("<Q", self.mm, CAPACITY_OFFSET)[0]

    def unread(self) -> int:
        head = struct.unpack_from("<Q", self.mm, HEAD_OFFSET)[0]
        tail = struct.unpack_from("<Q", self.mm, TAIL_OFFSET)[0]
        return head - tail if head > tail else 0


def read_status(path) -> dict | None:
    """The governor's four numbers, from the file it keeps up to date."""
    try:
        with open(path, "rb") as f:
            held, alive, holds, gap = STATUS.unpack(f.read(STATUS.size))
    except (OSError, struct.error):
        return None
    return {"blocked_ns": held, "governed_ns": alive, "holds": holds,
            "longest_poll_ns": gap}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pid", type=int, required=True)
    ap.add_argument("--high-water", type=int, required=True)
    ap.add_argument("--low-water", type=int, required=True)
    ap.add_argument("--poll-us", type=int, required=True)
    ap.add_argument("--status", required=True)
    ap.add_argument("--verdict-ring", required=True)
    ap.add_argument("rings", nargs="+")
    a = ap.parse_args()

    stop = []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
    signal.signal(signal.SIGINT, lambda *_: stop.append(1))
    rings = [Cursors(p) for p in a.rings]
    vring = Cursors(a.verdict_ring)
    with open(a.status, "wb") as f:
        f.write(bytes(STATUS.size))
    with open(a.status, "r+b") as f:
        status = mmap.mmap(f.fileno(), STATUS.size)

    t0 = last = time.monotonic_ns()
    held_ns = holds = longest = 0
    held_since = t0  # the driver starts this with the daemon held
    try:
        while not stop:
            fill = max(r.unread() for r in rings)
            v_ok = 2 * vring.unread() <= vring.capacity()
            now = time.monotonic_ns()
            longest = max(longest, now - last)
            last = now
            if held_since is None:
                if fill >= a.high_water and v_ok:
                    os.kill(a.pid, signal.SIGSTOP)
                    held_since, holds = now, holds + 1
            elif fill < a.low_water or not v_ok:
                os.kill(a.pid, signal.SIGCONT)
                held_ns += now - held_since
                held_since = None
            STATUS.pack_into(
                status, 0,
                held_ns + (0 if held_since is None else now - held_since),
                now - t0, holds, longest)
            time.sleep(a.poll_us / 1e6)
    except ProcessLookupError:  # the daemon has gone: nothing to govern
        held_since = None
    finally:
        now = time.monotonic_ns()
        if held_since is not None:
            held_ns += now - held_since
            try:
                os.kill(a.pid, signal.SIGCONT)
            except ProcessLookupError:
                pass
        STATUS.pack_into(status, 0, held_ns, now - t0, holds, longest)
    print(json.dumps({"blocked_ns": held_ns, "governed_ns": now - t0,
                      "holds": holds, "longest_poll_ns": longest}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

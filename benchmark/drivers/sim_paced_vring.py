"""Traffic driver ``sim_paced_vring``: ``sim_paced`` as it is, with the
verdict ring's writer in its counters.

The engine's report holds the ring's accounting (``ring_accounting()``:
dropped, waits, fill peak) and its span (``spans()``:
``fsx.sink.vring_wait``) only where the engine's own sink offers them,
and ``harness.SinkTap`` forwards neither, so the driver reads them from
the sink behind the tap, as ``sim_paced`` reads ``dropped`` there, and
gives them to the metric readers under ``gen["vring"]``: the accounting,
and ``spans`` in the report's own form (``span_window.subtract`` takes
two of them).  A program whose sink has neither (before ISSUE 32) adds
nothing, and the readers read nothing.  Once ``SinkTap`` forwards both,
the readers can turn to the report and this file can go.
"""

from benchmark import harness

sim_paced = harness.load_module("drivers", "sim_paced")


class Driver(sim_paced.Driver):
    def counters(self) -> dict:
        out = super().counters()
        ring = self.sink.real
        if hasattr(ring, "ring_accounting") and hasattr(ring, "spans"):
            from flowsentryx_tpu.engine.metrics import span_store

            out["vring"] = dict(
                ring.ring_accounting(),
                spans=span_store({sp.name: sp.hist for sp in ring.spans()}))
        return out

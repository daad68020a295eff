"""Share of the window the aggregator's transfer worker spent in
``ingest.lock_wait`` spans: waiting for the device lock, which the
interval commit holds while it dispatches."""

from benchmark import spanclock


def read(ctx):
    return spanclock.window_share(ctx.spans, "ingest.lock_wait", ctx.t0_ns,
                                  ctx.t1_ns)

"""Share of the window the aggregator's transfer worker spent in
``ingest.merge`` spans: under the device lock, the packed triples'
chunked transfer and the sparse ingest dispatches."""

from benchmark import spanclock


def read(ctx):
    return spanclock.window_share(ctx.spans, "ingest.merge", ctx.t0_ns,
                                  ctx.t1_ns)

"""Share of the window the aggregator's transfer worker spent in
``ingest.fold`` spans: the native fold of a raw batch into packed
(row, bucket, count) triples, on the sparse transport."""

from benchmark import spanclock


def read(ctx):
    return spanclock.window_share(ctx.spans, "ingest.fold", ctx.t0_ns,
                                  ctx.t1_ns)

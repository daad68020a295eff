"""The ingest programs' share of their roofline on a mesh: the least
bytes of ``ingest_kernel_roofline`` (an int32 read-modify-write of every
distinct (row, bucket) cell the window's samples touch) split evenly over
the traced devices, over peak HBM bandwidth, divided by the ingest
programs' device time per device (``layer_s`` is a per-device mean).
Every cell is updated on at least one device, so no split of the work
can push it past 100%."""

from benchmark import peaks, roofline


def read(ctx):
    t = ctx.trace
    dev_s = t["layer_s"].get("ingest", 0.0)
    if t["devices"] == 0 or dev_s <= 0 or ctx.distinct_cells <= 0:
        return None
    least = roofline.least_ingest_bytes(ctx.distinct_cells) / t["devices"]
    return roofline.share_pct(least, peaks.peak(ctx.device_kind), dev_s)

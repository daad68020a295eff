"""Mean of the committer's ``commit.lock_wait`` spans in the window: an
interval's wait for the aggregator's device lock (behind the transfer
worker's merge) and then the wheel's lock."""


def read(ctx):
    got = [s.end_ns - s.start_ns for s in ctx.spans
           if s.stage == "commit.lock_wait"]
    if not got:
        return None
    return sum(got) / len(got) / 1e6

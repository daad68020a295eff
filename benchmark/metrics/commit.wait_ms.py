"""Mean, over the window's intervals, of the time from the reaper's
collection at the tick (``reaper.collect`` start) to the start of the
interval's commit (``commit.e2e`` start, same seq): collection,
broadcast and the wait for the committer."""


def read(ctx):
    collected = {s.seq: s.start_ns for s in ctx.spans
                 if s.stage == "reaper.collect"}
    got = [s.start_ns - collected[s.seq] for s in ctx.spans
           if s.stage == "commit.e2e" and s.seq in collected]
    if not got:
        return None
    return sum(got) / len(got) / 1e6

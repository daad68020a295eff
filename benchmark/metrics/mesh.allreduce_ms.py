"""Device time of collective operations (all-reduce and the like, by
``xplane.COLLECTIVE``), mean per device, per interval committed in the
window: on a mesh the ingest settle's stream all-reduce and the commit's
one all-reduce per chunk."""


def read(ctx):
    t = ctx.trace
    if t["devices"] == 0 or t["collective_s"] <= 0 or ctx.commits <= 0:
        return None
    return 1e3 * t["collective_s"] / ctx.commits

"""The part of ``mesh.allreduce_ms`` that no other operation on the same
device overlaps: collective time the device spends doing nothing else,
mean per device, per interval committed in the window."""


def read(ctx):
    t = ctx.trace
    if t["devices"] == 0 or t["collective_s"] <= 0 or ctx.commits <= 0:
        return None
    return 1e3 * t["collective_exposed_s"] / ctx.commits

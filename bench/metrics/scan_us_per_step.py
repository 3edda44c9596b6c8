"""Microseconds of sweep-kernel device time per event step, over the
sweep modules the device trace holds whole.  Their steps are the
window's event steps (the engines' ``events`` statistic: the longest
trace's events per call) times their share of all sweep modules run in
the window (``work["runs"]``: shard scans, or calls of the unstreamed
batch).  Padding steps cost device time but are not counted as steps,
so padding shows as a dearer step."""


def read(ctx):
    dev, work = ctx["device"], ctx["work"]
    if not dev or not dev["kernel_runs"] or not work or not work["steps"] \
            or not work["runs"]:
        return None
    steps = work["steps"] * dev["kernel_runs"] / work["runs"]
    return 1e6 * dev["kernel_s"] / steps

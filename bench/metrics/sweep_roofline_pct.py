"""The sweep kernel's share of its roofline: the least time its work
can take on the chip (``bench/roofline.py``) over its device time, for
the sweep modules the device trace holds whole, each given an equal
share of the window's work."""

import roofline


def read(ctx):
    dev, work = ctx["device"], ctx["work"]
    if not dev or not dev["kernel_runs"] or not work \
            or work["ops"] is None or not work["runs"]:
        return None
    share = dev["kernel_runs"] / work["runs"]
    return roofline.share_pct(work["ops"] * share, work["bytes"] * share,
                              dev["kernel_s"], ctx["peaks"])

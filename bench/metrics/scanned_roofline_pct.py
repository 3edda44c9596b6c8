"""The sweep kernel's share of its roofline over the work its
dispatches really scanned (``bench/roofline_scanned.py``): the least
time of that work over the device time of the sweep modules the trace
holds whole, each given an equal share of the window's plain sweep
dispatches (``sweep.kernel.pallas`` + ``sweep.kernel.scan``)."""

import roofline
import roofline_scanned


def read(ctx):
    dev, obs = ctx["device"], ctx["obs"]
    dispatches = obs.get("sweep.kernel.pallas", 0) \
        + obs.get("sweep.kernel.scan", 0)
    if not dev or not dev["kernel_runs"] or not dispatches \
            or "sweep.fit_cells" not in obs:
        return None
    ops, nbytes = roofline_scanned.scanned_work(
        obs["sweep.fit_cells"], obs["sweep.events_scanned"],
        obs["sweep.carry_cells"])
    share = dev["kernel_runs"] / dispatches
    return roofline.share_pct(ops * share, nbytes * share,
                              dev["kernel_s"], ctx["peaks"])

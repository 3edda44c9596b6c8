"""Microseconds of sweep wait (``sweep_wait_s``'s spans) per event step
the scans ran (``sweep.steps``: the longest trace's real events of each
dispatched scan).  Padding steps are not counted, so padding shows as a
dearer step, as in ``scan_us_per_step``."""

from metrics.sweep_wait_s import SPANS


def read(ctx):
    obs = ctx["obs"]
    found = [obs[k] for k in SPANS if k in obs]
    steps = obs.get("sweep.steps")
    return 1e6 * sum(found) / steps if found and steps else None

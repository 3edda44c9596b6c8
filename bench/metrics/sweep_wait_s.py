"""Seconds per answer the host spent on sweep kernels: each dispatch,
from the kernel call to the host holding its result
(``span.replay.compute``, ``span.batch.compute``,
``span.stream.compute``), over whole answers."""

SPANS = ("span.replay.compute.total_s", "span.batch.compute.total_s",
         "span.stream.compute.total_s")


def read(ctx):
    found = [ctx["obs"][k] for k in SPANS if k in ctx["obs"]]
    return sum(found) / ctx["answers"] if found else None

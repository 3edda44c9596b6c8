"""Seconds per answer of replay preparation in the streamed engines:
the event compile (``span.stream.compile``) and the reference replay
for window skipping (``span.stream.reference``)."""

SPANS = ("span.stream.compile.total_s", "span.stream.reference.total_s")


def read(ctx):
    found = [ctx["obs"][k] for k in SPANS if k in ctx["obs"]]
    return sum(found) / ctx["answers"] if found else None

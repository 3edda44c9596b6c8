"""Seconds per answer building the unstreamed replay engines: each
trace's event compile (``span.replay.compile``) and the stacking and
upload of the traces' event columns (``span.batch.upload``)."""

SPANS = ("span.replay.compile.total_s", "span.batch.upload.total_s")


def read(ctx):
    found = [ctx["obs"][k] for k in SPANS if k in ctx["obs"]]
    return sum(found) / ctx["answers"] if found else None

"""Seconds per answer loading trace files: each
``traces.load_trace_file`` call (``span.ingest.load``)."""


def read(ctx):
    s = ctx["obs"].get("span.ingest.load.total_s")
    return None if s is None else s / ctx["answers"]

"""Seconds per answer the streamed sweep waited for its next shard's
upload (``span.stream.upload_wait``)."""


def read(ctx):
    s = ctx["obs"].get("span.stream.upload_wait.total_s")
    return None if s is None else s / ctx["answers"]

"""Sweep calls per answer: the serial device rounds of the searches
(``span.batch.reject_rates`` and ``span.stream_batch.reject_rates``)."""

SPANS = ("span.batch.reject_rates.count",
         "span.stream_batch.reject_rates.count")


def read(ctx):
    n = sum(ctx["obs"].get(k, 0) for k in SPANS)
    return n / ctx["answers"] if n else None

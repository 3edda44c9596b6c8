"""Seconds per answer the Pond policy spent on its customers' history
percentiles (``policy_engine._prefix_percentiles``, the span
``span.policy.percentiles`` inside ``policy.decide``)."""


def read(ctx):
    s = ctx["obs"].get("span.policy.percentiles.total_s")
    return None if s is None else s / ctx["answers"]

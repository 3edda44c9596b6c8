"""Share of the streamed calls' event steps that no dispatch scanned,
because window skipping started a call past them or a reject-cap exit
ended it before them (``stream.steps_unscanned``), over those and the
steps the dispatches scanned (``sweep.steps``)."""


def read(ctx):
    obs = ctx["obs"]
    left = obs.get("stream.steps_unscanned")
    total = (left or 0) + obs.get("sweep.steps", 0)
    return None if left is None or not total else 100.0 * left / total

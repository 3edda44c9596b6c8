"""Roofline work of what the sweep's dispatches really scanned
(``bench/roofline.py``'s bounds, counted where a stream skips shards or
stops at a reject cap).

The program counts, at every plain sweep dispatch, what it scanned
(``core/obs.py`` counters, summed over the window):

  ops   = OPS_PER_CELL * sweep.fit_cells
  bytes = EVENT_BYTES * sweep.events_scanned
          + STATE_BYTES * sweep.carry_cells

* ``sweep.fit_cells``: servers * ``sweep.lane_arrivals``, the (lane,
  server) cells the best fits of the scanned arrivals compare, summed
  over the call's traces and its real candidate lanes.
* ``sweep.events_scanned``: the real events each dispatch read, summed
  over its traces; a skipped shard, or one a reject-cap exit left,
  reads none.
* ``sweep.carry_cells``: the free-core and used-memory entries per
  server and the used-pool entry per group, in and out of each
  dispatch, per real lane and trace (an unstreamed batch takes one
  shared initial state in).

The constants are ``bench/roofline.py``'s: padding is not work, and
each count is the least the work needs, so the least time is a lower
bound and the share cannot pass 100%.
"""
from __future__ import annotations

from roofline import EVENT_BYTES, OPS_PER_CELL, STATE_BYTES


def scanned_work(fit_cells: int, events: int,
                 carry_cells: int) -> tuple[int, int]:
    """(integer operations, HBM bytes) of the scanned work."""
    return (OPS_PER_CELL * fit_cells,
            EVENT_BYTES * events + STATE_BYTES * carry_cells)

"""Roofline share of the sweep kernel (``sweep_core.build_sweep``).

The least time a sweep call can take is the larger of two bounds,
computed from shapes alone, whatever implements the kernel:

  ops   = OPS_PER_CELL * servers * lanes * arrivals
  bytes = calls * events * EVENT_BYTES
          + 2 * lanes * traces * (2 * servers + groups) * STATE_BYTES
  least = max(ops / peak_ops, bytes / peak_bytes_per_s)
  share = 100 * least / kernel device time

* ``arrivals``: VM arrivals summed over the traces of a call; only an
  arrival asks for a best fit.  ``lanes``: candidate lanes summed over
  the calls (the program's ``pad.cand_lanes_used`` counter); every
  trace of a call prices all of them.  Padded lanes and padded events
  are not work, so they do not count.
* ``OPS_PER_CELL``: the integer operations an arrival needs per (lane,
  server) for the best fit: compare free cores, compare free local
  memory, AND both with the group's pool test (two ANDs), mask the
  score, and the argmin's compare and select.  The pool test itself is
  per group, not per server, and is left out.
* ``bytes``: each call reads every event once (kind, slot, cores,
  local, pool and memory, 2 bytes each: the least width that holds
  them) and moves the carry (free cores and used local memory per
  server, used pool per group) in and out once.  A kernel that keeps
  the carry in VMEM moves nothing else.
* ``peak_ops``: the table's int8 rate, the highest integer rate the chip
  is published with, so ``least`` is a lower bound and the share cannot
  pass 100%.  That rate is the matrix units'; the sweep's compares and
  selects run on the vector unit, whose rate Google does not publish
  and which is lower, so the share reads low against what the vector
  unit could do.
"""
from __future__ import annotations

OPS_PER_CELL = 7
EVENT_BYTES = 12
STATE_BYTES = 2


def sweep_work(arrivals: int, events: int, lanes: int, calls: int,
               traces: int, servers: int, groups: int) -> tuple[int, int]:
    """(integer operations, HBM bytes) of ``calls`` sweep calls over
    ``traces`` traces holding ``arrivals`` arrivals and ``events``
    events in all, with ``lanes`` candidate lanes summed over the
    calls."""
    ops = OPS_PER_CELL * servers * lanes * arrivals
    nbytes = (calls * events * EVENT_BYTES
              + 2 * lanes * traces * (2 * servers + groups) * STATE_BYTES)
    return ops, nbytes


def least_time(ops: int, nbytes: int, peaks: dict) -> tuple[float, str]:
    """The least seconds the work can take, and which bound binds."""
    t_ops = ops / peaks["int8_ops"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_mem else (t_mem, "bytes")


def share_pct(ops: int, nbytes: int, kernel_s: float,
              peaks: dict) -> float:
    return 100.0 * least_time(ops, nbytes, peaks)[0] / kernel_s

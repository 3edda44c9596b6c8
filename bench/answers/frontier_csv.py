"""A frontier answer from trace files: ``answers.frontier``'s answer,
with each trace read from a CSV.gz file on every answer.

The first answer (set-up's warm-up) writes the cell's K traces once,
with ``traces.save_trace_csv``, under ``.bench_work/frontier_csv/``.
Every answer then loads both files with ``traces.load_trace_file``
inside the ``ingest`` layer and prices the frontier's grid on what it
loaded, as ``answers.frontier.answer`` does.  One checkout runs one
cell at a time, so the file names hold no seed.

Check: the plain reader (``bench/csvtrace.py``) parses each file, and
every VM's arrival, lifetime, cores and memory must equal what the
program ingested (``ingest_off_vms``).  Then the frontier's own checks
run on the files' columns: a file rounds arrivals to 1 ms, so the
generated columns would not replay the same trace.
"""
from __future__ import annotations

import copy
import types
from pathlib import Path

import numpy as np

import csvtrace
from answers import frontier

WORK = Path(__file__).resolve().parents[2] / ".bench_work" / "frontier_csv"


def trace_files(cell) -> list[Path]:
    """The cell's trace files, written on the first call."""
    paths = getattr(cell, "trace_files", None)
    if paths is None:
        from repro.core import traces
        WORK.mkdir(parents=True, exist_ok=True)
        paths = [WORK / f"trace{k}.csv.gz" for k in range(len(cell.traces))]
        for vms, path in zip(cell.vms_list, paths):
            traces.save_trace_csv(vms, str(path))
        cell.trace_files = paths
    return paths


def answer(cell, clock) -> dict:
    from repro.core import traces
    paths = trace_files(cell)
    with clock.layer("ingest"):
        vms_list = [traces.load_trace_file(str(p)) for p in paths]
    loaded = copy.copy(cell)
    loaded.vms_list = vms_list
    out = frontier.answer(loaded, clock)
    out["vms"] = vms_list
    return out


class _FileTrace:
    """One file's columns where ``answers.frontier`` reads a trace."""

    def __init__(self, cols: dict):
        self.mem_gb = cols["mem_gb"]

    def __len__(self) -> int:
        return len(self.mem_gb)


def _from_files(cell, files: list):
    """The cell with its traces as the files hold them."""
    view = copy.copy(cell)
    view.traces = [_FileTrace(cols) for cols in files]
    view.columns = files.__getitem__
    return view


def _off_vms(cols: dict, vms: list) -> int:
    """VMs whose four columns differ between the file and the
    program; every VM where the counts differ."""
    if len(vms) != len(cols["arrival"]):
        return max(len(vms), len(cols["arrival"]))
    off = np.zeros(len(vms), bool)
    for name in csvtrace.COLUMNS:
        off |= np.array([getattr(vm, name) for vm in vms], float) \
            != cols[name]
    return int(off.sum())


def check(cell, outputs: list) -> dict:
    """Compared numbers, each ``(value, limit)``."""
    files = [csvtrace.read(p) for p in trace_files(cell)]
    off = max(_off_vms(cols, vms) for out in outputs
              for cols, vms in zip(files, out["vms"]))
    return {"ingest_off_vms": (off, 0),
            **frontier.check(_from_files(cell, files), outputs)}


def control(cell) -> list:
    """The reference in the program's place, with memory counted in
    2 GB slices, as ``answers.frontier.control``; the plain reader's
    rows stand in for the ingested VMs."""
    files = [csvtrace.read(p) for p in trace_files(cell)]
    outs = frontier.control(_from_files(cell, files))
    for out in outs:
        out["vms"] = [[types.SimpleNamespace(**dict(zip(csvtrace.COLUMNS,
                                                        row)))
                       for row in zip(*(cols[c] for c in csvtrace.COLUMNS))]
                      for cols in files]
    return outs

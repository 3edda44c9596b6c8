"""Plain reader of a VM trace file, for the check of the ingest layer.
It imports nothing of the program.

A file is CSV, gzipped where its name ends in ``.gz``, whose header row
names at least the columns ``arrival``, ``lifetime``, ``cores`` and
``mem_gb``, as ``traces.save_trace_csv`` writes them.  The rows come
back in arrival order, ties in file order: the order in which a replay
takes the VMs.
"""
from __future__ import annotations

import csv
import gzip

import numpy as np

COLUMNS = ("arrival", "lifetime", "cores", "mem_gb")


def read(path) -> dict:
    """The file's four columns as float arrays, in arrival order."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt", newline="") as f:
        rows = list(csv.DictReader(f))
    cols = {name: np.array([float(row[name]) for row in rows])
            for name in COLUMNS}
    order = np.argsort(cols["arrival"], kind="stable")
    return {name: col[order] for name, col in cols.items()}

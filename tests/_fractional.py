"""Non-integral replay decisions, the input on which the replay engines
take their float64 numpy sweep (and, for failures, the scalar oracle)."""
from __future__ import annotations

import dataclasses


def shift_half(decisions):
    """Each decision with 0.5 GB moved between its local and its pool
    share: into the pool, or out of it where nothing is local.  The
    policy's split and QoS migrations are otherwise kept."""
    out = []
    for d in decisions:
        step = 0.5 if d.local_gb >= 0.5 else -0.5
        out.append(dataclasses.replace(d, local_gb=d.local_gb - step,
                                       pool_gb=d.pool_gb + step))
    return out

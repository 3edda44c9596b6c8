"""Seconds per answer in the policy layer
(``cluster_sim.policy_decisions``), by the benchmark's clock around the
call."""


def read(ctx):
    s = ctx["layers"].get("policy")
    return None if s is None else s / ctx["answers"]

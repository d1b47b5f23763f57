"""Planner: the share of the SLO that the plan's split leaves unspent,
``1 - Plan.e2e_latency / slo`` (moves ``plan_cost``)."""


def read(run):
    slo = run.plan.workload.slo
    return 100.0 * (1.0 - run.plan.e2e_latency / slo)

"""Batched launches per history query, the anchor fixpoint included.

``len(hop_stats) + 1`` of each ``run_plan_batched`` result (one launch per
plan level plus the anchor), averaged over the window's queries: the
planner's choice, which ``history_query_s`` follows.
"""


def read(run):
    launches = run.counters.get("launches")
    return sum(launches) / len(launches) if launches else None

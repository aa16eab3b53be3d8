"""Fixpoint sweeps per history query: the critical path through the plan.

For each query, the anchor fixpoint's sweeps plus, per level launch, the
slowest lane's sweeps (``StreamStats.sweeps``), averaged over
the window's queries. Moves ``history_query_s``.
"""


def read(run):
    sweeps = run.counters.get("sweeps")
    return sum(sweeps) / len(sweeps) if sweeps else None

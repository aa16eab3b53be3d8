"""Valid lanes per batched launch over the window's launches (saturated cell).

Read from the service's ``launch_log`` (``LaunchRecord.lanes``), the
warm-up's launches excluded. Moves ``queries_per_s``: fuller launches answer
more queries per device pass.
"""


def read(run):
    lanes = run.counters.get("launch_lanes")
    return sum(lanes) / len(lanes) if lanes else None

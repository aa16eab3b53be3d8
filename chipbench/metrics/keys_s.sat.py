"""Host seconds of the store's set algebra per answered query (saturated service cell).

Self time of the program's ``cg.store.keys`` spans (``window_keys``
intersections on a miss, ``delta_keys`` differences; ``spans.py``) in
the traced window, over the queries answered in it. Moves
``queries_per_s``: the device waits while the host intersects key sets.
"""

import spans


def read(run):
    found = spans.of_run(run)
    answered = run.counters.get("answered")
    if found is None or not answered:
        return None
    return spans.self_seconds(found, "cg.store.keys") / answered

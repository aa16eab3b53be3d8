"""Host seconds of the store's block building per answered query (saturated service cell).

Self time of the program's ``cg.store.blocks`` spans (key decode,
weights, dst sort, padding and upload of a block on a cache miss; the
set algebra nested in them subtracted; ``spans.py``) in the traced
window, over the queries answered in it. Moves ``queries_per_s``.
"""

import spans


def read(run):
    found = spans.of_run(run)
    answered = run.counters.get("answered")
    if found is None or not answered:
        return None
    return spans.self_seconds(found, "cg.store.blocks") / answered

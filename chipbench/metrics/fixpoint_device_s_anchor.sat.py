"""Device seconds per answered query inside the service's anchor fixpoints.

The union of the device-op intervals that fall inside the program's
``cg.anchor.rebuild`` and ``cg.anchor.hop`` spans (``spans.py``), over
the queries answered in the window. Moves ``queries_per_s``: one-lane
anchor fixpoints hold the chip between packed launches.
"""

import spans


def read(run):
    found = spans.of_run(run)
    answered = run.counters.get("answered")
    if found is None or not answered:
        return None
    seconds = spans.device_seconds(run.trace_lib, run.trace_data, found,
                                   spans.ANCHOR)
    return None if seconds is None else seconds / answered

"""Share of the traced window the device idles inside host preparation (saturated service cell).

Device 0's idle time whose innermost open program span, at the gap's
midpoint, is ``cg.store.*`` (``spans.py``), over the window, as a
percentage. Moves ``queries_per_s``: the part of ``idle_share.sat`` that
set algebra and block building hold the chip back for.
"""

import spans


def read(run):
    found = spans.of_run(run)
    if found is None:
        return None
    seconds = spans.idle_seconds(run.trace_lib, run.trace_data, found,
                                 spans.STORE)
    window = run.trace_lib.window_s(run.trace_data)
    return None if seconds is None or not window else 100.0 * seconds / window

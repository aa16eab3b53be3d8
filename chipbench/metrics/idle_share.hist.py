"""Share of the traced window in which no operation ran on the device (history cell).

1 - (union of the device-op intervals) / (window), from the profiler
trace. Moves ``history_query_s``: the host holds the chip back for this share.
"""


def read(run):
    if run.trace_data is None:
        return None
    return run.trace_lib.idle_share(run.trace_data)

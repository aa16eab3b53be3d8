"""Device seconds per history query inside the anchor fixpoints.

The union of the device-op intervals that fall inside the program's
``cg.anchor.rebuild`` and ``cg.anchor.hop`` spans (``spans.py``), over
the window's queries: the one-lane apex fixpoint of each query. Moves
``history_query_s``.
"""

import spans


def read(run):
    found = spans.of_run(run)
    queries = run.counters.get("queries")
    if found is None or not queries:
        return None
    seconds = spans.device_seconds(run.trace_lib, run.trace_data, found,
                                   spans.ANCHOR)
    return None if seconds is None else seconds / queries

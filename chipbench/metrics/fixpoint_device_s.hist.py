"""Device seconds per history query in the fixpoint's XLA modules.

Summed from the trace's module events whose names match ``PATTERNS``
(the anchor's ``_fixpoint_jit`` and the level launches'
``_batched_incremental_jit``), over the window's queries. Moves
``history_query_s``. A trace whose device ran in the window but in no
module of these names stops the run: the fixpoint's programs were renamed
or moved, and the reading would otherwise vanish unseen.
"""

PATTERNS = (r"_fixpoint_jit", r"_batched_incremental_jit")


def read(run):
    queries = run.counters.get("queries")
    if run.trace_data is None or not queries:
        return None
    seconds = run.trace_lib.module_seconds(run.trace_data, PATTERNS)
    if seconds is None:
        if run.trace_lib.busy_s(run.trace_data):
            raise RuntimeError(f"no XLA module in the trace matches {PATTERNS}")
        return None
    return seconds / queries

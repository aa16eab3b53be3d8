"""Share of stacked Δ lanes served from the store's Δ key memo (saturated service cell).

Σ``delta_hit`` / (Σ``delta_hit`` + Σ``delta_miss``) over the program's
``cg.store.blocks`` spans in the traced window (``spans.py``), as a
percentage: each ``delta_stack`` miss counts its lanes whose Δ keys the
memo held and those it computed. Moves ``queries_per_s``: a computed lane
is a ``setdiff1d`` of two window key sets while the device waits. Reads
nothing when no span carries the counters (a program without the memo).
"""

import spans

BLOCKS = "cg.store.blocks"


def read(run):
    counted = [s for s in spans.of_run(run) or ()
               if s.name == BLOCKS and "delta_hit" in s.args]
    if not counted:
        return None
    hit = spans.total(counted, BLOCKS, "delta_hit")
    return 100.0 * hit / (hit + spans.total(counted, BLOCKS, "delta_miss"))

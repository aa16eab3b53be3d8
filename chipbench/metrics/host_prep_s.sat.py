"""Host seconds of store preparation per query answered in the window.

The time inside the outermost calls of the store's set algebra and block
building (``run.PREP_METHODS``, timed by the benchmark's store subclass)
during the window, over the queries answered in it. Moves
``queries_per_s``: the device waits while the host prepares a launch.
"""


def read(run):
    answered = run.counters.get("answered")
    return run.window_prep_s / answered if answered else None

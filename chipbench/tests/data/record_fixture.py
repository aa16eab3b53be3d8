"""Record ``fixture.xplane.pb``: a small TPU trace with the benchmark's host spans.

    python3 chipbench/tests/data/record_fixture.py <log dir>

Inside ``cb.window``: three rounds of ``cb.turn`` (five small jitted
matmuls, then 20 ms of ``cb.store`` and one jitted cumsum) and ``cb.wait``
(100 ms asleep). The ``.xplane.pb`` lands under
``<log dir>/plugins/profile/<time>/``. Runs only on a TPU.
"""

import sys
import time

import jax
import jax.numpy as jnp


def main(log_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_fixture: needs a TPU")
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    g = jax.jit(lambda x: jnp.cumsum(x * 2.0, axis=0))
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("cb.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("cb.turn"):
                for _ in range(5):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("cb.store"):
                    time.sleep(0.02)
                g(x).block_until_ready()
            with jax.profiler.TraceAnnotation("cb.wait"):
                time.sleep(0.1)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])

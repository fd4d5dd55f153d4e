"""Records ``data/cpu_tiny.xplane.pb``, the small trace the reduction
test reads: a tiny jitted program run three times on the CPU inside a
``bench:window`` span, with ``bench:`` host spans around its calls and
host-only sleeps between them (idle gaps of known cause).

    JAX_PLATFORMS=cpu python bench/tests/record_trace.py
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "data", "cpu_tiny.xplane.pb")


def record(out: str = OUT) -> str:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    step = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    a = jnp.ones((192, 192), jnp.float32)
    step(a).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with TraceAnnotation("bench:window"):
            for _ in range(3):
                with TraceAnnotation("bench:decode"):
                    step(a).block_until_ready()
                with TraceAnnotation("bench:host_wait"):
                    time.sleep(0.02)
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)[0]
        shutil.copy(src, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


if __name__ == "__main__":
    print(record(sys.argv[1] if len(sys.argv) > 1 else OUT))

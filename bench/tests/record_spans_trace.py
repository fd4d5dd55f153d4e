"""Records ``data/cpu_spans.xplane.pb``, the small trace the span readers'
test reads (``bench/spans.py``): three passes of a loop shaped like the
program's serving loop, run on the CPU inside a ``bench:window`` span.
Each pass is a ``dali:serve.step`` span holding a ``dali:serve.decode``
child (a tiny jitted program whose host callback opens a
``dali:store.fetch_weights`` span and sleeps 10 ms) and a
``dali:serve.tokens`` child, then 20 ms of host-only sleep in no child:
the loop's own time, with nothing running on the device.

    JAX_PLATFORMS=cpu python bench/tests/record_spans_trace.py
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "data", "cpu_spans.xplane.pb")
SLEEP_S = 0.02          # the loop's own time in each pass
FETCH_S = 0.01          # host time inside each callback


def record(out: str = OUT) -> str:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    def fetch(x):
        with TraceAnnotation("dali:store.fetch_weights", layer=0,
                             miss_rows=1, bytes=x.nbytes):
            time.sleep(FETCH_S)
            return np.asarray(x) + 1

    @jax.jit
    def step(a):
        b = jax.pure_callback(fetch, jax.ShapeDtypeStruct(a.shape, a.dtype),
                              a)
        return jnp.tanh(b @ b).sum()

    a = jnp.ones((192, 192), jnp.float32)
    step(a).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with TraceAnnotation("bench:window"):
            for i in range(3):
                with TraceAnnotation("dali:serve.step", step=i, live=1):
                    with TraceAnnotation("dali:serve.decode", step=i):
                        res = step(a)
                    with TraceAnnotation("dali:serve.tokens", step=i):
                        res.block_until_ready()
                    time.sleep(SLEEP_S)
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)[0]
        shutil.copy(src, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


if __name__ == "__main__":
    print(record(sys.argv[1] if len(sys.argv) > 1 else OUT))

"""Record the small chip trace that ``test_trace.py`` reads (run on the
chip once; the result is committed under ``fixtures/``).

    python3 benchmark/tests/record_fixture.py <out-dir>

A warm jitted ``step`` runs three times inside a ``bench.window``
annotation, each run inside a ``bench.job`` annotation and followed by
20 ms of host sleep, so the window holds three device busy stretches and
three idle gaps of about 20 ms labelled ``bench.window``.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main(out: str) -> None:
    @jax.jit
    def step(x):
        return jnp.tanh(x @ x) + 1.0

    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    tmp = os.path.join(out, "raw")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.job"):
                step(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, os.path.join(out, "chip_step.xplane.pb"))
    shutil.rmtree(tmp)
    print(jax.devices()[0].device_kind, os.path.getsize(os.path.join(out, "chip_step.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])

"""Record the small TPU trace the reduction's test reads: three calls of
the program's qmm kernel and three fused XLA ops inside the benchmark's
window annotation, the host sleeping 2 ms between them.

    python bench/tools/record_trace.py out/small_trace

Prints the reduction of what it recorded, for the test to pin.
"""
from __future__ import annotations

import glob
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from yardstick import trace as tr  # noqa: E402


def main() -> int:
    from repro.kernels import ops as kops
    from repro.qtensor import quantize
    out = Path(sys.argv[1])
    rng = np.random.default_rng(0)
    w = quantize(jnp.asarray(rng.normal(size=(2048, 2048)), jnp.float32), 4,
                 group_size=128)
    xq = jnp.asarray(rng.integers(-127, 128, (8, 2048)), jnp.int8)
    xs = jnp.full((8, 1), 0.01, jnp.float32)
    qmm = jax.jit(lambda x, s: kops.qmm(x, w, s))
    fused = jax.jit(lambda a: jnp.tanh(a) * 2 + 1)
    a = jnp.ones((1024, 1024))
    qmm(xq, xs).block_until_ready()
    fused(a).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for _ in range(3):
            qmm(xq, xs).block_until_ready()
            time.sleep(0.002)
            fused(a).block_until_ready()
    jax.profiler.stop_trace()
    path = max(glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True),
               key=lambda p: Path(p).stat().st_mtime)
    red = tr.reduce_trace(path)
    print(path, Path(path).stat().st_size)
    print(json.dumps(red, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

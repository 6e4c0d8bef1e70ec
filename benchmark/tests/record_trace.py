"""Record a SMALL device trace on the chip for the trace reducer's test
(``python -m benchmark.tests.record_trace``): a few steps of a matmul,
a top-k and, with two or more chips, an all-reduce, inside host spans
named as the harness names them. Prints what the trace holds (planes,
lines, first event names) and leaves the ``.xplane.pb`` under
``chiprun_out/trace_probe/``."""

from __future__ import annotations

import glob
import os
import time


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from geomx_tpu.runtime import require_tpu

    require_tpu()
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:2] if len(devs) >= 2 else devs[:1]), ("dp",))

    @jax.jit
    def step(x, w):
        y = jnp.tanh(x @ w)
        _m, i = jax.lax.top_k(jnp.abs(y.reshape(-1)), 64)
        return y.mean() + i.sum()

    x = jax.device_put(jnp.ones((512, 512), jnp.bfloat16),
                       NamedSharding(mesh, P("dp")))
    w = jax.device_put(jnp.ones((512, 512), jnp.bfloat16),
                       NamedSharding(mesh, P()))
    step(x, w).block_until_ready()
    out = "chiprun_out/trace_probe"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for r in range(3):
            with jax.profiler.TraceAnnotation(f"bench.step w0 r{r}"):
                step(x, w).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    print(path, os.path.getsize(path), "bytes")
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs),
                  [(e.name[:50], e.start_ns, e.duration_ns)
                   for e in evs[:6]])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

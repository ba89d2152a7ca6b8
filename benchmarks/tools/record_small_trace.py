#!/usr/bin/env python3
"""Record the small trace the reduction's test reads
(``tests/benchmarks/data/small.xplane.pb``): three steps of a tiny
sharded matmul with a ``psum`` over all chips, each inside a
``bench.step`` annotation, a host sleep between them (an idle gap that an
annotation names) and the ``bench.window`` annotation around it all.

    python3 benchmarks/tools/record_small_trace.py <out dir>

Run on the four-chip host, so that the trace holds a collective."""

import glob
import os
import shutil
import sys
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("data",))
    x = jax.device_put(jnp.ones((len(devs) * 256, 512), jnp.bfloat16),
                       NamedSharding(mesh, P("data", None)))
    w = jax.device_put(jnp.ones((512, 512), jnp.bfloat16),
                       NamedSharding(mesh, P()))

    def body(x, w):
        y = jnp.tanh(x @ w)
        return jax.lax.psum(y.astype(jnp.float32).sum(0), "data")

    step = jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(P("data", None), P()),
                             out_specs=P()))
    step(x, w).block_until_ready()
    tmp = os.path.join(out_dir, "_raw")
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.step", i=i):
                step(x, w).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    shutil.copy(found, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    print(os.path.getsize(os.path.join(out_dir, "small.xplane.pb")),
          "bytes,", len(devs), devs[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1])

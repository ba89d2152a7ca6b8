"""Multi-chip SPMD training: dp x tp mesh with ZeRO-1 sharded moments.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/train_multichip.py

On real hardware drop the env overrides — the same script runs over
the chips jax reports (`chip_smoke.py --chips 4` runs this engine at
BERT-base width on a four-chip host). The engine compiles ONE SPMD
executable: feeds batch-shard over 'data', the fc weights
column/row-shard over 'model' (megatron-style), every Adam moment
shards 1/N over 'data' (ZeRO-1), and XLA inserts the
all-reduces/gathers. For pipeline stages, MoE
experts, or ring-attention sequence parallelism see
docs/PARALLELISM.md — they ride the same engine.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.parallel import ParallelEngine, ShardingRules
from paddle_tpu.parallel.engine import make_mesh
from paddle_tpu.parallel.sharding import P


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=64)
    args = ap.parse_args()
    fluid.flags.enable_compile_cache()

    import jax

    devs = jax.devices()
    tp = 2 if len(devs) % 2 == 0 and len(devs) > 1 else 1
    mesh = make_mesh(devs, ("data", "model"), (len(devs) // tp, tp))
    print("mesh:", dict(mesh.shape))

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        x = layers.data("x", [256], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, 512, act="relu")    # column-parallel
        h = layers.fc(h, 256, act="relu")    # row-parallel
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(pred - y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)

    rules = ShardingRules([
        (r"fc_0\.w_0", P(None, "model")),
        (r"fc_1\.w_0", P("model", None)),
    ], zero1=True)

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    engine = ParallelEngine(main_prog, loss_name=loss.name, mesh=mesh,
                            rules=rules)

    rs = np.random.RandomState(0)
    w = rs.randn(256, 1).astype("float32")
    for i in range(args.steps):
        xb = rs.randn(args.batch, 256).astype("float32")
        (l,) = engine.run({"x": xb, "y": xb @ w}, [loss])
        if i % 5 == 0:
            print("step %d loss %.4f" % (i, float(np.asarray(l))))
    print("final loss %.5f" % float(np.asarray(l)))


if __name__ == "__main__":
    main()

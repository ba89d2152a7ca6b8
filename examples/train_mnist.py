"""Minimal end-to-end training: MNIST conv net, the book flow.

    python examples/train_mnist.py [--steps N]

Runs on the accelerator JAX finds; JAX_PLATFORMS=cpu runs it on the CPU
on purpose (TPUPlace refuses a CPU nobody asked for).

Covers the core loop a reference (Fluid) user knows: build a Program
with layers, minimize, run startup, feed batches, save/load an
inference model. The whole train step (forward+backward+Adam) compiles
to ONE XLA executable with donated parameter buffers.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--outdir", default="/tmp/mnist_model")
    args = ap.parse_args()
    fluid.flags.enable_compile_cache()

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        img = layers.data("img", [1, 28, 28], dtype="float32")
        label = layers.data("label", [1], dtype="int64")
        h = layers.conv2d(img, num_filters=16, filter_size=5, act="relu")
        h = layers.pool2d(h, pool_size=2, pool_stride=2)
        h = layers.conv2d(h, num_filters=32, filter_size=5, act="relu")
        h = layers.pool2d(h, pool_size=2, pool_stride=2)
        probs = layers.fc(h, size=10, act="softmax")
        loss = layers.mean(layers.cross_entropy(probs, label))
        acc = layers.accuracy(probs, label)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)

    from paddle_tpu.dataset import mnist

    train = fluid.reader.batch(mnist.train(), args.batch, drop_last=True)
    step = 0
    for epoch in range(100):
        for samples in train():
            imgs = np.stack([s[0].reshape(1, 28, 28) for s in samples])
            lbls = np.array([[s[1]] for s in samples], dtype="int64")
            l, a = exe.run(main_prog, feed={"img": imgs, "label": lbls},
                           fetch_list=[loss, acc])
            step += 1
            if step % 20 == 0 or step == 1:
                print("step %d loss %.4f acc %.3f"
                      % (step, float(np.asarray(l).reshape(-1)[0]),
                         float(np.asarray(a).reshape(-1)[0])))
            if step >= args.steps:
                break
        if step >= args.steps:
            break

    fluid.io.save_inference_model(args.outdir, ["img"], [probs], exe,
                                  main_prog)
    print("inference model saved to", args.outdir)


if __name__ == "__main__":
    main()

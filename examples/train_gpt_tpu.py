"""TPU best-practice training: GPT causal LM with every perf lever on.

    python examples/train_gpt_tpu.py [--layers N] [--windows N]

What this shows a reference (Fluid) user switching to this framework:

- bf16 AMP           (main.set_amp(True) — f32 master weights)
- fused attention    (Pallas causal flash kernel, automatic)
- AdamW + cosine LR  (decoupled decay, LN/bias exempt)
- recompute          (per-layer checkpoints via RecomputeOptimizer)
- K-step windows     (PyReader.windows -> run_repeated: K REAL
                      minibatches per device dispatch; what it buys is
                      not measured on the current code — PERF.md)
- async checkpoints  (save_persistables_async overlaps the write)

Synthetic data (env has no egress); swap `gen` for a real corpus
reader. Defaults are tiny; scale --d-model/--layers/--seq up on real
hardware. Runs on the accelerator JAX finds; JAX_PLATFORMS=cpu runs it
on the CPU on purpose.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import gpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--windows", type=int, default=6,
                    help="number of K-step windows to train")
    ap.add_argument("--k", type=int, default=8, help="steps per window")
    ap.add_argument("--ckpt", default="/tmp/gpt_ckpt")
    args = ap.parse_args()
    fluid.flags.enable_compile_cache()

    # the full modern-decoder stack: RMSNorm, SwiGLU, RoPE, GQA — all
    # compose with the causal flash kernel and the decode cache
    cfg = dict(d_model=args.d_model, d_ff=4 * args.d_model, n_head=4,
               n_kv_head=2, n_layer=args.layers, vocab=1024,
               max_length=args.seq, dropout=0.1, pos_emb="rope",
               norm="rms", ffn_act="swiglu")

    ckpts = []
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        # packed=True: variable-length documents pack into fixed rows
        # (block-diagonal attention, per-segment RoPE resets). Packing
        # shrinks the pad fraction — tighten n_rows below toward the
        # actual token count to approach padding-free compute
        loss, feeds = gpt.build(cfg, seq_len=args.seq, checkpoints=ckpts,
                                packed=True)
        lr = layers.cosine_decay(3e-4, step_each_epoch=args.windows *
                                 args.k, epochs=1)
        opt = fluid.optimizer.RecomputeOptimizer(
            fluid.optimizer.AdamW(
                learning_rate=lr, weight_decay=0.1,
                apply_decay_param_fun=lambda n: ".w_0" in n))
        opt._set_checkpoints(ckpts)
        opt.minimize(loss)
    main_prog.set_amp(True)  # bf16 compute, f32 master weights

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)

    rs = np.random.RandomState(0)

    def gen():
        while True:
            docs = [rs.randint(1, cfg["vocab"],
                               rs.randint(args.seq // 4,
                                          args.seq)).tolist()
                    for _ in range(args.batch)]
            f = fluid.reader.pack_sequences(docs, args.seq,
                                            n_rows=args.batch)
            yield (f["ids"], f["segment_ids"], f["pos_ids"])

    feed_vars = [main_prog.global_block().var(n) for n in feeds]
    reader = layers.PyReader(feed_list=feed_vars, capacity=16)
    reader.decorate_batch_generator(gen)

    pending = None
    n = 0
    t0 = time.time()
    for window, steps in reader.windows(args.k):
        vals = exe.run_repeated(main_prog, feed=window, fetch_list=[loss],
                                steps=steps, feed_stacked=True)
        n += 1
        print("window %d (%d steps) loss %.4f"
              % (n, steps, float(np.asarray(vals[0]).reshape(-1)[0])))
        # checkpoint every other window; the write overlaps training
        if n % 2 == 0:
            if pending is not None:
                pending.wait()
            pending = fluid.io.save_persistables_async(
                exe, args.ckpt, main_prog)
        if n >= args.windows:
            break
    if pending is not None:
        pending.wait()
    dt = time.time() - t0
    toks = n * args.k * args.batch * args.seq
    print("done: %d token-slots in %.1fs (%.0f slots/s, packed rows); "
          "checkpoint at %s" % (toks, dt, toks / dt, args.ckpt))


if __name__ == "__main__":
    main()

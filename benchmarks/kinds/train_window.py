"""Runner kind ``train_window``: masked-LM training on one chip through
``Executor.run_repeated(steps=K, feed_stacked=True)``, every window ending
in a fetched loss. The loop is ``benchmarks/lib/train_loop.py``."""

from benchmarks.lib import train_loop


class Step:
    def __init__(self, main, loss, scope, exe, steps):
        self.main, self.loss, self.scope, self.exe = main, loss, scope, exe
        self.steps = steps

    def __call__(self, feed):
        return self.exe.run_repeated(
            self.main, feed=feed, fetch_list=[self.loss], scope=self.scope,
            steps=self.steps, feed_stacked=True)

    def hlo(self, one_step_feed):
        return self.exe.lowered_hlo(
            self.main, feed=one_step_feed, fetch_list=[self.loss],
            scope=self.scope, stage="stablehlo")

    def check(self, scope):
        return []


def run(ctx):
    steps = ctx.traffic["steps_per_window"]
    return train_loop.run(
        ctx, lambda main, loss, scope, exe: Step(main, loss, scope, exe,
                                                 steps))

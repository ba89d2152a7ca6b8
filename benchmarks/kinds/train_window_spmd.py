"""Runner kind ``train_window_spmd``: the same masked-LM window sharded
over the cell's chips through ``ParallelEngine.run_repeated`` on a
``data`` x ``model`` mesh, as ``chip_smoke.py --chips 4`` builds it."""

from benchmarks.lib import train_loop


class Step:
    def __init__(self, main, loss, scope, devices, mesh_shape, steps):
        from paddle_tpu.parallel import ParallelEngine, ShardingRules
        from paddle_tpu.parallel.engine import make_mesh

        self.loss, self.scope, self.steps = loss, scope, steps
        self.n_devices = len(devices)
        self.engine = ParallelEngine(
            main, loss_name=loss.name,
            mesh=make_mesh(devices, ("data", "model"), tuple(mesh_shape)),
            rules=ShardingRules())

    def __call__(self, feed):
        return self.engine.run_repeated(
            feed, [self.loss], self.scope, steps=self.steps,
            feed_stacked=True)

    def hlo(self, one_step_feed):
        return self.engine.lowered_hlo(one_step_feed, [self.loss],
                                       self.scope, stage="stablehlo")

    def check(self, scope):
        """Every chip of the mesh holds the (replicated) weights."""
        why_not = []
        if self.engine.device_count != self.n_devices:
            why_not.append("mesh has %d devices, the cell %d"
                           % (self.engine.device_count, self.n_devices))
        arr = scope.find_var("word_embedding")
        held = {s.device for s in arr.addressable_shards}
        if len(held) != self.n_devices:
            why_not.append("word_embedding lives on %d of %d devices"
                           % (len(held), self.n_devices))
        return why_not


def run(ctx):
    tr = ctx.traffic
    return train_loop.run(
        ctx, lambda main, loss, scope, exe: Step(
            main, loss, scope, ctx.devices, tr["mesh_shape"],
            tr["steps_per_window"]))

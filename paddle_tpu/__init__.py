"""paddle_tpu: a TPU-native deep-learning framework.

A from-scratch rebuild of the reference graph-program framework
(/root/reference, PaddlePaddle Fluid v1.3-era) designed TPU-first:

* Python builds a Program (blocks of ops) — same control plane as the
  reference (SURVEY §1) — but the Executor lowers a whole block to ONE XLA
  computation instead of interpreting ops, so fusion/layout/memory/GC are
  the compiler's job, not a runtime's.
* Gradients are graph ops appended by append_backward; their lowerings come
  mechanically from jax.vjp of the forward lowerings.
* Data parallelism is SPMD over a jax.sharding.Mesh (CompiledProgram
  .with_data_parallel); collectives ride ICI via XLA, replacing the
  reference's NCCL op-handle engine.

Import as `import paddle_tpu as fluid` — the API surface mirrors
python/paddle/fluid.
"""

from . import ops as _ops  # registers all op lowerings  # noqa: F401
from . import analysis  # attaches shape rules + exposes the verifier  # noqa: F401
from . import (  # noqa: F401
    backward,
    clip,
    initializer,
    io,
    layers,
    metrics,
    nets,
    observe,
    optimizer,
    profiler,
    regularizer,
)
from . import (contrib, flags, imperative, inference,  # noqa: F401
               kernels, learning_rate_decay, lod_tensor, reader,
               recordio_writer, resilience, transpiler)
from .lod_tensor import (LoDTensor, LoDTensorArray, Tensor,  # noqa: F401
                         create_lod_tensor, create_random_int_lodtensor)
from .reader import batch  # noqa: F401  (paddle.batch top-level parity)
from .flags import get_flag, set_flag  # noqa: F401
from .async_executor import AsyncExecutor, DataFeedDesc  # noqa: F401
from .compiler import (BuildStrategy, CompiledProgram,  # noqa: F401
                       ExecutionStrategy, ParallelExecutor)
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa: F401
from .core.executor import Executor  # noqa: F401
from .core.pipeline import (ConstFeedCache, DevicePrefetcher,  # noqa: F401
                            FetchHandle, WindowFeed)
from .core.place import (CPUPlace, CUDAPinnedPlace, CUDAPlace,  # noqa: F401
                         TPUPlace, is_compiled_with_tpu)
from .core.program import (  # noqa: F401
    name_scope,
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    program_guard,
    unique_name,
)
from .core.scope import Scope, global_scope, scope_guard  # noqa: F401
from .data_feeder import DataFeeder  # noqa: F401
from . import (average, compat, data_feed_desc, debugger,  # noqa: F401
               distribute_lookup_table, evaluator, graphviz, net_drawer,
               utils)
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401

from . import version  # noqa: F401
__version__ = version.full_version

# reference-parity alias: user code does `fluid.io.save_params(...)` etc.
name = "paddle_tpu"

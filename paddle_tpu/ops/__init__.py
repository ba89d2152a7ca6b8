"""Op lowerings — importing this package registers every op.

The registry is the analog of the reference's static kernel registry
(paddle/fluid/framework/op_registry.h); modules here mirror the
operators/ directory layout (SURVEY §2.2).
"""

from . import (  # noqa: F401
    activations,
    attention,
    basic,
    beam_search_ops,
    control_flow_ops,
    delta_ops,
    detection_ops,
    distributed_ops,
    fused_ops,
    loss_ops,
    mamba_ops,
    math,
    metrics,
    misc_ops,
    moe_ops,
    nn,
    quant_ops,
    recompute_ops,
    rnn,
    optimizer_ops,
    pipeline_ops,
    power_ops,
    scan_ops,
    sequence,
    ssm_ops,
    tensor_ops,
)

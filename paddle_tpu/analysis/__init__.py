"""Static program verifier: shape/dtype inference + IR lint passes.

The compile-time checking layer the reference got from per-op
``InferShape`` + OpDesc validation (framework/shape_inference.h), rebuilt
for whole-block XLA lowering: importing this package attaches shape rules
for the core op vocabulary to the registry's ``infer_shape`` hook, and

* ``Program.validate()`` / ``verify_program`` run inference + the lint
  suite, fill inferred shapes back onto Variables, and raise
  ``ProgramVerifyError`` (op type, name-scope, definition site) on
  errors;
* the Executor runs the same check at prepare time when
  ``PADDLE_TPU_VALIDATE=1`` (tests/conftest.py turns it on suite-wide);
* ``tools/lint_program.py`` is the CLI; ``paddle_analysis_*`` observe
  families count programs checked, findings by rule, and verify time.

See docs/ANALYSIS.md for the rule catalog and how to write a rule.
"""

from . import range_rules  # noqa: F401  (attaches the transfer set)
from . import shape_rules  # noqa: F401  (attaches the core rule set)
from .cost import (CostAnalysis, DeviceModel,  # noqa: F401
                   predict_step_seconds)
from .cost_rules import register_cost_rule  # noqa: F401 (attaches rules)
from .dataflow import Dataflow  # noqa: F401
from .distributed import (BARRIER_OPS, WIRE_OPS,  # noqa: F401
                          pserver_spec_findings, shard_fit_report,
                          validate_distributed, validate_transpile)
from .infer import (DIST_RULES, Finding, InferContext,  # noqa: F401
                    InferError, ProgramVerifyError,
                    infer_program_shapes, validation_enabled,
                    verify_program)
from .lint import LINT_RULES, lint_program  # noqa: F401
from .memory import (BytesPoly, MemoryAnalysis,  # noqa: F401
                     decode_cache_bytes, device_budget,
                     estimate_peak_bytes, register_footprint_rule)
from .ranges import (AbstractValue, Calibration,  # noqa: F401
                     RangeAnalysis, RangeContext, register_range_rule)
from .tv import (ProgramSnapshot, RewriteViolation,  # noqa: F401
                 describe_rewrites, tv_enabled, validate_rewrite)

__all__ = [
    "AbstractValue",
    "BARRIER_OPS",
    "BytesPoly",
    "Calibration",
    "CostAnalysis",
    "DIST_RULES",
    "Dataflow",
    "DeviceModel",
    "Finding",
    "InferContext",
    "InferError",
    "LINT_RULES",
    "MemoryAnalysis",
    "ProgramSnapshot",
    "ProgramVerifyError",
    "RangeAnalysis",
    "RangeContext",
    "RewriteViolation",
    "WIRE_OPS",
    "decode_cache_bytes",
    "describe_rewrites",
    "device_budget",
    "estimate_peak_bytes",
    "infer_program_shapes",
    "lint_program",
    "predict_step_seconds",
    "pserver_spec_findings",
    "register_cost_rule",
    "register_footprint_rule",
    "register_range_rule",
    "shard_fit_report",
    "tv_enabled",
    "validate_distributed",
    "validate_rewrite",
    "validate_transpile",
    "validation_enabled",
    "verify_program",
]

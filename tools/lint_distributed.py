#!/usr/bin/env python
"""Statically verify transpiled distributed jobs from the command line.

The CLI face of ``paddle_tpu.analysis.validate_distributed``: builds one
or more example model programs (the same tiny model-zoo configs
tools/lint_program.py serves), runs ``DistributeTranspiler`` over each
at a configurable world size, and verifies the whole job — wire typing,
partition coverage, deadlock/ordering, cross-program translation
validation, and the per-pserver memory proof when
``PADDLE_TPU_DEVICE_HBM_BYTES`` is set — before anything launches.

    python tools/lint_distributed.py                    # all examples
    python tools/lint_distributed.py --model gpt ctr    # a subset
    python tools/lint_distributed.py --trainers 4 --pservers 3
    python tools/lint_distributed.py --json             # machine-readable

Exit code: 0 = every job verified with no error findings, 1 = at least
one error, 2 = bad usage. Findings count at ``site=cli`` in the
``paddle_analysis_dist_*`` observe families.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lint_program import EXAMPLE_BUILDERS, build_example  # noqa: E402

SEVERITY_ORDER = {"info": 0, "warning": 1, "error": 2}


def _endpoints(n: int, base_port: int = 6170) -> str:
    return ",".join("127.0.0.1:%d" % (base_port + i) for i in range(n))


def verify_example_distributed(name, trainers=2, pservers=2):
    """Build example ``name``, transpile at trainers x pservers, verify.
    Returns the flat Finding list (never raises)."""
    import paddle_tpu as fluid
    from paddle_tpu.analysis import validate_distributed

    main, startup, _loss = build_example(name)
    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, pservers=_endpoints(pservers),
                trainers=trainers, sync_mode=True, startup_program=startup)
    return validate_distributed(t, raise_on_error=False, site="cli")


def main(argv=None):
    p = argparse.ArgumentParser(
        description="cross-program distributed-job verifier over example "
                    "model programs")
    p.add_argument("--model", nargs="*", choices=sorted(EXAMPLE_BUILDERS),
                   help="examples to verify (default: all)")
    p.add_argument("--trainers", type=int, default=2,
                   help="trainer count to transpile for (default 2)")
    p.add_argument("--pservers", type=int, default=2,
                   help="pserver count to transpile for (default 2)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON document instead of text")
    p.add_argument("--min-severity", choices=("info", "warning", "error"),
                   default="info", help="hide findings below this severity")
    args = p.parse_args(argv)
    names = args.model or sorted(EXAMPLE_BUILDERS)
    floor = SEVERITY_ORDER[args.min_severity]

    any_error = False
    doc = {}
    for name in names:
        findings = verify_example_distributed(
            name, trainers=args.trainers, pservers=args.pservers)
        shown = [f for f in findings
                 if SEVERITY_ORDER[f.severity] >= floor]
        any_error |= any(f.severity == "error" for f in findings)
        if args.json:
            doc[name] = [{"rule": f.rule, "severity": f.severity,
                          "message": f.message, "op_type": f.op_type,
                          "var": f.var, "def_site": f.def_site}
                         for f in shown]
        else:
            verdict = ("FAIL" if any(f.severity == "error"
                                     for f in findings) else "ok")
            print("%-20s %dx%d  %s (%d finding(s))"
                  % (name, args.trainers, args.pservers, verdict,
                     len(shown)))
            for f in shown:
                print("    " + f.format())
    if args.json:
        print(json.dumps(doc, indent=2, sort_keys=True))
    return 1 if any_error else 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())

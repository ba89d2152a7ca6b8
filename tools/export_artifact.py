#!/usr/bin/env python
"""Build, inspect and validate deployable artifacts from the command
line — the CLI face of ``paddle_tpu.export`` (docs/DEPLOYMENT.md).

    python tools/export_artifact.py --model mnist --out mnist.pdz
    python tools/export_artifact.py --model mnist --out m.pdz \\
        --buckets 1,8 --no-aot
    python tools/export_artifact.py --inspect mnist.pdz
    python tools/export_artifact.py --validate mnist.pdz

``--model`` freezes one of the model-zoo forward-only programs (the
same tiny configs lint_program.py verifies — builders are shared, not
duplicated): startup-initialized
weights, inference rewrite, live-config optimize with TV forced on,
params checksummed, memory polynomial and (unless
``--no-aot``) one jax.export executable per ``--buckets`` entry.

``--inspect`` prints the manifest without rehydrating anything: format
version, sections with their sha256 prefixes and sizes, the frozen
config_key, per-var param checksums and the predicted peak bytes per
bucket. ``--validate`` runs the full load-time validation ladder
(container, config_key, section checksums, TV digest, per-var param
checksums) and exits 1 on any skew — the pre-deploy gate a rollout
pipeline runs before pointing ``ReplicaRouter.roll`` at a file.

Exit code: 0 = built/clean, 1 = skew or corruption detected, 2 = bad
usage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lint_program import EXAMPLE_BUILDERS, build_example  # noqa: E402


def _build(args) -> int:
    import paddle_tpu as fluid
    from paddle_tpu import export
    from paddle_tpu.core.scope import Scope, scope_guard

    main, startup, loss = build_example(args.model, optimizer=False)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        feed_names = sorted(
            v.name for v in main.global_block().vars.values()
            if v.is_data)
        path = export.save_artifact(
            main, args.out, feed_names=feed_names,
            fetch_names=[loss.name], scope=scope,
            batch_sizes=tuple(args.buckets),
            aot=False if args.no_aot else None,
            name=args.model)
    size = os.path.getsize(path)
    print("wrote %s (%d bytes): model=%s feeds=%s fetch=%s buckets=%s"
          % (path, size, args.model, ",".join(feed_names), loss.name,
             ",".join(str(b) for b in args.buckets) or "-"))
    return 0


def _inspect(path: str) -> int:
    from paddle_tpu.export.format import read_artifact

    manifest, zf = read_artifact(path)
    try:
        sizes = {i.filename: i.file_size for i in zf.infolist()}
    finally:
        zf.close()
    print("artifact %s" % path)
    print("  name: %s" % manifest.get("name"))
    print("  format_version: %s" % manifest.get("format_version"))
    print("  feeds: %s  fetches: %s  buckets: %s"
          % (",".join(manifest.get("feed_names") or []) or "-",
             ",".join(manifest.get("fetch_names") or []) or "-",
             ",".join(str(b) for b in manifest.get("batch_sizes") or [])
             or "-"))
    print("  optimize_level: %s  exact_numerics: %s"
          % (manifest.get("optimize_level"),
             manifest.get("exact_numerics")))
    print("  config_key: %s" % json.dumps(manifest.get("config_key")))
    if manifest.get("tv_digest"):
        print("  tv_digest: %s" % manifest["tv_digest"][:16])
    if manifest.get("aot_skipped"):
        print("  aot_skipped: %s" % manifest["aot_skipped"])
    print("  sections:")
    checks = manifest.get("checksums") or {}
    for s in manifest.get("sections") or []:
        print("    %-14s %8d bytes  sha256 %s..."
              % (s, sizes.get("section/%s" % s, 0),
                 (checks.get(s) or "")[:16]))
    params = manifest.get("params") or {}
    print("  params: %d vars" % len(params))
    for n in sorted(params):
        rec = params[n]
        print("    %-32s %-10s %-18s sha256 %s..."
              % (n, rec.get("dtype"), "x".join(
                  str(d) for d in rec.get("shape") or []) or "scalar",
                 (rec.get("sha256") or "")[:16]))
    pred = manifest.get("predicted_bytes") or {}
    if pred:
        print("  predicted peak bytes:")
        for b in sorted(pred, key=int):
            print("    batch %-6s %d" % (b, pred[b]))
    return 0


def _validate(path: str) -> int:
    from paddle_tpu import export

    try:
        art = export.load_artifact(path)
    except export.ArtifactSkewError as e:
        print("SKEW (%s): %s" % (e.reason, e), file=sys.stderr)
        return 1
    except export.ArtifactError as e:
        print("INVALID: %s" % e, file=sys.stderr)
        return 1
    print("OK %s: program=%s params=%d aot=%s"
          % (path, "yes" if art.program is not None else "no",
             len(art.params),
             ",".join(str(b) for b in sorted(art.aot)) or "-"))
    for section, reason in art.degraded:
        print("  degraded: %s (%s) -> recompute at serve time"
              % (section, reason))
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(
        description="build / inspect / validate deployable artifacts")
    p.add_argument("--model", choices=sorted(EXAMPLE_BUILDERS),
                   help="freeze this model-zoo example (forward-only)")
    p.add_argument("--out", help="artifact path to write (with --model)")
    p.add_argument("--buckets", default="1,8",
                   help="comma-separated batch-size buckets "
                        "(default: 1,8)")
    p.add_argument("--no-aot", action="store_true",
                   help="skip the AOT executable section")
    p.add_argument("--inspect", metavar="PATH",
                   help="print an artifact's manifest and exit")
    p.add_argument("--validate", metavar="PATH",
                   help="run load-time validation; exit 1 on skew")
    args = p.parse_args(argv)

    if args.inspect:
        return _inspect(args.inspect)
    if args.validate:
        return _validate(args.validate)
    if not args.model or not args.out:
        p.error("either --model + --out, --inspect or --validate "
                "is required")
    try:
        args.buckets = [int(b) for b in args.buckets.split(",") if b]
    except ValueError:
        p.error("--buckets takes comma-separated ints, got %r"
                % args.buckets)
    return _build(args)


if __name__ == "__main__":
    sys.exit(main())

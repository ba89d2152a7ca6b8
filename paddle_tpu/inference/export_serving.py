"""AOT serving artifact: a Python-free deployment format.

The reference's deployment story is a genuinely Python-free C++ engine
(/root/reference/paddle/fluid/inference/api/paddle_api.h:199). The
embedded-CPython shim (native/serving.cc) keeps that API shape but still
sinks with the Python runtime; this module closes the gap the TPU-native
way: `jax.export` serializes the AOT-lowered serving computation to
portable StableHLO bytecode, and `native/pjrt_serving.cc` replays it
through any PJRT C-API plugin (libtpu) with ZERO Python in the serving
process.

Artifact layout (save_serving_artifact):
    manifest.json        bucket shapes/dtypes, param order, platforms
    bucket_<batch>.shlo  serialized StableHLO (jax.export bytecode, one
                         multi-platform module per batch-size bucket)
    params.ptck          weights in the native tensor_store format
                         (native/tensor_store.cc reads it without Python)
    compile_options.pb   serialized xla CompileOptionsProto (the PJRT
                         compile call wants it; generated here so the C
                         loader never needs proto libraries)

Multi-platform modules carry a leading `_platform_index` i32 argument;
the manifest records the platform order so the loader passes the index
matching the plugin it opened.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["save_serving_artifact", "load_serving_artifact",
           "MANIFEST_VERSION"]

MANIFEST_VERSION = 1

# manifest dtype strings <-> the PJRT_Buffer_Type codes the C loader uses
# (pjrt_c_api.h PJRT_Buffer_Type enum order: INVALID, PRED, S8, S16, S32,
# S64, U8..U64, F16, F32, F64, BF16 — pinned here so a header bump can't
# silently renumber what the artifact means). int64 feeds never reach
# this table: the executor narrows them to int32 at the feed boundary
# (core/lowering.py as_jax_dtype), and _bucket_feeds builds the bucket
# shapes from the narrowed on-device dtypes.
_PJRT_TYPE = {"bool": 1, "int8": 2, "int16": 3, "int32": 4, "int64": 5,
              "uint8": 6, "float16": 10, "float32": 11, "float64": 12,
              "bfloat16": 13}


def _bucket_feeds(program, feed_names, batch_size) -> Dict[str, np.ndarray]:
    block = program.global_block()
    feed = {}
    for n in feed_names:
        var = block.var(n)
        shape = [batch_size if (s is None or s < 0) else int(s)
                 for s in (var.shape or ())]
        from ..core.lowering import as_jax_dtype

        feed[n] = np.zeros(shape, np.dtype(as_jax_dtype(var.dtype)))
    return feed


def save_serving_artifact(model_dir: str, out_dir: str,
                          batch_sizes: Sequence[int] = (1,),
                          platforms: Sequence[str] = ("cpu", "tpu")) -> str:
    """Export a save_inference_model directory into the AOT artifact.

    One StableHLO module per batch-size bucket (static shapes — the XLA
    contract); weights ride once in params.ptck. Returns out_dir.
    """
    import jax

    from ..core.executor import analyze_block
    from ..core.scope import scope_guard
    from ..native.tensor_store import save_tensors
    from . import AnalysisConfig, Predictor

    pred = Predictor(AnalysisConfig(model_dir=model_dir))
    program, scope = pred.program, pred.scope
    fetch_names = list(pred.fetch_names)

    os.makedirs(out_dir, exist_ok=True)
    buckets: List[dict] = []
    param_names: Optional[List[str]] = None

    for bs in batch_sizes:
        feed = _bucket_feeds(program, pred.feed_names, bs)
        with scope_guard(scope):
            (feed_names, fetch_names_a, const_state, mut_state,
             pure_written, needs_rng, step) = analyze_block(
                program, sorted(feed), fetch_names, scope)
        if mut_state or pure_written or needs_rng:
            raise ValueError(
                "serving program is not pure (writes state %s/%s or draws "
                "RNG) — export requires an inference-mode program"
                % (mut_state, pure_written))
        if param_names is None:
            param_names = list(const_state)
        elif param_names != list(const_state):
            raise AssertionError("const state differs between buckets")

        def fn(*args):
            feeds = list(args[:len(feed_names)])
            params = list(args[len(feed_names):])
            fetches, _, _, _ = step(feeds, params, [], None)
            return tuple(fetches)

        feed_args = [feed[n] for n in feed_names]
        param_args = [np.asarray(scope.find_var(n)) for n in const_state]
        exported = jax.export.export(
            jax.jit(fn), platforms=list(platforms))(*feed_args, *param_args)

        fname = "bucket_%d.shlo" % bs
        with open(os.path.join(out_dir, fname), "wb") as f:
            # raw StableHLO bytecode: what PJRT_Client_Compile consumes
            f.write(exported.mlir_module_serialized)
        with open(os.path.join(out_dir, fname + ".jaxexp"), "wb") as f:
            # full jax.export blob: the Python-side loader/debugger path
            f.write(exported.serialize())
        buckets.append({
            "batch_size": int(bs),
            "module_file": fname,
            "feed_names": list(feed_names),
            "feed_shapes": [list(feed[n].shape) for n in feed_names],
            "feed_dtypes": [str(feed[n].dtype) for n in feed_names],
            "out_names": list(fetch_names_a),
            "out_avals": [{"shape": list(a.shape), "dtype": str(a.dtype)}
                          for a in exported.out_avals],
        })

    save_tensors(os.path.join(out_dir, "params.ptck"),
                 {n: np.asarray(scope.find_var(n)) for n in param_names})

    from jax._src import compiler as jcompiler

    opts = jcompiler.get_compile_options(num_replicas=1, num_partitions=1)
    with open(os.path.join(out_dir, "compile_options.pb"), "wb") as f:
        f.write(opts.SerializeAsString())

    used_dtypes = ({dt for b in buckets for dt in b["feed_dtypes"]}
                   | {a["dtype"] for b in buckets for a in b["out_avals"]})
    unsupported = sorted(used_dtypes - set(_PJRT_TYPE))
    if unsupported:
        raise TypeError(
            "serving artifact cannot carry dtypes %s (supported: %s)"
            % (unsupported, sorted(_PJRT_TYPE)))
    manifest = {
        "version": MANIFEST_VERSION,
        "platforms": list(platforms),
        "param_names": param_names,
        "pjrt_types": {d: _PJRT_TYPE[d] for d in used_dtypes},
        "buckets": buckets,
    }
    with open(os.path.join(out_dir, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    _write_c_manifest(out_dir, manifest)
    return out_dir


def _write_c_manifest(out_dir: str, manifest: dict) -> None:
    """Whitespace-token twin of manifest.json for the C loader
    (native/pjrt_serving.cc) — fscanf-parseable, no JSON library needed.
    Layout:
        pds-manifest <version>
        platforms <n> <name>...
        params <n> <name>...
        buckets <n>
        bucket <batch_size> <module_file>
        feeds <n>  then per feed:  <name> <pjrt_type> <ndim> <dims...>
        outs <n>   then per out:   <name> <pjrt_type> <ndim> <dims...>
    """
    t = manifest["pjrt_types"]
    lines = ["pds-manifest %d" % manifest["version"],
             "platforms %d %s" % (len(manifest["platforms"]),
                                  " ".join(manifest["platforms"])),
             "params %d %s" % (len(manifest["param_names"]),
                               " ".join(manifest["param_names"])),
             "buckets %d" % len(manifest["buckets"])]
    for b in manifest["buckets"]:
        lines.append("bucket %d %s" % (b["batch_size"], b["module_file"]))
        lines.append("feeds %d" % len(b["feed_names"]))
        for n, dt, sh in zip(b["feed_names"], b["feed_dtypes"],
                             b["feed_shapes"]):
            lines.append("%s %d %d %s" % (
                n, t[dt], len(sh), " ".join(str(d) for d in sh)))
        lines.append("outs %d" % len(b["out_avals"]))
        for n, a in zip(b["out_names"], b["out_avals"]):
            lines.append("%s %d %d %s" % (
                n, t[a["dtype"]], len(a["shape"]),
                " ".join(str(d) for d in a["shape"])))
    with open(os.path.join(out_dir, "manifest.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")


def load_serving_artifact(artifact_dir: str):
    """Python-side loader (testing/debugging counterpart of the C one):
    deserializes each bucket with jax.export and returns
    (manifest, {batch_size: callable(feed_dict) -> [outputs]})."""
    import jax

    from ..native.tensor_store import load_tensors

    with open(os.path.join(artifact_dir, "manifest.json")) as f:
        manifest = json.load(f)
    params = load_tensors(os.path.join(artifact_dir, "params.ptck"))
    param_vals = [params[n] for n in manifest["param_names"]]

    runners = {}
    for b in manifest["buckets"]:
        with open(os.path.join(artifact_dir,
                               b["module_file"] + ".jaxexp"), "rb") as f:
            exported = jax.export.deserialize(bytearray(f.read()))

        def run(feed, _b=b, _e=exported):
            args = [np.asarray(feed[n]).astype(dt) for n, dt in
                    zip(_b["feed_names"], _b["feed_dtypes"])] + param_vals
            return [np.asarray(v) for v in _e.call(*args)]

        runners[b["batch_size"]] = run
    return manifest, runners

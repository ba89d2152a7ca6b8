"""Freeze and rehydrate deployable artifacts (save/load_artifact).

``save_artifact`` runs the expensive half of the serving pipeline ONCE
— verify, inference-rewrite, the level-N TV-checked optimizer pipeline,
param checksums, memory prediction, AOT
serialization — and writes the results into one validated file
(format.py). ``load_artifact`` is the cheap half: a file read plus
mandatory validation rehydrates a Predictor-ready bundle with ZERO
trace, ZERO optimize, and (with the AOT section) zero
XLA re-lowering; the cold-start acceptance tests pin exactly which
telemetry counters a load is allowed to move (none of the optimizer/
plan-miss families).

Validation is mandatory, not advisory: config_key and TV-digest
mismatches, param checksum failures, truncated files and future format
versions are REFUSED with a typed :class:`ArtifactSkewError` and
counted (``paddle_export_artifact_skew_total``); optional sections
degrade one at a time to recompute, each degradation counted by
(section, reason). A skewed artifact is never silently served.
"""

from __future__ import annotations

import io
import json
import time
import zipfile
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..observe import trace as _tr
from .format import (ArtifactError, ArtifactSkewError, read_artifact,
                     read_section, sha256_hex, write_artifact,
                     write_section)

__all__ = ["save_artifact", "load_artifact", "LoadedArtifact"]


# ------------------------------------------------------------- config
def _config_record() -> dict:
    """The portable optimization config the artifact was frozen under:
    the pass pipeline's full config_key (level + fold + quant + AMP
    knobs) and the ``PADDLE_TPU_KERNELS`` switch."""
    from .. import kernels
    from ..core import passes

    return {"passes": list(passes.config_key()),
            "kernels_enabled": bool(kernels.kernels_enabled())}


def _check_config(manifest: dict) -> None:
    recorded = manifest.get("config_key")
    if recorded is None:
        return
    current = _config_record()
    if (list(recorded.get("passes") or []) != current["passes"]
            or bool(recorded.get("kernels_enabled"))
            != current["kernels_enabled"]):
        raise ArtifactSkewError(
            "config_key",
            "artifact was frozen under config %s but this process runs "
            "%s — a plan optimized under one config must never serve "
            "another (re-export, or align PADDLE_TPU_OPTIMIZE*/"
            "PADDLE_TPU_KERNELS)" % (recorded, current))


# -------------------------------------------------------------- save
def _resolve_source(obj, feed_names, fetch_names, params, scope):
    """Normalize the three accepted inputs to
    (program, feed_names, fetch_names, params, batch_major_fetches,
    exact_numerics, already_inference)."""
    from ..core.program import Program
    from ..imperative.jit import CapturedFunction
    from ..inference import Predictor

    if isinstance(obj, CapturedFunction):
        entry = obj._last_entry
        if entry is None:
            raise ArtifactError(
                "call %r once (to capture) before save_artifact"
                % obj.__name__)
        if entry.trainable:
            raise ArtifactError(
                "%r captured a backward/optimizer step; only inference "
                "captures can be frozen into a serving artifact"
                % obj.__name__)
        bm = [n for n, sl in zip(entry.fetch_names, entry.fetch_slice)
              if sl]
        return (entry.program, list(entry.feed_order),
                list(entry.fetch_names),
                {n: np.asarray(v.value) for n, v in entry.state.items()},
                bm, bool(getattr(entry.program, "exact_numerics", False)),
                False)
    if isinstance(obj, Predictor):
        p = {}
        for n in obj.scope.local_var_names():
            v = obj.scope.find_var(n)
            if v is not None:
                p[n] = np.asarray(v)
        return (obj.program, list(obj.feed_names), list(obj.fetch_names),
                p, [], bool(getattr(obj.program, "exact_numerics", False)),
                True)
    if isinstance(obj, Program):
        if feed_names is None or fetch_names is None:
            raise ArtifactError(
                "save_artifact(Program) needs feed_names= and "
                "fetch_names=")
        if params is None:
            if scope is None:
                raise ArtifactError(
                    "save_artifact(Program) needs params= (name -> "
                    "array) or scope= to read persistables from")
            params = {}
            for var in obj.list_vars():
                if var.persistable and scope.has_var(var.name):
                    params[var.name] = np.asarray(scope.find_var(var.name))
        return (obj, list(feed_names), list(fetch_names),
                {n: np.asarray(v) for n, v in params.items()}, [],
                bool(getattr(obj, "exact_numerics", False)), False)
    raise ArtifactError(
        "save_artifact takes a Program, a CapturedFunction or a "
        "Predictor; got %r" % type(obj).__name__)


def _freeze_program(program, fetch_names, batch_major_fetches, params,
                    exact, already_inference):
    """Verify + inference-rewrite + (unless exact_numerics) run the
    LIVE-config optimizer pipeline with TV forced on. Returns
    (optimized_program, rewrite_log, pass_stats)."""
    from ..analysis import verify_program
    from ..core.passes import optimize_level, optimize_program
    from ..core.scope import Scope
    from ..inference import _rewrite_for_inference

    if not already_inference:
        program = _rewrite_for_inference(program)
        block = program.global_block()
        for n in batch_major_fetches:
            var = block.vars.get(n)
            if var is not None and var.shape:
                var.shape = (-1,) + tuple(var.shape[1:])
    pscope = Scope()
    for n, v in params.items():
        pscope.set_var(n, v)
    verify_program(program, fetch_list=list(fetch_names), scope=pscope,
                   raise_on_error=True, site="export")
    if exact or optimize_level() <= 0:
        # exact_numerics replays (and level-0 runs) execute the
        # UNOPTIMIZED sequence — freeze exactly what would run
        return program, [], []
    optimized, stats, mgr = optimize_program(
        program, fetch_list=list(fetch_names), scope=pscope,
        tv=True, return_manager=True)
    return optimized, mgr.rewrite_log, stats


def _params_blob(params: Dict[str, np.ndarray]) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **{n: np.asarray(v) for n, v in params.items()})
    return buf.getvalue()


def _memory_record(program, fetch_names, batch_sizes) -> Optional[dict]:
    try:
        from ..analysis.memory import MemoryAnalysis

        ma = MemoryAnalysis(program, list(fetch_names), site="export")
        poly = ma.peak_poly(max(list(batch_sizes) or [1]))
        return {"peak_poly": {str(d): c for d, c in poly.terms.items()},
                "peak_poly_text": poly.describe(),
                "predicted_bytes": {str(b): int(ma.peak_bytes(b))
                                    for b in (batch_sizes or (1,))}}
    except Exception:
        return None


def _aot_blob(program, feed_names, fetch_names, params, batch_sizes,
              manifest) -> Optional[bytes]:
    """jax.export-serialize one executable per batch-size bucket into
    an inner zip (aot.json + bucket_<n>.jaxexp). Returns None — the
    graceful sections-absent fallback — when jax.export is missing,
    the program is impure (serving AOT requires pure inference), or
    export fails; the manifest records why."""
    import jax

    if not batch_sizes:
        manifest["aot_skipped"] = "no batch_sizes requested"
        return None
    try:
        import jax.export  # noqa: F401 — submodule is not auto-imported
        jax.export.export
    except (ImportError, AttributeError):
        manifest["aot_skipped"] = "jax.export unavailable"
        return None
    from ..core.executor import analyze_block
    from ..core.lowering import as_jax_dtype
    from ..core.scope import Scope, scope_guard

    scope = Scope()
    for n, v in params.items():
        scope.set_var(n, v)
    block = program.global_block()
    platform = jax.default_backend()
    buckets: List[dict] = []
    inner = io.BytesIO()
    try:
        with zipfile.ZipFile(inner, "w", zipfile.ZIP_DEFLATED) as zf:
            for bs in sorted(set(int(b) for b in batch_sizes)):
                feed = {}
                for n in feed_names:
                    var = block.var(n)
                    shape = [bs if (s is None or s < 0) else int(s)
                             for s in (var.shape or ())]
                    feed[n] = np.zeros(
                        shape, np.dtype(as_jax_dtype(var.dtype)))
                with scope_guard(scope):
                    (f_names, o_names, const_state, mut_state,
                     pure_written, needs_rng, step) = analyze_block(
                        program, sorted(feed), list(fetch_names), scope)
                if mut_state or pure_written or needs_rng:
                    manifest["aot_skipped"] = (
                        "program is not pure (state writes %s/%s, "
                        "rng=%s)" % (mut_state, pure_written, needs_rng))
                    return None

                def fn(*args):
                    feeds = list(args[:len(f_names)])
                    ps = list(args[len(f_names):])
                    fetches, _, _, _ = step(feeds, ps, [], None)
                    return tuple(fetches)

                feed_args = [feed[n] for n in f_names]
                param_args = [np.asarray(scope.find_var(n))
                              for n in const_state]
                exported = jax.export.export(
                    jax.jit(fn), platforms=[platform])(
                        *feed_args, *param_args)
                zf.writestr("bucket_%d.jaxexp" % bs,
                            exported.serialize())
                buckets.append({
                    "batch_size": bs,
                    "feed_names": list(f_names),
                    "feed_dtypes": [str(feed[n].dtype) for n in f_names],
                    "param_names": list(const_state),
                    "out_names": list(o_names),
                })
            zf.writestr("aot.json", json.dumps(
                {"platform": platform, "buckets": buckets},
                sort_keys=True))
    except Exception as e:  # noqa: BLE001 — AOT is best-effort by contract
        manifest["aot_skipped"] = "%s: %s" % (type(e).__name__, e)
        return None
    return inner.getvalue()


def save_artifact(obj, path: str, *,
                  feed_names: Optional[Sequence[str]] = None,
                  fetch_names: Optional[Sequence[str]] = None,
                  params: Optional[Dict[str, Any]] = None,
                  scope=None,
                  batch_sizes: Sequence[int] = (),
                  aot: Optional[bool] = None,
                  serving: Optional[dict] = None,
                  name: Optional[str] = None) -> str:
    """Freeze ``obj`` — a ``Program`` (+ ``feed_names``/``fetch_names``
    and ``params`` or ``scope``), a ``CapturedFunction`` (last capture)
    or a ``Predictor`` — into one deployable artifact file at ``path``.

    What gets frozen: the verified + live-config-optimized program
    (TV forced on; ``exact_numerics`` captures freeze the unoptimized
    sequence, exactly what would run), per-var-checksummed params,
    the predicted peak-memory polynomial, the full config_key, the TV
    rewrite-log digest, and — for each ``batch_sizes`` bucket, unless
    ``aot=False`` or ``PADDLE_TPU_EXPORT_AOT=0`` — a
    ``jax.export``-serialized executable. ``serving=`` attaches a
    ``DecodeEngine`` construction record (``cfg``/``b_max``/
    ``max_len``) for ``DecodeEngine.from_artifact`` and
    ``ReplicaRouter.roll``. ``obj=None`` with ``params=`` and
    ``serving=`` writes a serving-only artifact — no program section,
    the engine rebuilds its decode step from ``cfg`` but
    re-checksums nothing. Returns ``path``."""
    import os as _os

    from ..observe.families import ARTIFACT_SAVE_SECONDS, ARTIFACT_SAVES

    t0 = time.perf_counter()
    with _tr.trace_span("export.save", path=path):
        if obj is None:
            if params is None or serving is None:
                raise ArtifactError(
                    "save_artifact(None) is the serving-only form: it "
                    "needs params= and serving={'cfg': ...}")
            program, feeds, fetches = None, [], []
            pvals = {n: np.asarray(v) for n, v in params.items()}
            rewrite_log, pass_stats, exact = None, [], False
        else:
            (program, feeds, fetches, pvals, bm, exact,
             already_inf) = _resolve_source(obj, feed_names, fetch_names,
                                            params, scope)
            program, rewrite_log, pass_stats = _freeze_program(
                program, fetches, bm, pvals, exact, already_inf)
        from ..core.passes import optimize_level

        manifest: dict = {
            "name": name or getattr(obj, "__name__", None)
            or "artifact",
            "feed_names": feeds,
            "fetch_names": fetches,
            "batch_sizes": sorted(set(int(b) for b in batch_sizes)),
            "exact_numerics": exact,
            "optimize_level": 0 if exact else optimize_level(),
            "config_key": _config_record(),
            "pass_stats": [{k: v for k, v in row.items()
                            if k in ("pass", "ops_before", "ops_after")}
                           for row in pass_stats],
            "params": {
                n: {"sha256": sha256_hex(np.asarray(v).tobytes()),
                    "dtype": str(np.asarray(v).dtype),
                    "shape": list(np.asarray(v).shape)}
                for n, v in pvals.items()},
        }
        blobs: Dict[str, bytes] = {}
        if program is not None:
            write_section(blobs, manifest, "program",
                          json.dumps(program.to_dict(),
                                     sort_keys=True).encode())
        write_section(blobs, manifest, "params", _params_blob(pvals))
        if rewrite_log is not None:
            log_blob = json.dumps(rewrite_log, sort_keys=True,
                                  default=repr).encode()
            manifest["tv_digest"] = sha256_hex(log_blob)
            write_section(blobs, manifest, "rewrite_log", log_blob)
        mem = (None if program is None else _memory_record(
            program, fetches, manifest["batch_sizes"]))
        if mem is not None:
            manifest["predicted_bytes"] = mem["predicted_bytes"]
            write_section(blobs, manifest, "memory",
                          json.dumps(mem, sort_keys=True).encode())
        want_aot = (aot if aot is not None else
                    _os.environ.get("PADDLE_TPU_EXPORT_AOT", "1") != "0")
        if program is not None and want_aot:
            ab = _aot_blob(program, feeds, fetches, pvals,
                           manifest["batch_sizes"], manifest)
            if ab is not None:
                write_section(blobs, manifest, "aot", ab)
        elif program is not None and batch_sizes:
            manifest["aot_skipped"] = "disabled (aot=False / " \
                "PADDLE_TPU_EXPORT_AOT=0)"
        if serving is not None:
            if "cfg" not in serving:
                raise ArtifactError(
                    "serving= record needs at least a 'cfg' dict "
                    "(DecodeEngine model config)")
            write_section(blobs, manifest, "serving",
                          json.dumps(serving, sort_keys=True).encode())
        write_artifact(path, manifest, blobs)
    ARTIFACT_SAVES.inc()
    ARTIFACT_SAVE_SECONDS.observe(time.perf_counter() - t0)
    return path


# -------------------------------------------------------------- load
class _AotRunner:
    """One frozen executable: calls the deserialized jax.export module
    with the artifact's params baked in, zero re-lowering."""

    __slots__ = ("exported", "feed_names", "feed_dtypes", "out_names",
                 "param_vals")

    def __init__(self, exported, meta, params):
        self.exported = exported
        self.feed_names = list(meta["feed_names"])
        self.feed_dtypes = list(meta["feed_dtypes"])
        self.out_names = list(meta["out_names"])
        self.param_vals = [np.asarray(params[n])
                           for n in meta["param_names"]]

    def __call__(self, feed: Dict[str, np.ndarray]) -> List[np.ndarray]:
        args = [np.asarray(feed[n]).astype(dt, copy=False)
                for n, dt in zip(self.feed_names, self.feed_dtypes)]
        outs = self.exported.call(*(args + self.param_vals))
        return [np.asarray(v) for v in outs]


class LoadedArtifact:
    """A validated, rehydrated artifact: the frozen program + params
    are already installed process-side; ``predictor()``
    hands back a serving-ready Predictor whose plan cache is seeded
    (zero misses for covered signatures) and whose bucket runs ride the
    AOT section when present."""

    def __init__(self, path, manifest):
        self.path = path
        self.manifest = manifest
        self.program = None
        self.feed_names: List[str] = list(manifest.get("feed_names")
                                          or [])
        self.fetch_names: List[str] = list(manifest.get("fetch_names")
                                           or [])
        self.params: Dict[str, np.ndarray] = {}
        self.memory: Optional[dict] = None
        self.rewrite_log: Optional[list] = None
        self.aot: Dict[int, _AotRunner] = {}
        self.serving: Optional[dict] = None
        self.degraded: List[tuple] = []

    # ------------------------------------------------------- queries
    @property
    def batch_sizes(self) -> List[int]:
        return list(self.manifest.get("batch_sizes") or [])

    def predicted_bytes(self, batch_size: int) -> Optional[int]:
        """Evaluate the frozen peak-memory polynomial at
        ``batch_size`` (None when the memory section degraded)."""
        if not self.memory:
            return None
        b = max(1, int(batch_size))
        return int(round(sum(float(c) * (b ** int(d)) for d, c in
                             (self.memory.get("peak_poly")
                              or {}).items())))

    # ------------------------------------------------------ serving
    def predictor(self, warmup_batch_sizes: Optional[Sequence[int]]
                  = None):
        """A Predictor over the frozen program: ``pre_optimized`` (the
        executor will not re-run the pass pipeline), plan-cache seeded
        per bucket (first runs are HITS, counted in
        ``paddle_export_plans_seeded_total``), AOT runners attached
        when the section survived. Default buckets are the artifact's
        recorded ``batch_sizes``."""
        from ..inference import Predictor
        from ..observe.families import ARTIFACT_PLANS_SEEDED

        if self.program is None:
            raise ArtifactError(
                "artifact %r carries no program section (serving-only "
                "artifact?) — predictor() needs one" % self.path)
        buckets = (self.batch_sizes if warmup_batch_sizes is None
                   else sorted(set(int(b) for b in warmup_batch_sizes)))
        pred = Predictor.from_program(
            self.program, self.feed_names, self.fetch_names,
            dict(self.params), pre_optimized=True)
        pred._buckets = list(buckets)
        block = self.program.global_block()
        for bs in buckets:
            feed = {}
            for n in self.feed_names:
                var = block.var(n)
                shape = [bs if (s is None or s < 0) else int(s)
                         for s in (var.shape or ())]
                feed[n] = np.zeros(shape, dtype=var.dtype)
            if pred._exe.seed_plan(self.program, feed,
                                   self.fetch_names, scope=pred.scope):
                ARTIFACT_PLANS_SEEDED.inc()
        if self.aot:
            pred._aot = dict(self.aot)
        return pred


def _load_params(manifest, blob, path):
    """Parse + per-var-validate the params section: every recorded var
    must be present with the recorded dtype/shape and sha256 — a
    single flipped byte refuses the artifact (``param_checksum``)."""
    try:
        data = np.load(io.BytesIO(blob), allow_pickle=False)
        arrays = {n: data[n] for n in data.files}
    except Exception as e:
        raise ArtifactSkewError(
            "param_checksum",
            "artifact %r params section is unreadable (%s: %s)"
            % (path, type(e).__name__, e))
    out = {}
    for n, rec in (manifest.get("params") or {}).items():
        arr = arrays.get(n)
        if arr is None:
            raise ArtifactSkewError(
                "param_checksum",
                "artifact %r params section lacks recorded var %r"
                % (path, n))
        if sha256_hex(arr.tobytes()) != rec.get("sha256") \
                or str(arr.dtype) != rec.get("dtype") \
                or list(arr.shape) != list(rec.get("shape") or []):
            raise ArtifactSkewError(
                "param_checksum",
                "artifact %r param %r fails its recorded checksum/"
                "dtype/shape — corrupted or tampered weights are "
                "never served" % (path, n))
        out[n] = arr
    return out


def load_artifact(path: str) -> LoadedArtifact:
    """Validate + rehydrate an artifact (the cheap half — a file read).

    The validation ladder, in order, all mandatory: container + format
    version (``corrupt``/``future_version``), recorded config_key vs
    the running process (``config_key``), per-section sha256
    (``section_checksum``), the TV rewrite-log digest (``tv_digest``),
    per-var param checksums (``param_checksum``). Any failure raises
    :class:`ArtifactSkewError`, counted by reason — never silently
    served. Optional sections (memory / rewrite_log / aot) degrade
    individually to recompute, counted by (section, reason) in
    ``paddle_export_artifact_degraded_total``."""
    from ..observe.families import (ARTIFACT_DEGRADED, ARTIFACT_LOADS,
                                    ARTIFACT_SKEW)

    t0 = time.perf_counter()
    try:
        with _tr.trace_span("export.load", path=path):
            manifest, zf = read_artifact(path)
            try:
                art = _load_validated(path, manifest, zf)
            finally:
                zf.close()
    except ArtifactSkewError as e:
        ARTIFACT_SKEW.labels(reason=e.reason).inc()
        ARTIFACT_LOADS.labels(
            outcome="corrupt" if e.reason == "corrupt" else "skew").inc()
        raise
    for section, reason in art.degraded:
        ARTIFACT_DEGRADED.labels(section=section, reason=reason).inc()
    ARTIFACT_LOADS.labels(outcome="ok").inc()
    from ..observe.families import ARTIFACT_LOAD_SECONDS

    ARTIFACT_LOAD_SECONDS.observe(time.perf_counter() - t0)
    return art


def _load_validated(path, manifest, zf) -> LoadedArtifact:
    from ..io import _program_from_dict

    _check_config(manifest)
    art = LoadedArtifact(path, manifest)
    versions = manifest.get("section_versions") or {}

    # --- program (mandatory when listed; version skew refuses: there
    # is nothing to serve if the program schema is unknown)
    prog_blob = read_section(zf, manifest, "program")
    if prog_blob is not None:
        if versions.get("program", 1) > 1:
            raise ArtifactSkewError(
                "future_version",
                "artifact %r program section is schema version %s; "
                "this runtime reads <= 1" % (path,
                                             versions.get("program")))
        try:
            art.program = _program_from_dict(json.loads(prog_blob))
        except Exception as e:
            raise ArtifactSkewError(
                "corrupt", "artifact %r program section does not "
                "parse (%s: %s)" % (path, type(e).__name__, e))
        art.program.exact_numerics = bool(
            manifest.get("exact_numerics", False))
        # the executor trusts the freeze: _prepare skips the pass
        # pipeline for this program (it already ran, TV-checked, at
        # save time — that is the zero-optimize contract)
        art.program._pre_optimized = True

    # --- TV rewrite-log digest (mandatory when a program rides along)
    log_blob = read_section(zf, manifest, "rewrite_log")
    if log_blob is not None:
        if manifest.get("tv_digest") != sha256_hex(log_blob):
            raise ArtifactSkewError(
                "tv_digest",
                "artifact %r rewrite-log digest mismatch: the frozen "
                "program's optimization provenance cannot be trusted"
                % path)
        art.rewrite_log = json.loads(log_blob)
    elif art.program is not None:
        art.degraded.append(("rewrite_log", "absent"))

    # --- params (mandatory: weights are the artifact's payload)
    par_blob = read_section(zf, manifest, "params")
    if par_blob is None:
        raise ArtifactError(
            "artifact %r carries no params section" % path)
    art.params = _load_params(manifest, par_blob, path)

    # --- memory prediction (optional)
    mem_blob = read_section(zf, manifest, "memory")
    if mem_blob is None:
        if art.program is not None:
            art.degraded.append(("memory", "absent"))
    else:
        art.memory = json.loads(mem_blob)

    # --- AOT executables (optional; requires a working jax.export)
    aot_blob = read_section(zf, manifest, "aot")
    if aot_blob is None:
        if art.program is not None:
            art.degraded.append(("aot", "absent"))
    elif versions.get("aot", 1) > 1:
        art.degraded.append(("aot", "version"))
    else:
        try:
            import jax
            import jax.export  # noqa: F401 — submodule not auto-imported

            jax.export.deserialize
            with zipfile.ZipFile(io.BytesIO(aot_blob)) as azf:
                meta = json.loads(azf.read("aot.json"))
                for b in meta["buckets"]:
                    exported = jax.export.deserialize(bytearray(
                        azf.read("bucket_%d.jaxexp" % b["batch_size"])))
                    art.aot[int(b["batch_size"])] = _AotRunner(
                        exported, b, art.params)
        except Exception:  # noqa: BLE001 — degrade to the plan path
            art.aot = {}
            art.degraded.append(("aot", "jax"))

    # --- serving record (optional; engines need it, predictors don't)
    srv_blob = read_section(zf, manifest, "serving")
    if srv_blob is not None:
        art.serving = json.loads(srv_blob)
    return art

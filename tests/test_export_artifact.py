"""Deployable artifacts (ISSUE 19): compile-once export, validated
cold start, fleet rolling upgrade.

Contracts pinned here:

* Round-trip parity — for three model-zoo inference programs, a
  save_artifact/load_artifact/predictor() round trip reproduces the
  from-scratch executor's output BITWISE (the frozen program is the
  live-config optimized program, TV forced on at freeze time); the
  int8-quantized freeze stays within the quantize pass's own stated
  QUANT_TOLERANCE of the fp32 reference.
* The cold-start contract — loading an artifact and serving the first
  covered batch moves ZERO optimizer-pipeline counters and ZERO
  executor plan-cache misses; seeded plans and AOT
  calls are counted in their own paddle_export_* families.
* Skew safety — truncated files, flipped param bytes, stale
  config_key, tampered TV digests and future format versions are
  refused with a typed ArtifactSkewError, counted by reason, and never
  silently served; a missing optional section degrades to recompute
  with the degradation counted; concurrent writers never torch the
  file (atomic tmp+rename, same contract as tensor_store).
* Rolling upgrade — ReplicaRouter.roll replaces a 2-replica fleet
  one at a time with drain; every in-flight request reports exactly
  one terminal outcome; a replica crash mid-roll recovers through the
  ordinary monitor path already at the NEW version.
* The CLI (tools/export_artifact.py) builds from the shared
  lint_program model-zoo builders, --inspect prints the manifest, and
  --validate exits 1 on skew.
"""

import io
import json
import os
import sys
import threading
import time
import zipfile

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import export
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.observe import families as fam

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))
try:
    from lint_program import build_example
finally:
    sys.path.pop(0)


def _feed_for(main, batch, seed=0):
    rng = np.random.RandomState(seed)
    feed = {}
    for var in main.global_block().vars.values():
        if not var.is_data:
            continue
        shape = [batch if (s is None or s < 0) else int(s)
                 for s in (var.shape or [batch])]
        if var.dtype.startswith(("int", "uint")):
            feed[var.name] = rng.randint(0, 2, shape).astype("int64")
        else:
            feed[var.name] = rng.uniform(-1, 1, shape).astype("float32")
    return feed


def _freeze_zoo(model, path, batch=4):
    """Build one forward-only zoo model, run the from-scratch
    reference, freeze it. Returns (ref_output, feed, path)."""
    main, startup, loss = build_example(model, optimizer=False)
    scope = Scope()
    feed = _feed_for(main, batch)
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        ref, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        export.save_artifact(main, path, feed_names=sorted(feed),
                             fetch_names=[loss.name], scope=scope,
                             batch_sizes=(batch,), name=model)
    return np.asarray(ref), feed, path


# --------------------------------------------------------- round trip
@pytest.mark.parametrize("model", ["mnist", "ctr", "stacked_lstm"])
def test_roundtrip_bitwise_parity_zoo(model, tmp_path):
    ref, feed, path = _freeze_zoo(model, str(tmp_path / "m.pdz"))
    art = export.load_artifact(path)
    out = np.asarray(art.predictor().run(feed)[0])
    np.testing.assert_array_equal(out, ref)
    # the frozen bundle is complete: nothing degraded on a same-config
    # same-process round trip
    assert art.degraded == []


def test_roundtrip_quantized_within_stated_tolerance(tmp_path,
                                                     monkeypatch):
    """A freeze under PADDLE_TPU_OPTIMIZE_QUANT=1 bakes the int8-PTQ
    program; the round trip is bitwise vs the quantized scratch run
    and within the quantize pass's own stated tolerance of fp32."""
    from paddle_tpu.core.passes.quantize_pass import QUANT_TOLERANCE

    main, startup, loss = build_example("mnist", optimizer=False)
    scope = Scope()
    feed = _feed_for(main, 4)
    with scope_guard(scope):
        fluid.Executor(fluid.TPUPlace()).run(startup, scope=scope)
        base, = fluid.Executor(fluid.TPUPlace()).run(
            main, feed=feed, fetch_list=[loss], scope=scope)
        base = np.asarray(base)
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE_QUANT", "1")
        qref, = fluid.Executor(fluid.TPUPlace()).run(
            main, feed=feed, fetch_list=[loss], scope=scope)
        qref = np.asarray(qref)
        path = str(tmp_path / "q.pdz")
        export.save_artifact(main, path, feed_names=sorted(feed),
                             fetch_names=[loss.name], scope=scope,
                             batch_sizes=(4,))
        art = export.load_artifact(path)
        out = np.asarray(art.predictor().run(feed)[0])
    np.testing.assert_array_equal(out, qref)
    assert np.allclose(out, base, **QUANT_TOLERANCE)
    assert art.manifest["config_key"]["passes"][2] is True  # quant on


def test_exact_numerics_freezes_unoptimized_program(tmp_path):
    """exact_numerics programs freeze the UNOPTIMIZED op sequence —
    exactly what the executor would run — with an empty rewrite log."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        out = fluid.layers.fc(x, size=4)
    main.exact_numerics = True
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        feed = {"x": np.random.RandomState(3).randn(4, 8).astype(
            "float32")}
        ref, = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    path = export.save_artifact(main, str(tmp_path / "e.pdz"),
                                feed_names=["x"],
                                fetch_names=[out.name], scope=scope,
                                batch_sizes=(4,))
    art = export.load_artifact(path)
    assert art.manifest["exact_numerics"] is True
    assert art.manifest["optimize_level"] == 0
    assert art.rewrite_log == []
    assert art.program.exact_numerics is True
    got = np.asarray(art.predictor().run(feed)[0])
    np.testing.assert_array_equal(got, np.asarray(ref))


# --------------------------------------------------------- cold start
def _opt_total():
    return sum(fam.OPTIMIZER_PROGRAMS.labels(level=lv).value
               for lv in ("1", "2"))


def test_cold_start_moves_zero_compile_counters(tmp_path):
    """THE cold-start acceptance criterion: load + first covered batch
    move ZERO optimizer-pipeline runs and ZERO executor plan-cache
    misses — the artifact replaced both with a file read. Seeded plans
    are counted in their own family."""
    ref, feed, path = _freeze_zoo("mnist", str(tmp_path / "m.pdz"))
    miss0 = fam.EXECUTOR_CACHE_MISSES.value
    opt0 = _opt_total()
    seeded0 = fam.ARTIFACT_PLANS_SEEDED.value
    ok0 = fam.ARTIFACT_LOADS.labels(outcome="ok").value

    art = export.load_artifact(path)
    pred = art.predictor()
    out = np.asarray(pred.run(feed)[0])

    np.testing.assert_array_equal(out, ref)
    assert fam.EXECUTOR_CACHE_MISSES.value == miss0
    assert _opt_total() == opt0
    assert fam.ARTIFACT_PLANS_SEEDED.value == seeded0 + 1
    assert fam.ARTIFACT_LOADS.labels(outcome="ok").value == ok0 + 1


def test_artifact_written_before_the_tuner_went_still_loads():
    """``tests/references/artifact_pr42.pdz`` was written by the parent
    of PR 43 (``save_artifact(aot=False)``, two tuned entries injected):
    its ``tuned_kernels`` section is listed, checksummed and read by
    nothing; the frozen program serves the recorded output bitwise and
    no degradation is counted for the section."""
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "references")
    with open(os.path.join(here, "artifact_pr42.json")) as f:
        recorded = json.load(f)
    path = os.path.join(here, "artifact_pr42.pdz")
    with zipfile.ZipFile(path) as zf:
        manifest = json.loads(zf.read("manifest.json"))
        assert b"train_window|" in zf.read("section/tuned_kernels")
    assert "tuned_kernels" in manifest["sections"]
    assert "tuned_kernels" not in export.SECTIONS
    art = export.load_artifact(path)
    assert [s for s, _ in art.degraded] == ["aot"]   # written without
    feed = {"x": np.asarray(recorded["feed"]["x"], "float32")}
    out = np.asarray(art.predictor().run(feed)[0])
    np.testing.assert_array_equal(out,
                                  np.asarray(recorded["out"], "float32"))


def test_seed_plan_installs_without_miss(tmp_path):
    """Executor.seed_plan: installs a ready plan (True), is idempotent
    (False on the second call), and the seeded signature's first run
    counts a HIT, not a miss."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[6], dtype="float32")
        out = fluid.layers.fc(x, size=3)
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor(fluid.TPUPlace()).run(startup, scope=scope)
    feed = {"x": np.zeros((2, 6), "float32")}
    exe = fluid.Executor(fluid.TPUPlace())
    assert exe.seed_plan(main, feed, [out], scope=scope) is True
    assert exe.seed_plan(main, feed, [out], scope=scope) is False
    miss0 = fam.EXECUTOR_CACHE_MISSES.value
    hit0 = fam.EXECUTOR_CACHE_HITS.value
    with scope_guard(scope):
        exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    assert fam.EXECUTOR_CACHE_MISSES.value == miss0
    assert fam.EXECUTOR_CACHE_HITS.value == hit0 + 1


def test_aot_section_serves_first_token(tmp_path):
    """With a live AOT section the bucket run is served by the frozen
    jax.export executable — counted — and stays bitwise."""
    ref, feed, path = _freeze_zoo("mnist", str(tmp_path / "m.pdz"))
    art = export.load_artifact(path)
    assert sorted(art.aot) == [4]
    aot0 = fam.ARTIFACT_AOT_CALLS.value
    out = np.asarray(art.predictor().run(feed)[0])
    np.testing.assert_array_equal(out, ref)
    assert fam.ARTIFACT_AOT_CALLS.value == aot0 + 1


# --------------------------------------------------------- skew safety
def _fc_artifact(tmp_path, name="a.pdz"):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        out = fluid.layers.fc(h, size=4, act="softmax")
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor(fluid.TPUPlace()).run(startup, scope=scope)
    path = str(tmp_path / name)
    export.save_artifact(main, path, feed_names=["x"],
                         fetch_names=[out.name], scope=scope,
                         batch_sizes=(2,), aot=False)
    return path


def _rewrite(path, out_path, edit):
    """Re-write an artifact zip through ``edit(name->bytes dict)``."""
    with zipfile.ZipFile(path) as zf:
        data = {n: zf.read(n) for n in zf.namelist()}
    edit(data)
    with zipfile.ZipFile(out_path, "w") as zf:
        for n, b in data.items():
            zf.writestr(n, b)
    return out_path


def _skew_count(reason):
    return fam.ARTIFACT_SKEW.labels(reason=reason).value


def test_truncated_file_refused_and_counted(tmp_path):
    path = _fc_artifact(tmp_path)
    raw = open(path, "rb").read()
    trunc = str(tmp_path / "t.pdz")
    with open(trunc, "wb") as f:
        f.write(raw[:len(raw) // 2])
    c0 = _skew_count("corrupt")
    l0 = fam.ARTIFACT_LOADS.labels(outcome="corrupt").value
    with pytest.raises(export.ArtifactSkewError) as e:
        export.load_artifact(trunc)
    assert e.value.reason == "corrupt"
    assert _skew_count("corrupt") == c0 + 1
    assert fam.ARTIFACT_LOADS.labels(outcome="corrupt").value == l0 + 1


def test_flipped_param_byte_refused(tmp_path):
    """One perturbed weight value — with the SECTION checksum patched
    to match, so only the per-var ladder rung can catch it."""
    import hashlib

    path = _fc_artifact(tmp_path)

    def edit(data):
        with np.load(io.BytesIO(data["section/params"])) as npz:
            arrs = {k: npz[k].copy() for k in npz.files}
        arrs[sorted(arrs)[0]].flat[0] += 1.0
        buf = io.BytesIO()
        np.savez(buf, **arrs)
        data["section/params"] = buf.getvalue()
        m = json.loads(data["manifest.json"])
        m["checksums"]["params"] = hashlib.sha256(
            data["section/params"]).hexdigest()
        data["manifest.json"] = json.dumps(m).encode()

    bad = _rewrite(path, str(tmp_path / "bad.pdz"), edit)
    c0 = _skew_count("param_checksum")
    with pytest.raises(export.ArtifactSkewError) as e:
        export.load_artifact(bad)
    assert e.value.reason == "param_checksum"
    assert _skew_count("param_checksum") == c0 + 1


def test_section_checksum_mismatch_refused(tmp_path):
    path = _fc_artifact(tmp_path)

    def edit(data):
        data["section/program"] = data["section/program"] + b" "

    bad = _rewrite(path, str(tmp_path / "bad.pdz"), edit)
    c0 = _skew_count("section_checksum")
    with pytest.raises(export.ArtifactSkewError) as e:
        export.load_artifact(bad)
    assert e.value.reason == "section_checksum"
    assert _skew_count("section_checksum") == c0 + 1


def test_stale_config_key_refused(tmp_path, monkeypatch):
    """A REAL config skew (not a tampered manifest): the artifact was
    frozen with quantization off, the loading process runs with it on
    — the frozen plan must never serve the mismatched config."""
    path = _fc_artifact(tmp_path)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE_QUANT", "1")
    c0 = _skew_count("config_key")
    with pytest.raises(export.ArtifactSkewError,
                       match="frozen under config") as e:
        export.load_artifact(path)
    assert e.value.reason == "config_key"
    assert _skew_count("config_key") == c0 + 1


def test_tampered_tv_digest_refused(tmp_path):
    path = _fc_artifact(tmp_path)

    def edit(data):
        m = json.loads(data["manifest.json"])
        m["tv_digest"] = "0" * 64
        data["manifest.json"] = json.dumps(m).encode()

    bad = _rewrite(path, str(tmp_path / "bad.pdz"), edit)
    c0 = _skew_count("tv_digest")
    with pytest.raises(export.ArtifactSkewError) as e:
        export.load_artifact(bad)
    assert e.value.reason == "tv_digest"
    assert _skew_count("tv_digest") == c0 + 1


def test_future_format_version_refused_with_message(tmp_path):
    path = _fc_artifact(tmp_path)

    def edit(data):
        m = json.loads(data["manifest.json"])
        m["format_version"] = export.FORMAT_VERSION + 41
        data["manifest.json"] = json.dumps(m).encode()

    bad = _rewrite(path, str(tmp_path / "bad.pdz"), edit)
    c0 = _skew_count("future_version")
    with pytest.raises(export.ArtifactSkewError,
                       match="format version") as e:
        export.load_artifact(bad)
    assert e.value.reason == "future_version"
    assert _skew_count("future_version") == c0 + 1


def test_missing_aot_section_degrades_and_counts(tmp_path):
    """aot=False leaves the AOT section out: the load still serves
    (seeded executor plans) and the degradation is counted."""
    path = _fc_artifact(tmp_path)  # saved with aot=False
    d0 = fam.ARTIFACT_DEGRADED.labels(section="aot",
                                      reason="absent").value
    art = export.load_artifact(path)
    assert ("aot", "absent") in art.degraded
    assert art.aot == {}
    assert fam.ARTIFACT_DEGRADED.labels(
        section="aot", reason="absent").value == d0 + 1
    # still serves through the seeded plan path
    out = art.predictor().run({"x": np.zeros((2, 8), "float32")})
    assert np.asarray(out[0]).shape == (2, 4)


def test_concurrent_writers_never_torch_the_file(tmp_path):
    """N racing save_artifact calls to ONE path (atomic tmp+rename,
    the tensor_store contract): whichever rename lands last, the file
    is always a complete, loadable artifact and no tmp litter stays."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        out = fluid.layers.fc(x, size=2)
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor(fluid.TPUPlace()).run(startup, scope=scope)
    path = str(tmp_path / "race.pdz")
    errors = []

    def save():
        try:
            export.save_artifact(main, path, feed_names=["x"],
                                 fetch_names=[out.name], scope=scope,
                                 aot=False)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=save) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    art = export.load_artifact(path)
    assert sorted(art.params) == sorted(
        v.name for v in main.list_vars() if v.persistable)
    assert not [n for n in os.listdir(str(tmp_path))
                if ".tmp." in n], "tmp litter left behind"


# ---------------------------------------------------------------- CLI
def test_cli_build_inspect_validate(tmp_path, capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import export_artifact as cli
    finally:
        sys.path.pop(0)
    out = str(tmp_path / "cli.pdz")
    assert cli.main(["--model", "mnist", "--out", out,
                     "--buckets", "2", "--no-aot"]) == 0
    assert cli.main(["--inspect", out]) == 0
    text = capsys.readouterr().out
    assert "format_version: 1" in text
    assert "config_key" in text and "params: 6 vars" in text
    assert cli.main(["--validate", out]) == 0
    # corrupted file: --validate is the exit-1 pre-deploy gate
    bad = str(tmp_path / "bad.pdz")
    with open(out, "rb") as f:
        raw = f.read()
    with open(bad, "wb") as f:
        f.write(raw[: len(raw) // 3])
    assert cli.main(["--validate", bad]) == 1


# ------------------------------------------------------ rolling upgrade
ROLL_CFG = dict(d_model=32, d_ff=64, n_head=2, n_layer=1, vocab=64,
                max_length=32, dropout=0.0)
ROLL_MAX_LEN = 32


def _gpt_params(seed_shift=0.0):
    """Decode-step weights for ROLL_CFG; ``seed_shift`` adds noise to
    every float weight so v1/v2 fleets produce DIFFERENT outputs (the
    version probe the roll assertions key on — a uniform shift would
    be laundered by layernorm, so perturb per-element)."""
    from paddle_tpu.models import gpt

    prog, start = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(prog, start):
            _logits, cache_names = gpt.build_decode_step(
                ROLL_CFG, batch=1, max_len=ROLL_MAX_LEN)
        fluid.Executor(fluid.TPUPlace()).run(start, scope=scope)
    params = {n: np.asarray(scope.find_var(n))
              for n in prog.global_block().vars
              if n.startswith("gpt_") and n not in cache_names
              and scope.find_var(n) is not None}
    if seed_shift:
        rng = np.random.RandomState(7)
        params = {
            n: (v + rng.normal(0.0, seed_shift, v.shape).astype(v.dtype)
                if np.issubdtype(v.dtype, np.floating) else v)
            for n, v in params.items()}
    return params


@pytest.fixture(scope="module")
def roll_fleet(tmp_path_factory):
    """v1 params, a v2 serving artifact, and the expected v1/v2 greedy
    outputs for the probe prompt (from throwaway single engines)."""
    from paddle_tpu.serving import DecodeEngine

    v1 = _gpt_params()
    v2 = _gpt_params(seed_shift=0.25)
    path = str(tmp_path_factory.mktemp("roll") / "gpt_v2.pdz")
    export.save_artifact(
        None, path, params=v2,
        serving=dict(cfg=ROLL_CFG, b_max=2, max_len=ROLL_MAX_LEN),
        name="gpt-v2")
    prompt = np.arange(1, 7, dtype="int64")
    outs = {}
    for tag, params in (("v1", v1), ("v2", v2)):
        eng = DecodeEngine(ROLL_CFG, params=params, b_max=1,
                           max_len=ROLL_MAX_LEN).start()
        try:
            outs[tag] = eng.submit(prompt, 4).result(timeout=240)
        finally:
            eng.stop()
    assert not np.array_equal(outs["v1"], outs["v2"]), \
        "version probe failed: v1 and v2 outputs must differ"
    return dict(v1=v1, path=path, prompt=prompt,
                out_v1=outs["v1"], out_v2=outs["v2"])


def test_roll_replaces_fleet_with_drain_exactly_once(roll_fleet):
    """THE rolling-upgrade acceptance criterion: a 2-replica v1 fleet
    rolls to a v2 artifact replica-by-replica with drain; every
    request in flight during the roll reports exactly ONE terminal
    outcome (served by v1 or v2, both byte-checked); after the roll
    the whole fleet serves v2."""
    from paddle_tpu.serving import DecodeEngine, ReplicaRouter

    v1, path = roll_fleet["v1"], roll_fleet["path"]
    prompt = roll_fleet["prompt"]

    def v1_factory(idx):
        return DecodeEngine(ROLL_CFG, params=v1, b_max=2,
                            max_len=ROLL_MAX_LEN, queue_capacity=32)

    router = ReplicaRouter(v1_factory, n_replicas=2, poll_s=0.05,
                           max_readmissions=3)
    try:
        # warm both replicas (compile before the roll's drains)
        for _ in range(2):
            np.testing.assert_array_equal(
                router.submit(prompt, 4).result(timeout=240),
                roll_fleet["out_v1"])
        rolled0 = fam.ARTIFACT_ROLL_REPLICAS.value
        ok0 = fam.ARTIFACT_ROLLS.labels(outcome="ok").value
        done = []
        reqs = [router.submit(prompt, 4) for _ in range(6)]
        for r in reqs:
            r.add_done_callback(lambda _r: done.append(_r))
        rolled = router.roll(path, queue_capacity=32)
        outs = [r.result(timeout=240) for r in reqs]
        # exactly one terminal outcome per in-flight request ...
        assert len(done) == len(reqs)
        assert {id(r) for r in done} == {id(r) for r in reqs}
        # ... each served by a real version of the model, bitwise
        for o in outs:
            assert (np.array_equal(o, roll_fleet["out_v1"])
                    or np.array_equal(o, roll_fleet["out_v2"])), o
        # every replica was replaced, with drain, and counted
        assert rolled == 2
        assert fam.ARTIFACT_ROLL_REPLICAS.value == rolled0 + 2
        assert fam.ARTIFACT_ROLLS.labels(outcome="ok").value == ok0 + 1
        # the whole fleet now serves v2
        for _ in range(2):
            np.testing.assert_array_equal(
                router.submit(prompt, 4).result(timeout=240),
                roll_fleet["out_v2"])
    finally:
        router.close()


def test_roll_crash_mid_roll_recovers_at_new_version(roll_fleet):
    """Chaos criterion: a replica that dies MID-ROLL (after the
    factory swap, while another replica is rebuilding) is recovered by
    the ordinary monitor path — and comes back at the NEW version,
    because roll swaps the engine factory before the first drain."""
    from paddle_tpu.serving import DecodeEngine, ReplicaRouter

    v1, path = roll_fleet["v1"], roll_fleet["path"]
    prompt = roll_fleet["prompt"]

    def v1_factory(idx):
        return DecodeEngine(ROLL_CFG, params=v1, b_max=2,
                            max_len=ROLL_MAX_LEN, queue_capacity=32)

    router = ReplicaRouter(v1_factory, n_replicas=2, poll_s=0.05,
                           max_readmissions=3)
    try:
        router.submit(prompt, 4).result(timeout=240)
        art = export.load_artifact(path)
        killed = []

        def v2_factory(idx):
            if not killed:
                # first rebuild (replica 0 mid-roll): crash the OTHER,
                # not-yet-rolled replica — a terminal scheduler error
                # is exactly what alive() reports as death
                victim = router.replicas[1]
                victim.engine._error = RuntimeError("chaos: mid-roll")
                killed.append(victim.idx)
            return DecodeEngine.from_artifact(art, queue_capacity=32)

        restarts0 = sum(r.restarts for r in router.replicas)
        rolled = router.roll(None, engine_factory=v2_factory)
        assert killed == [1]
        # the roll completed (the crashed replica either rolled here or
        # was recovered concurrently by the monitor — both at v2)
        assert rolled == 2
        # recovery really happened (drain + rebuild, counted per slot)
        assert sum(r.restarts for r in router.replicas) \
            >= restarts0 + 2

        def _fleet_serves_v2():
            outs = [router.submit(prompt, 4).result(timeout=240)
                    for _ in range(4)]
            return all(np.array_equal(o, roll_fleet["out_v2"])
                       for o in outs)

        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if all(r.engine.alive() and not r.draining
                   for r in router.replicas) and _fleet_serves_v2():
                break
            time.sleep(0.1)
        else:
            pytest.fail("fleet never converged to v2 after mid-roll "
                        "crash")
    finally:
        router.close()


def test_from_artifact_without_serving_section_refuses(tmp_path):
    path = _fc_artifact(tmp_path)
    from paddle_tpu.serving import DecodeEngine

    d0 = fam.ARTIFACT_DEGRADED.labels(section="serving",
                                      reason="absent").value
    with pytest.raises(export.ArtifactError, match="serving"):
        DecodeEngine.from_artifact(path)
    assert fam.ARTIFACT_DEGRADED.labels(
        section="serving", reason="absent").value == d0 + 1

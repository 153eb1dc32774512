"""kernelcheck (repro.core.analyze): races, declaration audit, fusion.

Two halves: (1) the whole 24-kernel suite must come back *clean* - the
declarations the runtime trusts (reads/writes/combines/donates) are
verified, not assumed - and (2) deliberately broken fixture kernels must
trip each finding kind with the right kernel/stage/buffer named, because a
sanitizer that cannot find planted bugs proves nothing (the CI gate's
``--inject-*`` flags are these same fixtures).
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import analyze, cuda_suite
from repro.core.analyze import (
    Finding,
    FusionVerdict,
    SanitizerError,
    analyze_entry,
    analyze_kernel,
    report_to_json,
)
from repro.core.api import launch
from repro.core.kernel import KernelDef

SUITE = cuda_suite.build_suite(scale=1)


def _kinds(report):
    return {f.kind for f in report.findings}


# --- the suite is clean ------------------------------------------------------
@pytest.mark.parametrize("entry", SUITE, ids=lambda e: e.name)
def test_suite_entry_clean(entry):
    for report in analyze_entry(entry):
        assert report.clean, "\n".join(str(f) for f in report.findings)


def test_fusion_marks_at_least_three_suite_pairs_mergeable():
    verdicts = [v for e in SUITE for r in analyze_entry(e) for v in r.fusion]
    mergeable = [v for v in verdicts if v.mergeable]
    assert len(mergeable) >= 3, [str(v) for v in verdicts]
    # the known-provable pairs: matmul's private-init prologue and its
    # shared->global epilogue, and lud's last-step -> store epilogue
    got = {(v.kernel, v.pair) for v in mergeable}
    assert ("matmul_tiled", (0, 1)) in got
    assert any(k == "lud_diag" for k, _ in got)


def test_fusion_keeps_reduction_barriers():
    entry = next(e for e in SUITE if e.name == "reduce_shared")
    (report,) = analyze_entry(entry)
    assert report.clean
    # every reduction level reads another thread's slot: no pair mergeable
    assert all(not v.mergeable for v in report.fusion)


def test_fusion_sees_value_preserving_writes():
    """Soundness regression: a shared write that stores an *unchanged*
    value under the sample inputs (here: zeros over zero-initialized
    shared) still orders against other threads - the pair must NOT be
    proven mergeable, or the optimizer fuses a real cross-thread tree
    (the nn argmin select bug)."""
    def wr(ctx, st):
        return st.set_shared(
            s=st.shared["s"].at[ctx.tid].set(st.glob["x"][ctx.tid]))

    def rd(ctx, st):
        v = st.shared["s"][jnp.minimum(ctx.tid + 1, 3)]
        return st.set_glob(y=st.glob["y"].at[ctx.tid].set(v))

    k = KernelDef("noop_write", (wr, rd), writes=("y",), reads=("x", "y"),
                  shared={"s": ((4,), jnp.float32)})
    art = analyze.analyze_fusion(
        k, grid=1, block=4,
        args={"x": jnp.zeros(4, jnp.float32), "y": jnp.zeros(4, jnp.float32)})
    (v,) = art["verdicts"]
    assert not v["mergeable"]
    assert "different thread" in v["reason"]
    # and the no-op write keeps the cell non-private (no scalarization)
    assert not art["shared"]["s"]["private"]


# --- planted bugs: each finding kind fires with the right location -----------
def test_planted_race_caught():
    kernel, grid, block, args = analyze.planted_race()
    report = analyze_kernel(kernel, grid=grid, block=block, args=args)
    (f,) = [f for f in report.findings if f.kind == "shared-race"]
    assert f.kernel == "planted_race"
    assert f.buffer == "s"
    assert f.stage == 0
    assert "read-write" in f.detail


def test_planted_write_write_race_caught():
    def clash(ctx, st):
        # every thread stores its tid into slot 0: a WW race
        s = st.shared["s"].at[jnp.zeros_like(ctx.tid)].set(ctx.tid + 1)
        return st.set_shared(s=s)

    def store(ctx, st):
        out = st.glob["out"].at[ctx.tid].set(st.shared["s"][0])
        return st.set_glob(out=out)

    k = KernelDef("ww", (clash, store), writes=("out",), reads=("out",),
                  shared={"s": ((4,), jnp.int32)})
    report = analyze_kernel(k, grid=1, block=8,
                            args={"out": jnp.zeros(8, jnp.int32)})
    (f,) = [f for f in report.findings if f.kind == "shared-race"]
    assert f.stage == 0 and f.buffer == "s"
    assert "write-write" in f.detail


def test_masked_writeback_is_not_a_race():
    # the IR's conditional-write idiom: inactive threads store the value
    # already present - kernelcheck must not call that a race
    def level(ctx, st):
        s = st.shared["s"]
        active = ctx.tid < 4
        v = jnp.where(active, s[ctx.tid] + s[jnp.minimum(ctx.tid + 4, 7)],
                      s[ctx.tid])
        return st.set_shared(s=s.at[ctx.tid].set(v))

    def seed(ctx, st):
        return st.set_shared(
            s=st.shared["s"].at[ctx.tid].set(st.glob["x"][ctx.tid]))

    def store(ctx, st):
        out = st.glob["out"].at[ctx.tid].set(st.shared["s"][ctx.tid])
        return st.set_glob(out=out)

    k = KernelDef("masked", (seed, level, store), writes=("out",),
                  reads=("x", "out"), shared={"s": ((8,), jnp.float32)})
    report = analyze_kernel(k, grid=1, block=8,
                            args={"x": jnp.arange(8.0),
                                  "out": jnp.zeros(8)})
    assert report.clean, "\n".join(str(f) for f in report.findings)


def test_planted_undeclared_read_caught():
    kernel, grid, block, args = analyze.planted_undeclared_read()
    report = analyze_kernel(kernel, grid=grid, block=block, args=args)
    (f,) = [f for f in report.findings if f.kind == "undeclared-read"]
    assert f.buffer == "bias"
    assert "bias" in (f.suggestion or "")


def test_planted_bad_combine_caught():
    kernel, grid, block, args = analyze.planted_bad_combine()
    report = analyze_kernel(kernel, grid=grid, block=block, args=args)
    (f,) = [f for f in report.findings if f.kind == "combine-mismatch"]
    assert f.buffer == "out"
    assert '"sum"' in (f.suggestion or "")


def test_undeclared_write_and_unused_read_caught():
    def stage(ctx, st):
        extra = st.glob["extra"].at[ctx.tid].set(ctx.tid)
        out = st.glob["out"].at[ctx.tid].set(ctx.tid * 2)
        return st.set_glob(out=out, extra=extra)

    k = KernelDef("drift", (stage,), writes=("out",),
                  reads=("out", "ghost"))
    report = analyze_kernel(k, grid=1, block=16,
                            args={"out": jnp.zeros(16, jnp.int32),
                                  "extra": jnp.zeros(16, jnp.int32),
                                  "ghost": jnp.zeros(4, jnp.int32)})
    kinds = _kinds(report)
    assert "undeclared-write" in kinds    # extra written, not declared
    assert "unused-read" in kinds         # ghost declared, never touched
    assert "undeclared-read" in kinds     # extra's scatter implies a read
    by_kind = {f.kind: f for f in report.findings}
    assert by_kind["undeclared-write"].buffer == "extra"
    assert by_kind["unused-read"].buffer == "ghost"


def test_missing_reads_suggested():
    def stage(ctx, st):
        out = st.glob["out"].at[ctx.tid].set(st.glob["x"][ctx.tid])
        return st.set_glob(out=out)

    k = KernelDef("noreads", (stage,), writes=("out",))
    report = analyze_kernel(k, grid=1, block=8,
                            args={"x": jnp.arange(8.0),
                                  "out": jnp.zeros(8)})
    (f,) = [f for f in report.findings if f.kind == "missing-reads"]
    assert "'x'" in f.suggestion and "'out'" in f.suggestion


def test_oob_write_without_drop_caught():
    def stage(ctx, st):
        # index runs past the end with no mode="drop": memcheck territory
        out = st.glob["out"].at[ctx.tid * 2].set(1.0)
        return st.set_glob(out=out)

    k = KernelDef("oob", (stage,), writes=("out",), reads=("out",))
    report = analyze_kernel(k, grid=1, block=8,
                            args={"out": jnp.zeros(8)})
    (f,) = [f for f in report.findings if f.kind == "oob-write"]
    assert f.buffer == "out" and f.stage == 0
    assert "drop" in (f.suggestion or "")


def test_oob_write_with_explicit_drop_is_clean():
    def stage(ctx, st):
        out = st.glob["out"].at[ctx.tid * 2].set(1.0, mode="drop")
        return st.set_glob(out=out)

    k = KernelDef("oob_ok", (stage,), writes=("out",), reads=("out",))
    report = analyze_kernel(k, grid=1, block=8,
                            args={"out": jnp.zeros(8)})
    assert report.clean


def test_donation_hazard_caught():
    def overwrite(ctx, st):
        return st.set_glob(buf=st.glob["buf"].at[ctx.tid].set(ctx.tid * 1.0))

    def reread(ctx, st):
        out = st.glob["out"].at[ctx.tid].set(st.glob["buf"][7 - ctx.tid])
        return st.set_glob(out=out)

    k = KernelDef("hazard", (overwrite, reread), writes=("buf", "out"),
                  reads=("buf", "out"), donates=("buf",))
    report = analyze_kernel(k, grid=1, block=8,
                            args={"buf": jnp.ones(8), "out": jnp.zeros(8)})
    (f,) = [f for f in report.findings if f.kind == "donation-hazard"]
    assert f.buffer == "buf" and f.stage == 1


def test_incomplete_combines_caught():
    def stage(ctx, st):
        a = st.glob["a"].at[ctx.tid].set(1.0)
        b = st.glob["b"].at[ctx.tid].set(2.0)
        return st.set_glob(a=a, b=b)

    k = KernelDef("partial", (stage,), writes=("a", "b"),
                  reads=("a", "b"), combines={"a": "sum"})
    report = analyze_kernel(k, grid=1, block=8,
                            args={"a": jnp.zeros(8), "b": jnp.zeros(8)})
    (f,) = [f for f in report.findings if f.kind == "incomplete-combines"]
    assert f.buffer == "b"


def test_concat_ownership_violation_caught():
    def stage(ctx, st):
        # every block writes row 0: not an owned-slice pattern
        y = st.glob["y"].at[jnp.zeros_like(ctx.tid)].set(
            ctx.tid * 1.0 + ctx.bid, mode="drop")
        return st.set_glob(y=y)

    k = KernelDef("notconcat", (stage,), writes=("y",), reads=("y",),
                  combines={"y": "concat"})
    report = analyze_kernel(k, grid=4, block=8,
                            args={"y": jnp.zeros(4, jnp.float32)})
    assert any(f.kind == "combine-mismatch" and "owned slice" in f.detail
               for f in report.findings)


# --- definition-time combines validation (kernel.__post_init__) --------------
def test_combines_keys_validated_at_definition():
    def stage(ctx, st):
        return st

    with pytest.raises(ValueError, match="not in writes"):
        KernelDef("bad", (stage,), writes=("y",), combines={"x": "sum"})
    with pytest.raises(ValueError, match="combine mode"):
        KernelDef("bad", (stage,), writes=("y",), combines={"y": "xor"})


# --- launch-path integration -------------------------------------------------
def test_sanitize_launch_raises_on_findings():
    kernel, grid, block, args = analyze.planted_race()
    with pytest.raises(SanitizerError, match="shared-race"):
        launch(kernel, grid=grid, block=block, args=args, sanitize=True)


def test_sanitize_launch_clean_kernel_runs_and_memoizes():
    def stage(ctx, st):
        gid = ctx.bid * ctx.block_dim + ctx.tid
        return st.set_glob(
            out=st.glob["out"].at[gid].set(st.glob["x"][gid] * 2))

    k = KernelDef("dbl", (stage,), writes=("out",), reads=("x", "out"))
    args = {"x": jnp.arange(64.0), "out": jnp.zeros(64)}
    out = launch(k, grid=2, block=32, args=args, sanitize=True)
    np.testing.assert_allclose(np.asarray(out["out"]),
                               np.arange(64.0) * 2)
    launch(k, grid=2, block=32, args=args, sanitize=True)
    assert len(getattr(k, "_kernelcheck_ok")) == 1  # one memoized verdict


def test_sanitize_env_var(monkeypatch):
    kernel, grid, block, args = analyze.planted_undeclared_read()
    monkeypatch.setenv("CUPBOP_SANITIZE", "1")
    with pytest.raises(SanitizerError, match="undeclared-read"):
        launch(kernel, grid=grid, block=block, args=args)
    monkeypatch.setenv("CUPBOP_SANITIZE", "0")
    out = launch(kernel, grid=grid, block=block, args=args)
    assert "out" in out


def test_sanitize_false_overrides_env(monkeypatch):
    kernel, grid, block, args = analyze.planted_undeclared_read()
    monkeypatch.setenv("CUPBOP_SANITIZE", "1")
    out = launch(kernel, grid=grid, block=block, args=args, sanitize=False)
    assert "out" in out


# --- report plumbing ---------------------------------------------------------
def test_report_to_json_shape():
    kernel, grid, block, args = analyze.planted_race()
    report = analyze_kernel(kernel, grid=grid, block=block, args=args)
    doc = report_to_json([report])
    assert doc["schema"] == 1
    assert doc["summary"]["n_findings"] == len(report.findings)
    (kr,) = doc["kernels"]
    assert kr["kernel"] == "planted_race"
    assert {f["kind"] for f in kr["findings"]} == {"shared-race"}
    json.dumps(doc)  # serializable


def test_finding_and_verdict_str():
    f = Finding(kind="shared-race", kernel="k", buffer="s", stage=2,
                detail="boom", suggestion="fix it")
    assert "[shared-race] k stage 2 / s: boom" in str(f)
    v = FusionVerdict(kernel="k", pair=(0, 1), mergeable=True, reason="ok")
    assert "mergeable" in str(v)


# --- the CLI gate ------------------------------------------------------------
def _run_cli(*flags):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "repro.core.analyze", *flags],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_cli_clean_subset_exits_zero(tmp_path):
    out = tmp_path / "report.json"
    res = _run_cli("--kernels", "vecadd,reverse", "--json", str(out))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "kernelcheck: OK" in res.stdout
    doc = json.loads(out.read_text())
    assert doc["summary"]["n_findings"] == 0


def test_cli_injected_race_trips_gate():
    res = _run_cli("--kernels", "vecadd", "--inject-race")
    assert res.returncode == 1, res.stdout + res.stderr
    assert "kernelcheck: FAILED" in res.stdout
    assert "shared-race" in res.stdout


# --- the fusion artifact (kernelcheck-fusion-1): schema + CLI ----------------
def test_fusion_artifact_schema():
    """The documented stable schema core/optimize.py and tools consume."""
    entry = next(e for e in SUITE if e.name == "pixel_pipeline")
    (art,) = analyze.fusion_entry(entry)
    assert art["schema"] == analyze.FUSION_SCHEMA == "kernelcheck-fusion-1"
    assert art["kernel"] == "pixel_pipeline"
    assert art["n_stages"] == 3
    for v in art["verdicts"]:
        assert set(v) == {"kernel", "pair", "mergeable", "reason"}
        assert v["kernel"] == "pixel_pipeline"
        i, j = v["pair"]
        assert 0 <= i < j < art["n_stages"]
        assert isinstance(v["mergeable"], bool)
        assert isinstance(v["reason"], str) and v["reason"]
    pairs = {tuple(v["pair"]) for v in art["verdicts"]}
    # all adjacents, plus the skip pair of the maximal mergeable run
    assert {(0, 1), (1, 2), (0, 2)} <= pairs
    for name, facts in art["shared"].items():
        assert name in entry.kernel.shared
        assert set(facts) == {"stages", "last_stage", "private"}
    json.dumps(art)  # serializable as-is


def test_fusion_cli_json(tmp_path):
    out = tmp_path / "fusion.json"
    res = _run_cli("--fusion-only", "--kernels",
                   "pixel_pipeline,reduce_shared", "--json", str(out))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "fusion pixel_pipeline: 2/2 adjacent pairs mergeable" in res.stdout
    doc = json.loads(out.read_text())
    assert doc["schema"] == "kernelcheck-fusion-1"
    assert doc["summary"]["n_kernels"] == 2
    by_kernel = {a["kernel"]: a for a in doc["kernels"]}
    assert set(by_kernel) == {"pixel_pipeline", "reduce_shared"}
    # reduce_shared's barrier tree must stay unfused in the artifact too
    assert not any(v["mergeable"]
                   for v in by_kernel["reduce_shared"]["verdicts"])

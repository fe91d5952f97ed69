"""Static-analysis subsystem tests (PR 8).

* the jaxpr escape auditor detects a planted raw ``dot_general`` with
  the correct shape/flops, and audits a clean Engine-only model to zero
  escapes (including through ``lax.scan`` multiplicity);
* the ratchet: a manifest-covered escape passes, a NEW escape fails,
  a STALE manifest entry fails;
* the dtype auditor flags planted fp64 and a planted FP8 contraction
  that no capable backend accounts for — and stays silent on the
  Engine's own FP8 dispatches (which widen before the dot);
* the AST linter rules and artifact validators on planted violations,
  plus green runs over the real repo and shipped baselines.
"""

import dataclasses
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.analysis import audit as audit_cli
from repro.analysis import dtype_audit, entries, jaxpr_audit, lint
from repro.core import engine
from repro.core import precision as prec

F16 = jnp.float16
DNUMS = (((1,), (0,)), ((), ()))


def _sds(*shape, dtype=F16):
    return jax.ShapeDtypeStruct(shape, dtype)


# --------------------------------------------------------------------- #
# jaxpr escape auditor
# --------------------------------------------------------------------- #
def test_planted_dot_general_detected_with_shape_and_flops():
    def model(x, w, v):
        h = engine.matmul(x, w, policy=prec.PAPER_FP16)
        return lax.dot_general(h, v, DNUMS)   # planted escape

    res = jaxpr_audit.audit(
        "toy", model, (_sds(8, 16), _sds(16, 32), _sds(32, 4)))
    assert not res.clean
    assert len(res.escapes) == 1
    esc = res.escapes[0]
    assert esc.lhs_shape == (8, 32) and esc.rhs_shape == (32, 4)
    assert esc.flops == 2 * 8 * 32 * 4
    assert esc.count == 1
    assert "float16" in esc.fingerprint


def test_clean_engine_only_model_zero_escapes():
    def model(x, w, v):
        h = engine.matmul(x, w, policy=prec.PAPER_FP16)
        return engine.matmul(h, v, policy=prec.PAPER_FP16)

    res = jaxpr_audit.audit(
        "toy", model, (_sds(8, 16), _sds(16, 32), _sds(32, 4)))
    assert res.clean and not res.unmatched_events
    assert res.n_events == 2


def test_scan_multiplicity_reconciles_and_escapes():
    w_sd = _sds(16, 16)

    def clean(x, w):
        with engine.repeat(5):
            y, _ = lax.scan(
                lambda c, _: (engine.matmul(c, w, policy=prec.PAPER_FP16),
                              None),
                x, None, length=5)
        return y

    res = jaxpr_audit.audit("toy", clean, (_sds(4, 16), w_sd))
    assert res.clean and not res.unmatched_events

    def planted(x, w):
        y, _ = lax.scan(lambda c, _: (lax.dot_general(c, w, DNUMS), None),
                        x, None, length=5)
        return y

    res = jaxpr_audit.audit("toy", planted, (_sds(4, 16), w_sd))
    assert len(res.escapes) == 1
    assert res.escapes[0].count == 5          # scan length multiplies in
    assert res.escapes[0].path == ("scan",)


def test_value_and_grad_backward_gemms_reconcile():
    """The Engine's custom-vjp backward dots must all be event-accounted —
    a grad trace is where escapes would silently double."""
    def loss(x, w):
        y = engine.matmul(x, w, policy=prec.PAPER_FP16)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    x = jnp.ones((8, 16), F16)

    def step(w):
        return jax.value_and_grad(lambda q: loss(x, q))(w)

    res = jaxpr_audit.audit("toy", step, (jnp.ones((16, 32), F16),))
    assert res.clean, [s.describe() for s in res.escapes]
    assert not res.unmatched_events


# --------------------------------------------------------------------- #
# ratchet semantics
# --------------------------------------------------------------------- #
def _toy_result(planted: bool):
    def model(x, w, v):
        h = engine.matmul(x, w, policy=prec.PAPER_FP16)
        return lax.dot_general(h, v, DNUMS) if planted else h

    return jaxpr_audit.audit(
        "toy", model, (_sds(8, 16), _sds(16, 32), _sds(32, 4)))


def test_ratchet_new_escape_fails():
    errors = audit_cli.ratchet_errors(
        "toy", _toy_result(planted=True), {"jaxpr": {"toy": []}})
    assert errors and "NEW escaped contraction" in errors[0]


def test_ratchet_manifest_covered_escape_passes():
    res = _toy_result(planted=True)
    manifest = {"jaxpr": {"toy": [
        {"fingerprint": res.escapes[0].fingerprint, "count": 1}]}}
    assert audit_cli.ratchet_errors("toy", res, manifest) == []


def test_ratchet_stale_entry_fails():
    manifest = {"jaxpr": {"toy": [
        {"fingerprint": "float16[1, 1]·float16[1, 1]->float16 "
                        "C[1];[0] B[];[]", "count": 1}]}}
    errors = audit_cli.ratchet_errors(
        "toy", _toy_result(planted=False), manifest)
    assert errors and "STALE manifest entry" in errors[0]


# --------------------------------------------------------------------- #
# dtype auditor
# --------------------------------------------------------------------- #
def test_dtype_audit_flags_planted_fp64():
    with jax.enable_x64(True):
        def model(x):
            return jnp.sum(x.astype(jnp.float64) * 2.0)

        closed, events = jaxpr_audit.trace_entry(
            "toy", model, (_sds(4, 4, dtype=jnp.float32),))
    findings = dtype_audit.audit_dtypes(closed, events)
    assert any(f.kind == "fp64" for f in findings), findings


def test_dtype_audit_flags_raw_fp8_contraction():
    def model(x, w):
        x8 = x.astype(jnp.float8_e4m3fn)
        w8 = w.astype(jnp.float8_e4m3fn)
        return lax.dot_general(x8, w8, DNUMS,
                               preferred_element_type=jnp.float32)

    closed, events = jaxpr_audit.trace_entry(
        "toy", model, (_sds(8, 16, dtype=jnp.float32),
                       _sds(16, 8, dtype=jnp.float32)))
    findings = dtype_audit.audit_dtypes(
        closed, events, extra_allowed=("float32",))
    assert [f.kind for f in findings] == ["fp8_uncovered"]


def test_dtype_audit_silent_on_engine_fp8_dispatch():
    """The Engine widens FP8 storage to the compute dtype around the XLA
    dot — a scaled dispatch must produce zero conformance findings."""
    def model(x, w):
        return engine.matmul(x, w, policy=prec.MIXED_FP8_E4M3)

    closed, events = jaxpr_audit.trace_entry(
        "toy", model, (_sds(8, 16), _sds(16, 32)))
    assert events, "scaled dispatch emitted no events"
    assert dtype_audit.audit_dtypes(closed, events) == []
    # and the escape audit still reconciles through the quantize ops
    res = jaxpr_audit.reconcile("toy", jaxpr_audit.collect_dots(closed),
                                events)
    assert res.clean


def test_shipped_policies_conform():
    assert dtype_audit.check_shipped_policies() == []


# --------------------------------------------------------------------- #
# registered entries + CLI acceptance
# --------------------------------------------------------------------- #
def test_ae_train_entry_audits_clean_against_manifest():
    """Acceptance: `python -m repro.analysis.audit --entry ae_train` exits
    zero on the manifest-covered tree."""
    assert audit_cli.run(["ae_train"], audit_cli.DEFAULT_MANIFEST) == 0


def test_cli_nonzero_on_planted_escape(monkeypatch, tmp_path):
    """Acceptance: a planted escaped dot_general makes the CLI exit
    non-zero (the manifest does not cover it)."""
    def build():
        def model(x, w):
            return lax.dot_general(x, w, DNUMS)
        return model, (_sds(8, 16), _sds(16, 4))

    monkeypatch.setitem(entries.ENTRY_POINTS, "toy_planted", build)
    manifest = tmp_path / "escapes.json"
    manifest.write_text(json.dumps({"jaxpr": {}, "ast": []}))
    report = tmp_path / "report.json"
    assert audit_cli.run(["toy_planted"], str(manifest),
                         str(report)) == 1
    rep = json.loads(report.read_text())
    assert rep["errors"] and rep["entries"]["toy_planted"]["escapes"]


def test_every_registered_entry_builds():
    for name in entries.ENTRY_POINTS:
        fn, args = entries.get_entry(name)
        assert callable(fn) and len(args) >= 1
    with pytest.raises(KeyError):
        entries.get_entry("nope")


# --------------------------------------------------------------------- #
# AST linter
# --------------------------------------------------------------------- #
def _plant_tree(tmp_path, rel, source):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return path


def test_lint_flags_planted_violations(tmp_path):
    _plant_tree(tmp_path, "models/bad.py", """
        import os
        import jax.numpy as jnp

        EVENT_LOG = []

        def f(x, w, spec):
            spec.m = 5
            os._exit(1)
            y = jnp.einsum("ij,jk->ik", x, w)
            return y @ w
    """)
    manifest = tmp_path / "escapes.json"
    manifest.write_text(json.dumps({"jaxpr": {}, "ast": []}))
    rules = {v[2] for v in lint.lint_sources(str(tmp_path), str(manifest))}
    assert rules == {"models-gemm", "os-exit", "spec-mutation",
                     "module-collector"}


def test_lint_manifest_allows_and_ratchets(tmp_path):
    _plant_tree(tmp_path, "models/ok.py", """
        import jax.numpy as jnp

        def f(x, w):
            return jnp.einsum("ij,jk->ik", x, w)
    """)
    allow = {"jaxpr": {}, "ast": [{"file": "models/ok.py",
                                   "call": "jnp.einsum",
                                   "equation": "ij,jk->ik", "count": 1}]}
    # manifest-covered: clean — but the same manifest against a tree
    # where the site was fixed reports the entry as stale
    m = tmp_path / "escapes.json"

    def _relativize(entries_):
        # lint reports files relative to the repo root; point the
        # manifest at the planted tree's actual relpath
        rel = os.path.relpath(tmp_path, lint._REPO_ROOT)
        return [dict(e, file=os.path.join(rel, e["file"]))
                for e in entries_]

    m.write_text(json.dumps({"jaxpr": {},
                             "ast": _relativize(allow["ast"])}))
    assert lint.lint_sources(str(tmp_path), str(m)) == []

    (tmp_path / "models" / "ok.py").write_text("def f():\n    return 0\n")
    stale = lint.lint_sources(str(tmp_path), str(m))
    assert stale and stale[0][2] == "models-gemm" \
        and "STALE" in stale[0][3]


def test_lint_real_repo_is_clean():
    assert lint.lint_sources() == []


def test_gemmspec_field_list_in_sync():
    """The linter keeps GemmSpec's field names as literals (it must not
    import jax); fail here if the dataclass drifts."""
    assert lint._GEMMSPEC_FIELDS == {
        f.name for f in dataclasses.fields(engine.GemmSpec)}


# --------------------------------------------------------------------- #
# artifact validation
# --------------------------------------------------------------------- #
def test_autotune_cache_validation(tmp_path):
    good = {"m256-n512-k256-float16-float32-float16-none-xla":
            {"bm": 128, "bn": 128, "bk": 128, "source": "heuristic",
             "us": 1.0}}
    p = tmp_path / "cache.json"
    p.write_text(json.dumps(good))
    assert lint.validate_autotune_cache(str(p)) == []

    bad = {"m4096-n4096-k4096-float32-float32-float32-none-pallas-d4":
           {"bm": 2048, "bn": 2048, "bk": 2048, "source": "measured",
            "us": 1.0},
           "not a key": {"bm": 1, "bn": 1, "bk": 1}}
    p.write_text(json.dumps(bad))
    rules = [v[2] for v in lint.validate_autotune_cache(str(p))]
    assert rules == ["autotune-cache", "autotune-cache"]


def test_shipped_baselines_satisfy_analytic_identities():
    assert lint.validate_baselines() == []


def test_baseline_validation_catches_broken_identity(tmp_path):
    src = os.path.join(lint._REPO_ROOT, "benchmarks", "baselines")
    for name in os.listdir(src):
        if name.endswith(".json"):
            (tmp_path / name).write_text(
                open(os.path.join(src, name)).read())
    tf = json.loads((tmp_path / "train_flops.json").read_text())
    tf["ae_train_B16"]["bwd"] += 2          # break bwd == 2*fwd and total
    (tmp_path / "train_flops.json").write_text(json.dumps(tf))
    probs = lint.validate_baselines(str(tmp_path))
    assert any("total != fwd + bwd" in v[3] for v in probs)


def test_shipped_escape_manifest_is_well_formed():
    assert lint.validate_escape_manifest() == []

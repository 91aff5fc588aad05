"""The static-analysis & program-audit subsystem (tmr_tpu/analysis).

Three layers of coverage:

1. **fixture proof per rule** — every AST rule and every program-tier
   predicate is proven to FIRE on a minimal bad fixture (a lint that
   can't fail can't protect anything) and to stay silent on the fixed
   version;
2. **the committed tree is clean** — the full AST tier over the real
   repo with the committed baseline yields zero unbaselined findings,
   and scripts/analyze.py emits a validated ``analysis_report/v1``
   saying so (rc 0);
3. **the program tier holds across gate states** — all 8
   TMR_DECODER_IMPL x TMR_QUANT x TMR_DECODE_TAIL combinations pass the
   jaxpr invariants on the reduced CPU geometry in tier-1 (slow-marked:
   the production sam_vit_b sweep at the 128^2 decoder grid).

Everything here runs under the conftest env (JAX_PLATFORMS=cpu, 8
forced host devices) — the transfer-guard pins are per-platform
precisely so that this works.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tmr_tpu.analysis import (
    Baseline,
    Finding,
    build_report,
    default_baseline_path,
    run_ast_passes,
)
from tmr_tpu.diagnostics import validate_analysis_report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: minimal registry/diagnostics stand-ins every mini-repo carries so the
#: hardwired-path passes (knob-parity, report-parity) have their anchors
_MINI_CONFIG = '''
ENV_KNOBS = {
    "TMR_DOCUMENTED": "a documented knob",
}
'''
_MINI_DIAG = '''
FOO_SCHEMA = "foo_report/v1"


def validate_foo_report(doc):
    return []
'''


def _mini_repo(tmp_path, files):
    """Materialize a throwaway repo layout: config/diagnostics defaults
    plus the caller's files ({relpath: source})."""
    defaults = {
        "tmr_tpu/__init__.py": "",
        "tmr_tpu/config.py": _MINI_CONFIG,
        "tmr_tpu/diagnostics.py": _MINI_DIAG,
    }
    for rel, src in {**defaults, **files}.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(tmp_path)


def _findings(root, rule_id, baseline=None):
    return run_ast_passes(root=root, rules=[rule_id], baseline=baseline)


# ===================================================================== AST
def test_jit_hygiene_fires_on_each_side_effect(tmp_path):
    root = _mini_repo(tmp_path, {"tmr_tpu/bad.py": '''
        import os
        import time

        import jax
        import numpy as np

        _CACHE = {}
        _COUNT = 0


        @jax.jit
        def bad(x):
            global _COUNT
            t = time.time()
            r = np.random.default_rng(0).standard_normal(3)
            mode = os.environ.get("TMR_SOMETHING", "off")
            print("tracing", mode)
            _CACHE["last"] = t
            _COUNT = 1
            return x + r.sum()


        def clean_host_helper():
            # NOT jit-compiled: the same constructs are legal here
            print("fine", file=None) if False else None
            return os.environ.get("TMR_SOMETHING")
    '''})
    msgs = [f.message for f in _findings(root, "jit-hygiene")]
    assert any("time.time" in m for m in msgs)
    assert any("random" in m for m in msgs)
    assert any("environment" in m for m in msgs)
    assert any("print" in m for m in msgs)
    assert any("_CACHE" in m for m in msgs)
    assert any("_COUNT" in m for m in msgs)
    assert all("bad" in m for m in msgs), "host helper must not be flagged"


def test_jit_hygiene_covers_partial_alias_and_posthoc_wrap(tmp_path):
    root = _mini_repo(tmp_path, {"tmr_tpu/alias.py": '''
        import functools
        import time

        import jax

        jit = functools.partial(jax.jit, donate_argnums=(0,))


        @jit
        def aliased(x):
            return x + time.time()


        def wrapped_later(x):
            return x * time.perf_counter()


        run = jax.jit(wrapped_later)
    '''})
    found = _findings(root, "jit-hygiene")
    names = {f.message.split("'")[1] for f in found}
    assert names == {"aliased", "wrapped_later"}


def test_lock_discipline_fires_and_lock_silences(tmp_path):
    bad = '''
        import threading


        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self.counts = {}
                self._thread = threading.Thread(target=self._loop)

            def _loop(self):
                self.counts["loop"] = 1  # unlocked write, thread side

            def snapshot(self):
                return dict(self.counts)  # read, caller side
    '''
    root = _mini_repo(tmp_path, {"tmr_tpu/serve/pool.py": bad})
    found = _findings(root, "lock-discipline")
    assert len(found) == 1 and "counts" in found[0].message

    fixed = bad.replace(
        'self.counts["loop"] = 1  # unlocked write, thread side',
        'with self._lock:\n'
        '                    self.counts["loop"] = 1',
    )
    root2 = _mini_repo(tmp_path / "fixed", {"tmr_tpu/serve/pool.py": fixed})
    assert _findings(root2, "lock-discipline") == []


def test_lock_discipline_atomics_whitelist_and_module_globals(tmp_path):
    src = '''
        import threading

        _LOG = []


        def worker():
            threading.Thread(target=record).start()


        def record():
            _LOG.append(1)
    '''
    root = _mini_repo(tmp_path, {"tmr_tpu/utils/faults.py": src})
    found = _findings(root, "lock-discipline")
    assert len(found) == 1 and "_LOG" in found[0].message

    baseline = Baseline({
        "suppressions": [],
        "lock_atomics": [{"file": "tmr_tpu/utils/faults.py",
                          "attr": "_LOG",
                          "reason": "GIL-atomic append, test fixture"}],
    })
    assert _findings(root, "lock-discipline", baseline=baseline) == []


def test_knob_parity_fires_both_directions(tmp_path):
    root = _mini_repo(tmp_path, {
        "tmr_tpu/config.py": '''
            ENV_KNOBS = {
                "TMR_DOCUMENTED": "consumed below",
                "TMR_STALE": "nothing consumes this",
            }
        ''',
        "tmr_tpu/mod.py": '''
            import os


            def f():
                a = os.environ.get("TMR_DOCUMENTED")
                b = os.environ.get("TMR_UNDOCUMENTED")
                return a, b
        ''',
    })
    msgs = [f.message for f in _findings(root, "knob-parity")]
    assert any("TMR_UNDOCUMENTED" in m and "missing" in m for m in msgs)
    assert any("TMR_STALE" in m and "stale" in m.lower() or
               "no code" in m for m in msgs)


def test_knob_import_time_fires_direct_and_via_helper(tmp_path):
    root = _mini_repo(tmp_path, {"tmr_tpu/eager.py": '''
        import os


        def _env_flag(name, default=False):
            return os.environ.get(name, "") not in ("", "0")


        DIRECT = os.environ.get("TMR_DIRECT", "0")
        VIA_HELPER = _env_flag("TMR_HELPER")


        def lazy():
            return os.environ.get("TMR_LAZY")  # call-time: legal
    '''})
    found = _findings(root, "knob-import-time")
    assert len(found) == 2
    assert any("TMR_DIRECT" in f.message for f in found)
    assert any("TMR_HELPER" in f.message for f in found)


def test_report_parity_fires_on_missing_validators(tmp_path):
    root = _mini_repo(tmp_path, {
        "tmr_tpu/diagnostics.py": '''
            FOO_SCHEMA = "foo_report/v1"


            def validate_foo_report(doc):
                return []


            BARE_SCHEMA = "bare_report/v1"
        ''',
        "scripts/emit.py": '''
            from tmr_tpu.diagnostics import FOO_REPORT_SCHEMA

            print({"schema": FOO_REPORT_SCHEMA})
        ''',
    })
    found = _findings(root, "report-parity")
    assert any("bare_report" in f.message for f in found)
    assert any("validate_foo_report" in f.message
               and f.file == "scripts/emit.py" for f in found)


def test_stdout_hygiene_fires_on_bare_print_only(tmp_path):
    root = _mini_repo(tmp_path, {"tmr_tpu/noisy.py": '''
        import sys


        def f():
            print("bare")
            print("to stderr", file=sys.stderr)
    '''})
    found = _findings(root, "stdout-hygiene")
    assert len(found) == 1
    assert 'print("bare")' in (tmp_path / "tmr_tpu/noisy.py"
                               ).read_text().splitlines()[found[0].line - 1]


def test_baseline_suppression_and_validation(tmp_path):
    f = Finding("stdout-hygiene", "tmr_tpu/noisy.py", 5, "bare print() x")
    b = Baseline({"suppressions": [{
        "rule": "stdout-hygiene", "file": "tmr_tpu/noisy.py",
        "match": "bare print", "reason": "fixture",
    }]})
    assert b.allows(f)
    assert not b.allows(Finding("stdout-hygiene", "tmr_tpu/other.py", 5,
                                "bare print() x"))
    assert not b.allows(Finding("jit-hygiene", "tmr_tpu/noisy.py", 5,
                                "bare print() x"))
    # a suppression without a reason is rejected at load
    with pytest.raises(ValueError, match="reason"):
        Baseline({"suppressions": [{"rule": "r", "file": "f"}]})
    # round-trip
    path = tmp_path / "b.json"
    b.save(str(path))
    b2 = Baseline.load(str(path))
    assert b2.allows(f)


def test_report_builder_and_validator(tmp_path):
    b = Baseline()
    f = Finding("stdout-hygiene", "tmr_tpu/noisy.py", 5, "bare print()")
    doc = build_report([f], b, program_audit=None, root="/x")
    assert validate_analysis_report(doc) == []
    assert doc["checks"]["clean"] is False
    assert doc["counts_by_rule"] == {"stdout-hygiene": 1}
    # suppressed -> clean
    b2 = Baseline({"suppressions": [{
        "rule": "stdout-hygiene", "file": "tmr_tpu/noisy.py",
        "reason": "fixture",
    }]})
    doc2 = build_report([f], b2, program_audit=None, root="/x")
    assert doc2["checks"]["clean"] is True
    assert doc2["baselined_count"] == 1
    # the error record is contractually valid; garbage is not
    assert validate_analysis_report(
        {"schema": "analysis_report/v1", "error": "boom"}
    ) == []
    assert validate_analysis_report({"schema": "nope"})


# ============================================================ program tier
def test_audit_jaxpr_s2_and_transfer_predicates():
    import jax
    import jax.numpy as jnp

    from tmr_tpu.analysis.program_audit import audit_jaxpr

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def dense(a):  # materializes a (64*64, 64*64)-shaped outer product
        f = a.reshape(-1)
        return (f[:, None] * f[None, :]).sum()

    S2 = (64 * 64) ** 2  # the bound a (4096,)-token attention would pin
    j = jax.make_jaxpr(dense)(x)
    rec = audit_jaxpr(j, "fixture", s2_bound=S2)
    assert not rec["ok"] and any("S^2" in p for p in rec["problems"])
    # streaming form stays under the bound
    j2 = jax.make_jaxpr(lambda a: (a * a).sum())(x)
    assert audit_jaxpr(j2, "fixture", s2_bound=S2)["ok"]

    def hops(a):
        b = jax.device_put(a)
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(a.shape, a.dtype), b
        )

    j3 = jax.make_jaxpr(hops)(x)
    rec3 = audit_jaxpr(j3, "fixture", transfer_pin=0)
    assert not rec3["ok"]
    assert any("callback" in p for p in rec3["problems"])
    assert any("device_put" in p for p in rec3["problems"])
    assert audit_jaxpr(j3, "fixture", transfer_pin=1)["problems"] == [
        p for p in audit_jaxpr(j3, "fixture", transfer_pin=1)["problems"]
        if "device_put" not in p
    ]


def test_audit_jaxpr_sees_inside_cond_branches():
    """cond/switch store their sub-jaxprs in a TUPLE param
    ('branches') — the walker must descend into it, or every invariant
    is blind inside conditionals (regression pin: a pure_callback
    hidden in a lax.cond branch must count)."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.analysis.program_audit import audit_jaxpr, jaxpr_stats

    def f(a):
        return jax.lax.cond(
            a.sum() > 0,
            lambda v: jax.pure_callback(
                lambda x: x, jax.ShapeDtypeStruct(v.shape, v.dtype), v
            ),
            lambda v: v * 2,
            a,
        )

    j = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.float32))
    assert jaxpr_stats(j)["callbacks"] == 1
    rec = audit_jaxpr(j, "fixture")
    assert not rec["ok"] and any("callback" in p for p in rec["problems"])


def test_audit_jaxpr_f64_and_quant_widen_predicates():
    import jax
    import jax.numpy as jnp

    from tmr_tpu.analysis.program_audit import audit_jaxpr

    with jax.enable_x64(True):
        j = jax.make_jaxpr(
            lambda a: a.astype(jnp.float64) * 2.0
        )(jax.ShapeDtypeStruct((8,), jnp.float32))
    rec = audit_jaxpr(j, "fixture")
    assert not rec["ok"] and any("float64" in p for p in rec["problems"])
    recq = audit_jaxpr(j, "fixture", quant=True)
    assert any("quantized path" in p for p in recq["problems"])
    # f32 program: both rules silent
    j2 = jax.make_jaxpr(lambda a: a.astype(jnp.bfloat16))(
        jax.ShapeDtypeStruct((8,), jnp.float32)
    )
    assert audit_jaxpr(j2, "fixture", quant=True)["ok"]


def test_attention_impls_hold_no_s2_at_production_grid():
    from tmr_tpu.analysis.program_audit import (
        NO_S2_ATTN_IMPLS,
        audit_attention_impls,
    )

    rec = audit_attention_impls(grids=((64, 64),))
    assert rec["ok"], rec
    audited = {
        k.split(":")[1].split("@")[0]
        for k, v in rec["impls"].items() if "skipped" not in v
    }
    # every contractually-streaming impl actually traced and was audited
    assert set(NO_S2_ATTN_IMPLS) <= audited


def test_program_audit_default_state_production_programs():
    """The bucketed production programs (sam_vit_b reduced CPU
    geometry) pass every invariant under the ambient env, and the
    transfer pins hold under the forced-8-device CPU conftest — which
    is also where the mesh-sharded serve variant (match_heads_dp, the
    shard_map dp program) is traceable and audited."""
    from tmr_tpu.analysis.program_audit import audit_production_programs

    rec = audit_production_programs(image_size=64, include_attention=False)
    assert rec["ok"], rec["problems"]
    names = {r["name"] for r in rec["states"][0]["programs"]}
    assert names == {"match_heads", "match_heads_dp", "backbone",
                     "heads_only", "nms_topk"}
    assert rec["platform"] == "cpu"


def test_program_audit_all_eight_gate_states_reduced_geometry():
    """TMR_DECODER_IMPL={xla,fused} x TMR_QUANT={off,int8} x
    TMR_DECODE_TAIL={host,device}: every combination's traced program
    passes the jaxpr invariants on the reduced CPU geometry (the tiny
    backbone keeps this in tier-1; the slow test runs the production
    sam_vit_b sweep)."""
    from tmr_tpu.analysis.program_audit import (
        ALL_GATE_STATES,
        audit_production_programs,
    )

    rec = audit_production_programs(
        image_size=64, emb_dim=16, backbone="resnet50_layer1",
        gate_states=ALL_GATE_STATES, include_attention=False,
        programs=("match_heads",),
    )
    assert rec["ok"], rec["problems"]
    assert len(rec["states"]) == 8
    seen = {tuple(sorted(s["gate_state"].items())) for s in rec["states"]}
    assert len(seen) == 8
    for state in rec["states"]:
        assert state["ok"], state


@pytest.mark.slow
def test_program_audit_production_geometry_full_sweep():
    """The production 128^2 decoder-grid geometry (image 1024,
    sam_vit_b, 2000 detection slots): all 8 gate states pass, plus the
    full four-program default-state audit and both attention grids."""
    from tmr_tpu.analysis.program_audit import (
        ALL_GATE_STATES,
        audit_production_programs,
    )

    rec = audit_production_programs(
        image_size=1024, max_detections=2000,
        gate_states=ALL_GATE_STATES,
        attention_grids=((64, 64), (96, 96)),
    )
    assert rec["ok"], rec["problems"]
    assert len(rec["states"]) == 8


# ================================================================== repo
def test_committed_tree_has_zero_unbaselined_findings():
    """THE acceptance pin: the full AST tier over the real tree with the
    committed baseline is clean (jit-hygiene and lock-discipline run
    here; the knob/report/stdout rules also ride their original
    test_small_utils wrappers)."""
    baseline = Baseline.load(default_baseline_path(REPO))
    findings = [
        f for f in run_ast_passes(root=REPO, baseline=baseline)
        if not baseline.allows(f)
    ]
    assert findings == [], "\n".join(str(f) for f in findings)


def test_run_analysis_library_entry():
    """The one-call library entry returns a validated clean report on
    the committed tree (AST tier; the program tier rides its own
    tests)."""
    from tmr_tpu.analysis import run_analysis

    doc = run_analysis(root=REPO, with_program_audit=False)
    assert doc["checks"]["ast_clean"] is True
    assert validate_analysis_report(doc) == []


def test_analyze_script_emits_validated_report(tmp_path):
    """scripts/analyze.py (AST tier) under the conftest CPU env: rc 0,
    ONE validated analysis_report/v1 JSON line on stdout, --out file
    matches, checks.clean true."""
    out = tmp_path / "analysis.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "analyze.py"),
         "--no-program-audit", "--json", "--out", str(out)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    doc = json.loads(lines[0])
    assert validate_analysis_report(doc) == []
    assert doc["checks"]["clean"] is True
    assert doc["schema"] == "analysis_report/v1"
    assert set(doc["rules"]) >= {
        "jit-hygiene", "lock-discipline", "knob-parity",
        "knob-import-time", "report-parity", "stdout-hygiene",
    }
    assert json.loads(out.read_text())["checks"]["clean"] is True


def test_analyze_baseline_update_emits_baseline_tagged_line(tmp_path):
    """--baseline-update's stdout line is tagged analysis_baseline/v1,
    NOT analysis_report/v1 — a report-tagged line must always pass
    validate_analysis_report, and this one structurally can't."""
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"schema": "analysis_baseline/v1",
                              "suppressions": [], "lock_atomics": [],
                              "transfer_guard": {}}))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "analyze.py"),
         "--no-program-audit", "--baseline", str(bl),
         "--baseline-update"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["schema"] == "analysis_baseline/v1"
    assert doc["baseline_updated"] == str(bl)


def test_analyze_script_nonzero_on_findings(tmp_path):
    """A dirty tree (bare print fixture) makes analyze.py exit 1 and
    carry the finding in the report — the CI gate is the exit code."""
    root = _mini_repo(tmp_path, {"tmr_tpu/noisy.py": '''
        def f():
            print("bare")
    '''})
    # the script analyzes ITS OWN repo root; drive the library instead
    # (subprocess-level rc is covered above) and pin the contract the
    # script builds on: findings -> clean False
    baseline = Baseline()
    findings = run_ast_passes(root=root, baseline=baseline)
    doc = build_report(findings, baseline, root=root)
    assert doc["checks"]["clean"] is False
    assert validate_analysis_report(doc) == []

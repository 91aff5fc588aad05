"""Run the repo-wide static analysis + compiled-program audit and emit
ONE ``analysis_report/v1`` JSON line (tmr_tpu/analysis).

Two tiers, both riding this one entry point:

- the AST tier (jit-hygiene, lock-discipline, knob-parity,
  knob-import-time, report-parity, stdout-hygiene) walks the source
  tree — no jax, sub-second;
- the program tier traces the bucketed production programs (backbone,
  fused match+heads, heads-only, nms_topk) plus every attention
  formulation to jaxprs and asserts the structural invariants (no-S²,
  no-f64, quant-widen, transfer guard). Trace-only: no compile, no
  device execution — safe on any backend, and the CPU run audits the
  same programs the TPU serves.

Flags:
  --json               accepted for uniformity (the JSON line is the
                       default and only stdout output — bench_guard's
                       one-line contract)
  --out FILE           additionally write the document, indented
  --baseline PATH      suppression baseline (default:
                       <repo>/analysis_baseline.json)
  --baseline-update    rewrite the baseline's suppression list from the
                       CURRENT findings (each entry still needs a human
                       reason — the writer stamps a placeholder you must
                       edit before committing) and exit 0
  --no-program-audit   AST tier only (fast pre-commit loop)
  --gate-states all    sweep all 8 decoder/quant/decode-tail gate states
                       (default: the ambient env only)
  --image-size N       program-audit trace geometry (default 64 on CPU,
                       1024 on TPU — the production 128^2 decoder grid)

Exit code: 0 when ``checks.clean`` (zero unbaselined findings and a
passing program audit), 1 otherwise — CI can gate on the code alone.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tmr_tpu.utils.bench_guard import run_guarded  # noqa: E402

from tmr_tpu.diagnostics import (  # noqa: E402
    ANALYSIS_REPORT_SCHEMA,
    validate_analysis_report,
)


def _emit_error(msg: str):
    print(json.dumps({"schema": ANALYSIS_REPORT_SCHEMA, "error": msg}),
          flush=True)


def _run(cancel) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="emit the analysis_report/v1 JSON line (default)")
    ap.add_argument("--out", default=None,
                    help="also write the document to this path, indented")
    ap.add_argument("--baseline", default=None,
                    help="suppression baseline path")
    ap.add_argument("--baseline-update", action="store_true",
                    help="rewrite the baseline suppressions from current "
                         "findings and exit")
    ap.add_argument("--no-program-audit", action="store_true",
                    help="AST tier only (no jax import)")
    ap.add_argument("--gate-states", choices=("env", "all"), default="env",
                    help="program audit under the ambient env, or the "
                         "full 2x2x2 decoder/quant/decode-tail sweep")
    ap.add_argument("--image-size", type=int, default=None,
                    help="program-audit geometry (default 64 cpu / "
                         "1024 tpu)")
    args = ap.parse_args()

    from tmr_tpu.analysis import (
        Baseline,
        build_report,
        default_baseline_path,
        run_ast_passes,
    )
    from tmr_tpu.analysis.core import default_repo_root

    root = default_repo_root()
    baseline_path = args.baseline or default_baseline_path(root)
    baseline = Baseline.load(baseline_path)
    findings = run_ast_passes(root=root, baseline=baseline)

    if args.baseline_update:
        cancel()
        baseline.suppressions = [
            {"rule": f.rule, "file": f.file, "match": f.message,
             "reason": "TODO: justify this suppression before committing"}
            for f in findings if not baseline.allows(f)
        ] + baseline.suppressions
        baseline.save(baseline_path)
        from tmr_tpu.analysis.core import BASELINE_SCHEMA

        # tagged as a BASELINE document, not analysis_report/v1 — a
        # report-tagged line must always pass validate_analysis_report
        print(json.dumps({
            "schema": BASELINE_SCHEMA,
            "baseline_updated": baseline_path,
            "suppressions": len(baseline.suppressions),
        }), flush=True)
        return 0

    program = None
    if not args.no_program_audit:
        from tmr_tpu.utils.cache import enable_compilation_cache

        enable_compilation_cache()  # the gate self-checks jit; reuse them
        import jax

        from tmr_tpu.analysis.program_audit import (
            ALL_GATE_STATES,
            audit_production_programs,
        )

        on_tpu = jax.default_backend() == "tpu"
        size = args.image_size or (1024 if on_tpu else 64)
        program = audit_production_programs(
            baseline=baseline,
            image_size=size,
            gate_states=(ALL_GATE_STATES if args.gate_states == "all"
                         else None),
            attention_grids=((64, 64), (96, 96)),
            record_refusals=True,
        )

    doc = build_report(findings, baseline, program_audit=program,
                       root=root)
    problems = validate_analysis_report(doc)
    if problems:  # self-check before print — the report contract
        raise AssertionError(f"invalid analysis_report/v1: {problems}")
    cancel()
    print(json.dumps(doc), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
    for f_ in doc["findings"]:  # human-readable mirror on stderr
        print(f"{f_['file']}:{f_['line']}: [{f_['rule']}] {f_['message']}",
              file=sys.stderr)
    return 0 if doc["checks"]["clean"] else 1


def main() -> int:
    return run_guarded(_run, _emit_error)


if __name__ == "__main__":
    sys.exit(main())

"""The graph-contract drift gate (see docs/ANALYSIS.md).

Re-lowers every artifact in the contract registry
(:mod:`ibamr_tpu.analysis.contracts`) on the host-CPU backend, runs
the graph censuses, and diffs the budget-comparable metrics against
``GRAPH_BUDGETS.json``:

- exit 0 — every artifact matches its budget exactly (clean);
- exit 1 — at least one metric IMPROVED (e.g. a convert chain
  disappeared): re-run with ``--tighten`` to ratchet the budget and
  commit the diff, so the win is pinned;
- exit 2 — at least one metric regressed (a new scatter, an un-fused
  FFT, a host transfer inside the scan, a dropped donation, a dtype
  widening); the report names artifact, metric, measured and budget.

Each artifact lowers in its own child process (the
``tools/hlo_cost_audit.py`` pattern: the XLA CPU pipeline has a rare
native-crash flake, and a fresh process also guarantees the
production x64-off config regardless of the caller's environment —
the in-process path additionally wraps measurement in
``jax.experimental.disable_x64()``).

Flags: ``--artifacts a,b`` subset, ``--heavy`` includes the
flagship-scale artifacts (minutes of compile), ``--tighten`` ratchets
``GRAPH_BUDGETS.json`` toward the measured values (merge-don't-clobber
twice over: unmeasured artifacts keep their committed budgets, and per
metric the ratchet is DIRECTIONAL — ceilings only tighten down, floors
like ``hidden_fraction``/``donated_args`` only tighten up; loosening a
budget after an intentional structural change requires
``--tighten --clobber``), ``--json`` emits
the machine-readable report, ``--in-process`` skips the child processes (used
by the test suite, which already isolates per-module).
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def tighten_merge(old: dict, measured: dict) -> dict:
    """Directional ratchet of ONE artifact's budget (PR 16).

    ``--tighten`` may only make a budget TIGHTER: ceiling metrics
    (BUDGET_MAX_METRICS — counts that regress UP) take
    ``min(old, measured)``; floor metrics (BUDGET_MIN_METRICS —
    ``donated_args``, ``hidden_fraction``, which regress DOWN) take
    ``max(old, measured)``. A metric the census newly emits is adopted
    at its measured value; a metric only the committed file knows is
    KEPT (the census/budget disagreement then surfaces as MISSING in
    the audit instead of being silently erased). A genuine regression
    therefore never launders through --tighten — loosening a budget on
    purpose requires ``--clobber``. Pinned by
    tests/test_graph_census.py::test_tighten_merges_directionally."""
    from ibamr_tpu.analysis.contracts import BUDGET_MIN_METRICS

    out = dict(old)
    for k, v in measured.items():
        if k not in old:
            out[k] = v
        elif k in BUDGET_MIN_METRICS:
            out[k] = max(int(old[k]), int(v))
        else:
            out[k] = min(int(old[k]), int(v))
    return out


def _measure_child(q, name):
    try:
        from ibamr_tpu.utils.backend_guard import force_cpu

        # 8 virtual devices so the sharded artifacts (sharded_chunk,
        # fftpar_transpose, lagrangian_exchange) see a real (4,2) mesh;
        # the single-device artifacts are unaffected by the count.
        force_cpu(8)
        from ibamr_tpu.analysis.contracts import measure_artifact

        t0 = time.perf_counter()
        metrics = measure_artifact(name)
        q.put({"name": name, "metrics": metrics,
               "compile_s": round(time.perf_counter() - t0, 1)})
    except Exception as e:  # noqa: BLE001 - report to parent
        q.put({"name": name, "error": f"{type(e).__name__}: {e}"})


def measure(name, timeout_s, in_process=False):
    if in_process:
        from ibamr_tpu.analysis.contracts import measure_artifact

        try:
            t0 = time.perf_counter()
            metrics = measure_artifact(name)
            return {"name": name, "metrics": metrics,
                    "compile_s": round(time.perf_counter() - t0, 1)}
        except Exception as e:  # noqa: BLE001
            return {"name": name, "error": f"{type(e).__name__}: {e}"}
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_measure_child, args=(q, name))
    p.start()
    p.join(timeout_s)
    if p.is_alive():
        p.terminate()
        p.join(10)
        return {"name": name, "error": f"timeout > {timeout_s:.0f}s"}
    try:
        return q.get_nowait()
    except Exception:
        return {"name": name, "error": f"child died rc={p.exitcode}"}


def main(argv=None) -> int:
    from ibamr_tpu.analysis.contracts import (
        ARTIFACTS, BUDGET_PATH, diff_budget, load_budgets, report_drift)

    ap = argparse.ArgumentParser(
        description="audit compiled-graph contracts against "
                    "GRAPH_BUDGETS.json")
    ap.add_argument("--artifacts", type=str, default="",
                    help="comma-separated subset (default: all "
                         "non-heavy)")
    ap.add_argument("--heavy", action="store_true",
                    help="include flagship-scale artifacts")
    ap.add_argument("--tighten", action="store_true",
                    help="ratchet budgets toward the measured values "
                         "(directional: ceilings only move DOWN, "
                         "floors only move UP — a regression never "
                         "launders through)")
    ap.add_argument("--clobber", action="store_true",
                    help="with --tighten: overwrite measured "
                         "artifacts' budgets wholesale (required to "
                         "LOOSEN a budget after an intentional "
                         "structural change)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report on stdout")
    ap.add_argument("--in-process", action="store_true",
                    help="skip child processes (test harness use)")
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--budgets", type=str, default=BUDGET_PATH)
    args = ap.parse_args(argv)

    if args.artifacts:
        names = [s.strip() for s in args.artifacts.split(",")]
        unknown = set(names) - set(ARTIFACTS)
        if unknown:
            raise SystemExit(f"unknown artifacts {sorted(unknown)}")
    else:
        names = [n for n, a in ARTIFACTS.items()
                 if args.heavy or not a.heavy]

    try:
        budgets = load_budgets(args.budgets)
    except FileNotFoundError:
        budgets = {}

    results, drifts, errors = {}, [], []
    for i, name in enumerate(names):
        if not args.as_json:
            print(f"[graph-audit] {i + 1}/{len(names)}: {name}",
                  flush=True)
        r = measure(name, args.timeout, in_process=args.in_process)
        if "error" in r:
            errors.append(r)
            if not args.as_json:
                print(f"[graph-audit]   ERROR {r['error']}",
                      flush=True)
            continue
        results[name] = r
        if name in budgets:
            drifts.append(diff_budget(name, r["metrics"],
                                      budgets[name]))
        elif not args.tighten and not args.as_json:
            print(f"[graph-audit]   (no budget yet — run --tighten "
                  f"to pin)", flush=True)

    if args.tighten:
        doc = {"_doc": (
            "Graph-contract budgets (tools/graph_audit.py; see "
            "docs/ANALYSIS.md). Measured on the host-CPU backend "
            "under the production x64-off config; 'donated_args' and "
            "'hidden_fraction' are floors (regress DOWN), every other "
            "metric a ceiling (regresses UP)."),
            "artifacts": dict(budgets)}
        for name, r in results.items():
            if args.clobber or name not in budgets:
                doc["artifacts"][name] = r["metrics"]
            else:
                doc["artifacts"][name] = tighten_merge(budgets[name],
                                                       r["metrics"])
        with open(args.budgets, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        if not args.as_json:
            print(f"[graph-audit] wrote {args.budgets} "
                  f"({len(results)} artifact(s) "
                  f"{'clobbered' if args.clobber else 'tightened'})")

    regressed = [d for d in drifts if d.regressions or d.missing]
    improved = [d for d in drifts if d.improvements
                and not (d.regressions or d.missing)]
    missing_budgets = [n for n in results if n not in budgets]
    rc = 0
    if errors or regressed:
        rc = 2
    elif improved or (missing_budgets and not args.tighten):
        rc = 1

    if args.as_json:
        print(json.dumps({
            "exit": rc,
            "artifacts": {n: r["metrics"] for n, r in results.items()},
            "compile_s": {n: r["compile_s"]
                          for n, r in results.items()},
            "regressed": [d.name for d in regressed],
            "improved": [d.name for d in improved],
            "unbudgeted": missing_budgets,
            "errors": errors,
        }, indent=1, sort_keys=True))
        return rc

    report = report_drift(drifts)
    if report:
        print(report)
    for e in errors:
        print(f"[graph-audit] {e['name']}: ERROR {e['error']}")
    if missing_budgets and not args.tighten:
        print(f"[graph-audit] unbudgeted artifact(s): "
              f"{missing_budgets} — run --tighten to pin")
    verdict = {0: "clean — every artifact matches its budget",
               1: "improved — run --tighten to ratchet the budgets",
               2: "REGRESSED — see the drift report above"}[rc]
    print(f"[graph-audit] {len(results)} measured, "
          f"{len(regressed)} regressed, {len(improved)} improved, "
          f"{len(errors)} error(s): {verdict}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())

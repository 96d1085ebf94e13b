"""Warm-pool scenario server CLI (docs/SERVING.md).

``serve.py bench`` — time request-to-first-step latency cold vs warm
through the router (``cold_warm_drill``) on the current backend (or
``--cpu``), emitting ONE JSON line on stdout.

``serve.py check`` — the cold-vs-warm compile-count contract gate
(the ``graph_audit`` exit-code convention):

- exit 0 — the drill matches SERVE_CONTRACT.json exactly (clean);
- exit 1 — improved (fewer cold compiles) or unbudgeted: re-run with
  ``--tighten`` to pin;
- exit 2 — regressed: a compile on the warm path, a new trace
  signature, a lost cache hit, or a failed request. A cache
  regression fails CI structurally, not anecdotally.

Contract metric directions: ``cold_compiles``, ``warm_compiles`` and
``warm_new_trace_signatures`` are ceilings (regress UP);
``warm_hits`` is a floor (regresses DOWN). The check runs on the
forced host-CPU backend so the verdict is hermetic.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CONTRACT_PATH = os.path.join(REPO, "SERVE_CONTRACT.json")

CEILINGS = ("cold_compiles", "warm_compiles",
            "warm_new_trace_signatures")
FLOORS = ("warm_hits",)
CONTRACT_METRICS = CEILINGS + FLOORS


def run_drill(args, force_cpu_backend: bool) -> dict:
    if force_cpu_backend:
        from ibamr_tpu.utils.backend_guard import force_cpu
        force_cpu()
        platform = "cpu"
    else:
        from ibamr_tpu.utils.backend_guard import auto_backend
        platform = auto_backend().devices()[0].platform
    from ibamr_tpu.serve import aot_cache
    aot_cache.enable_persistent_cache()
    from ibamr_tpu.serve.router import cold_warm_drill

    out = cold_warm_drill(
        n_cells=args.n, n_lat=args.n_lat, n_lon=args.n_lon,
        lanes=args.lanes, steps=args.steps, dt=args.dt,
        engine=args.engine or None)
    out["platform"] = platform
    return out


def load_contract(path: str = CONTRACT_PATH):
    with open(path) as f:
        return json.load(f)["contract"]


def diff_contract(measured: dict, contract: dict):
    """(regressions, improvements) — each a list of human-readable
    drift lines."""
    regressions, improvements = [], []
    for name in CONTRACT_METRICS:
        if name not in contract:
            continue
        got, want = measured.get(name), contract[name]
        if got is None:
            regressions.append(f"{name}: missing from measurement")
            continue
        if name in FLOORS:
            worse, better = got < want, got > want
        else:
            worse, better = got > want, got < want
        if worse:
            regressions.append(f"{name}: measured {got} vs budget "
                               f"{want} (REGRESSED)")
        elif better:
            improvements.append(f"{name}: measured {got} vs budget "
                                f"{want} (improved)")
    for flag in ("cold_ok", "warm_ok"):
        if not measured.get(flag, False):
            regressions.append(f"{flag}: request failed")
    return regressions, improvements


def cmd_bench(args) -> int:
    out = run_drill(args, force_cpu_backend=args.cpu)
    print(json.dumps(out, sort_keys=True))
    return 0


def cmd_check(args) -> int:
    measured = run_drill(args, force_cpu_backend=True)
    if args.tighten:
        doc = {"_doc": (
            "Cold-vs-warm serving compile-count contract "
            "(tools/serve.py check; see docs/SERVING.md). Measured on "
            "the forced host-CPU backend. 'warm_hits' is a floor "
            "(regresses DOWN), every other metric a ceiling (regresses "
            "UP); warm_compiles == 0 is the kill-the-cold-start "
            "guarantee."),
            "drill": {k: measured[k] for k in
                      ("n", "lanes", "steps", "engine")},
            "contract": {k: measured[k] for k in CONTRACT_METRICS}}
        with open(args.contract, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"[serve] wrote {args.contract}")
        return 0
    try:
        contract = load_contract(args.contract)
    except FileNotFoundError:
        contract = None
    regressions, improvements = ([], []) if contract is None \
        else diff_contract(measured, contract)
    if contract is None:
        # an unbudgeted drill still gates request health
        regressions = [f"{flag}: request failed"
                       for flag in ("cold_ok", "warm_ok")
                       if not measured.get(flag, False)]
    rc = 2 if regressions else (1 if improvements or contract is None
                                else 0)
    if args.as_json:
        print(json.dumps({
            "exit": rc, "measured": measured,
            "regressed": regressions, "improved": improvements,
            "unbudgeted": contract is None}, indent=1, sort_keys=True))
        return rc
    for line in regressions:
        print(f"[serve] {line}")
    for line in improvements:
        print(f"[serve] {line}")
    if contract is None:
        print(f"[serve] no contract at {args.contract} — run "
              f"--tighten to pin")
    verdict = {0: "clean — drill matches the serve contract",
               1: "improved/unbudgeted — run --tighten to pin",
               2: "REGRESSED — the warm path is no longer free"}[rc]
    print(f"[serve] cold {measured['cold_first_step_s']}s / warm "
          f"{measured['warm_first_step_s']}s "
          f"(ratio {measured['warm_over_cold']}), "
          f"{measured['cold_compiles']} cold / "
          f"{measured['warm_compiles']} warm compile(s): {verdict}")
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="warm-pool scenario server: cold/warm latency "
                    "bench + compile-count contract gate")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def drill_args(p):
        p.add_argument("--n", type=int, default=16)
        p.add_argument("--n-lat", type=int, default=8)
        p.add_argument("--n-lon", type=int, default=16)
        p.add_argument("--lanes", type=int, default=2)
        p.add_argument("--steps", type=int, default=3)
        p.add_argument("--dt", type=float, default=5e-5)
        p.add_argument("--engine", type=str, default="",
                       help="engine name ('' = auto via the resolver)")

    b = sub.add_parser("bench", help="cold/warm request-to-first-step "
                                     "latency, one JSON line")
    drill_args(b)
    b.add_argument("--cpu", action="store_true",
                   help="force the host-CPU backend")
    b.set_defaults(fn=cmd_bench)

    c = sub.add_parser("check", help="gate the cold-vs-warm "
                                     "compile-count contract")
    drill_args(c)
    c.add_argument("--tighten", action="store_true",
                   help="rewrite the contract to the measured values")
    c.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable report on stdout")
    c.add_argument("--contract", type=str, default=CONTRACT_PATH)
    c.set_defaults(fn=cmd_check)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())

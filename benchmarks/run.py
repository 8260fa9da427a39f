"""Benchmark harness entry point (assignment deliverable d).

One module per paper artifact; each exposes ``run(quick) -> [(name,
us_per_call, derived), …]`` and this driver prints the combined CSV.

  PYTHONPATH=src python -m benchmarks.run            # full
  PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized
  PYTHONPATH=src python -m benchmarks.run --only fig3,kernels
  PYTHONPATH=src python -m benchmarks.run --quick --all --json BENCH_run.json

``--json`` additionally writes one consolidated machine-readable report:
per-suite wall seconds, the row tuples, and the traceback tail of any
suite that failed (``--all`` is an explicit alias for the every-suite
default, so CI invocations read as intent rather than omission).

Every known suite appears in the output exactly once: suites excluded by
``--only`` and suites that raise :class:`SuiteSkipped` (e.g. ``flagship``
where multi-process spawn is unavailable) are listed with their skip
reason rather than silently omitted — a missing line in a benchmark
report should always say why.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

SUITES = {
    "fig3": "benchmarks.fig3_speed_quality",  # paper Figure 3
    "table1": "benchmarks.table1_pubmed",  # paper Table 1
    "fig4": "benchmarks.fig4_multiscale",  # paper Figures 1 & 4
    "roofline": "benchmarks.roofline_table",  # assignment §Roofline
    "kernels": "benchmarks.kernel_micro",  # Pallas kernels
    "index_build": "benchmarks.index_build",  # §3.2 device build vs seed host
    "serve": "benchmarks.serve_latency",  # out-of-sample transform latency
    "service_load": "benchmarks.service_load",  # HTTP-service concurrency gate
    "flagship": "benchmarks.flagship",  # multi-process end-to-end map
    "partial_fit": "benchmarks.partial_fit",  # incremental growth + stability
    "pipeline": "benchmarks.pipeline",  # embed→store→fit→inverse→explore
}


class SuiteSkipped(RuntimeError):
    """Raised by a suite's ``run()`` when its prerequisites are absent.

    Distinct from failure: the harness records the reason, prints it, and
    exits 0 — but never drops the suite from the report.
    """


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="", help="comma-separated suite names")
    ap.add_argument(
        "--all", action="store_true",
        help="run every suite (the default; mutually exclusive with --only)",
    )
    ap.add_argument(
        "--json", default="",
        help="write a consolidated per-suite report (BENCH_run.json)",
    )
    args = ap.parse_args()
    if args.all and args.only:
        ap.error("--all and --only are mutually exclusive")
    only = set(args.only.split(",")) if args.only else set(SUITES)
    unknown = sorted(only - set(SUITES))
    if unknown:
        ap.error(f"unknown suite(s) {unknown} — have {sorted(SUITES)}")

    import importlib

    print("name,us_per_call,derived")
    failed = []
    report: dict = {"benchmark": "run", "quick": bool(args.quick), "suites": {}}
    for key, mod_name in SUITES.items():
        entry: dict = {"module": mod_name}
        if key not in only:
            entry["skipped"] = f"not selected (--only {args.only})"
            print(f"# skip {key}: {entry['skipped']}", flush=True)
            report["suites"][key] = entry
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
            rows = []
            for name, us, derived in mod.run(quick=args.quick):
                print(f"{name},{us:.1f},{derived}", flush=True)
                rows.append({"name": name, "us_per_call": float(us), "derived": derived})
            entry["rows"] = rows
        except SuiteSkipped as e:
            entry["skipped"] = str(e)
            print(f"# skip {key}: {e}", flush=True)
        except Exception:  # noqa: BLE001 — report and continue the suite
            failed.append(key)
            entry["error"] = traceback.format_exc(limit=8)
            traceback.print_exc(file=sys.stderr)
        entry["wall_s"] = round(time.time() - t0, 3)
        report["suites"][key] = entry
    report["failed"] = failed
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# report → {args.json}", file=sys.stderr)
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())

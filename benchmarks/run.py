# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV; ``--json PATH`` additionally writes the rows as BENCH JSONs so the
# perf trajectory is recorded run over run.  Benches tagged with a
# ``bench_group`` attribute (e.g. ``"serving"`` for bench_cascade) land in a
# sibling file BENCH_<group>.json next to PATH; untagged benches ("kernels")
# go to PATH itself.
import argparse
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write results as JSON (e.g. BENCH_kernels.json;"
                         " grouped benches go to sibling BENCH_<group>.json)")
    ap.add_argument("--only", metavar="SUBSTRS", default=None,
                    help="run only benches whose name contains one of the "
                         "comma-separated substrings")
    args = ap.parse_args()

    from benchmarks.paper_figures import ALL_BENCHES

    only = [s for s in (args.only or "").split(",") if s]
    grouped: dict[str, list] = {}
    failed: list[str] = []
    print("name,us_per_call,derived")
    for bench in ALL_BENCHES:
        if only and not any(s in bench.__name__ for s in only):
            continue
        group = getattr(bench, "bench_group", "kernels")
        results = grouped.setdefault(group, [])
        t0 = time.time()
        try:
            rows = bench()
        except Exception as e:  # noqa: BLE001
            print(f"{bench.__name__},0,ERROR:{type(e).__name__}:{e}")
            results.append({"bench": bench.__name__, "name": bench.__name__,
                            "us_per_call": 0.0,
                            "derived": f"ERROR:{type(e).__name__}:{e}",
                            "error": f"{type(e).__name__}: {e}"})
            failed.append(bench.__name__)
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
            results.append({"bench": bench.__name__, "name": name,
                            "us_per_call": us, "derived": derived})
        print(f"# {bench.__name__} took {time.time() - t0:.1f}s",
              file=sys.stderr)

    if args.json:
        for group, results in grouped.items():
            path = (args.json if group == "kernels" else os.path.join(
                os.path.dirname(args.json) or ".", f"BENCH_{group}.json"))
            with open(path, "w") as f:
                json.dump({"schema": "bench-rows/v1", "rows": results}, f,
                          indent=1)
            print(f"# wrote {len(results)} rows to {path}", file=sys.stderr)
    if failed:
        print(f"# {len(failed)} bench(es) raised: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

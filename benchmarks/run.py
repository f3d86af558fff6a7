"""Benchmark driver: one module per paper table/figure. Prints CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only fig2,...]

Every benchmark runs even when an earlier one fails; the exit status is 1
if any of them failed.
"""

from __future__ import annotations

import argparse
import csv
import io
import sys
import time

BENCHES = ("fig2", "fig7", "table1", "fig9_11", "lm_roofline", "md_step")


def _load(name):
    if name == "fig2":
        from benchmarks import fig2_tabulation_accuracy as m
        return m.run
    if name == "md_step":
        # three-engine MD stepping bench; also extends the BENCH_md.json
        # perf trajectory (headline numbers keyed by git sha, accumulated
        # across PRs — the CI artifact carries the history)
        from benchmarks import md_step_time as m
        return m.run
    if name == "fig7":
        from benchmarks import fig7_step_ladder as m
        return m.run
    if name == "table1":
        from benchmarks import table1_tts as m
        return m.run
    if name == "fig9_11":
        from benchmarks import fig9_11_scaling as m
        return m.run
    if name == "lm_roofline":
        from benchmarks import lm_roofline_table as m
        return m.run
    raise KeyError(name)


def _print_rows(rows):
    if not rows:
        return
    for row in rows:
        buf = io.StringIO()
        w = csv.writer(buf)
        w.writerow([f"{k}={v}" for k, v in row.items()])
        sys.stdout.write(buf.getvalue())
    sys.stdout.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names " + str(BENCHES))
    args = ap.parse_args(argv)
    names = args.only.split(",") if args.only else list(BENCHES)
    failed = []
    for name in names:
        t0 = time.time()
        print(f"# ---- {name} ----", flush=True)
        try:
            rows = _load(name)()
            _print_rows(rows)
            print(f"# {name}: {len(rows)} rows in {time.time()-t0:.1f}s",
                  flush=True)
        except Exception as e:  # a bench failure should not hide the others
            import traceback
            traceback.print_exc()
            print(f"# {name}: FAILED {type(e).__name__}: {e}", flush=True)
            failed.append(name)
    if failed:
        print(f"# FAILED: {','.join(failed)}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate it sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

One process serves the cell's traffic at each rate in turn (a fresh engine
each time, the programs from the compile cache) and prints, per rate, the
requests due, the time-to-first-token median and 90th percentile, and the
backlog: requests due but not yet admitted at the middle and at the end of
the window.  A rate whose backlog grows from the middle to the end is above
the knee.  The cell's fixed rate is set once from such a sweep (about four
fifths of the knee) in ``bench/cells/<cell>.json``; the benchmark's own runs
never search for a rate.  No correctness check is made here.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from harness import Run, percentile

    for rate in (float(r) for r in args.rates.split(",")):
        run = Run(args.workload, args.seed, args.seconds, 0, rate=rate,
                  log=lambda m: print(m, file=sys.stderr))
        run.setup()
        mid = run_window_with_probe(run)
        due = run.due_in_window()
        ttft = [r.first - r.due if r.first is not None else float("inf") for r in due]
        end_backlog = sum(1 for r in due if r.first is None or r.first > run.t1)
        print(json.dumps({
            "rate": rate, "due": len(due),
            "ttft_p50_s": percentile(ttft, 50), "ttft_p90_s": percentile(ttft, 90),
            "backlog_mid": mid, "backlog_end": end_backlog,
            "tokens": sum(len(r.times) for r in run.recs), "steps": run.k,
        }), flush=True)
        run.free_engine()
    return 0


def run_window_with_probe(run):
    """The window; returns the backlog at its middle (requests due by then
    without a first token by then)."""
    run.window()
    half = run.t0 + run.seconds / 2
    return sum(1 for r in run.recs
               if r.due <= half and (r.first is None or r.first > half))


if __name__ == "__main__":
    t = time.perf_counter()
    rc = main()
    print(f"sweep took {time.perf_counter() - t:.1f} s", file=sys.stderr)
    sys.exit(rc)

#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --control-seeds 1,2,3 \
        --seconds <s>

One process.  For each seed it serves the cell's traffic for ``--seconds``
exactly as ``bench/run.py`` does (same set-up, window, sample), then reads
the widest gap by which a served token's f32 reference logit lies below
the reference's best (the program's reading).  For the control seeds it
also puts the reference in the program's place, computed in float8 e4m3
(the precision below the bf16 that the configuration serves in), and reads
the widest gap of the tokens that the float8 forward puts first at the same
positions (the control's reading).  One JSON line per seed, then a summary:
the largest program reading (the lower end of the limit) and the smallest
control reading (the upper end).  The benchmark's own runs never run this.
"""

import argparse
import gc
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from harness import Run

    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    served, control = [], []
    for seed in seeds:
        run = Run(args.workload, seed, args.seconds, 0,
                  log=lambda m: print(m, file=sys.stderr))
        run.setup()
        run.window()
        run.read_memory()
        run.free_engine()
        gap, gap8 = run.compare(control=seed in ctl)
        served.append(gap)
        if gap8 is not None:
            control.append(gap8)
        print(json.dumps({"seed": seed, "served_gap": gap, "control_gap": gap8,
                          "sample_tokens": run.sample_tokens,
                          "finished": sum(1 for r in run.recs if r.done is not None)}),
              flush=True)
        del run
        gc.collect()
    print(json.dumps({"workload": args.workload, "lower": max(served),
                      "upper": min(control) if control else None,
                      "served": served, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

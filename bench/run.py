#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Earlier lines of standard error report the
run; its last lines there are the correctness checks, each number beside
its limit.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``.
It exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, or a device with no entry in ``bench/peaks.json``.

JAX's persistent compilation cache is ``<checkout>/.jax_cache``, so only
the first run of a cell in a checkout compiles.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the cache directory is part of the cache key: one fixed path in the
    # checkout, set before JAX reads its configuration
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from harness import BenchError, execute

    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        result = execute(
            args.workload, args.seed, args.seconds, args.trace,
            t_process=T_PROCESS, log=log,
        )
    except BenchError as err:
        log(f"bench: {err}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

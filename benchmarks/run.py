# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# Also writes two machine-diffable JSON trajectories:
#   benchmarks/BENCH_numerics.json - per-pool-dtype paged-decode RMSE vs
#     fp64 exact attention (accuracy regressions are a JSON diff);
#   benchmarks/BENCH_serving.json - engine-step latency of the bursty-
#     arrival scheduler sweep (scheduler_burst.py): deterministic
#     mean/worst TTFT and drain steps per policy x prefill-batch
#     configuration, plus the wall-clock sync-vs-async pipelining pair
#     (real tokens/sec and TTFT-seconds; streams asserted bit-identical).
import json
import os
import sys

NUMERICS_JSON = os.path.join(os.path.dirname(__file__), "BENCH_numerics.json")
SERVING_JSON = os.path.join(os.path.dirname(__file__), "BENCH_serving.json")

#: PR-7 acceptance bound: full telemetry (wallclock_traced) may cost at
#: most this fraction of wallclock_async tokens/sec.
TELEMETRY_OVERHEAD_BOUND = 0.05

#: PR-9 acceptance bound: the speculative row of the repetitive burst
#: must finish at or below this many engine steps per generated token
#: (the off row sits at ~1.0 during decode).
SPEC_STEPS_PER_TOKEN_BOUND = 0.6


def _check_spec_decode(serving_rows) -> None:
    """Fail the run when speculative decoding stops paying for itself on
    the repetitive burst, or (worse) when the in-run bit-identity assert
    did not certify the row."""
    on = next(
        (r for r in serving_rows
         if r["name"] == "scheduler_burst/spec_decode_on"), None,
    )
    if on is None:
        raise SystemExit(
            "spec_decode_on row missing from the serving trajectory - "
            "the speculative-decoding acceptance bound was not measured"
        )
    if not on.get("bit_identical"):
        raise SystemExit(
            "spec_decode_on row recorded without a passing bit-identity "
            "assert - speculation may have changed output bits"
        )
    spt = on["steps_per_token"]
    if spt > SPEC_STEPS_PER_TOKEN_BOUND:
        raise SystemExit(
            f"speculative decode steps-per-token {spt:.3f} exceeds the "
            f"{SPEC_STEPS_PER_TOKEN_BOUND} bound on the repetitive burst "
            f"(k={on['speculate']}, accept rate {on.get('accept_rate', 0):.2f})"
        )
    print(
        f"[spec decode {spt:.3f} steps/token, bit-identical - within the "
        f"{SPEC_STEPS_PER_TOKEN_BOUND} bound]", file=sys.stderr,
    )


def _check_telemetry_overhead(serving_rows) -> None:
    """Fail the whole run when observability costs more than the bound; a
    silent perf regression in a ride-along layer must not survive a green
    run."""
    traced = next(
        (r for r in serving_rows
         if r["name"] == "scheduler_burst/wallclock_traced"), None,
    )
    if traced is None:
        raise SystemExit(
            "wallclock_traced row missing from the serving trajectory - "
            "the telemetry-overhead acceptance bound was not measured"
        )
    overhead = traced["overhead_vs_async"]
    if overhead > TELEMETRY_OVERHEAD_BOUND:
        raise SystemExit(
            f"telemetry overhead {overhead:.1%} exceeds the "
            f"{TELEMETRY_OVERHEAD_BOUND:.0%} bound vs wallclock_async "
            f"({traced['tokens_per_s_wall']:.0f} tok/s traced)"
        )
    print(
        f"[telemetry overhead {overhead:+.1%} vs async - within the "
        f"{TELEMETRY_OVERHEAD_BOUND:.0%} bound]", file=sys.stderr,
    )


def _write_json(path: str, rows, label: str) -> None:
    # serialize BEFORE opening: a failure mid-evaluation must not
    # truncate the previous run's trajectory file
    payload = json.dumps(
        {"schema": 1, "rows": rows}, indent=1, sort_keys=True
    )
    with open(path, "w") as f:
        f.write(payload)
    print(f"[{label} trajectory written to {path}]", file=sys.stderr)


def main() -> None:
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)  # fp64 golden references

    from benchmarks import paged_vs_dense as PD
    from benchmarks import paper_tables as T
    from benchmarks import prefill_prefix as PP
    from benchmarks import roofline_report as R
    from benchmarks import scheduler_burst as SB

    rows = []
    rows += T.fig9a_uniform_mean_sweep()
    rows += T.fig9b_uniform_amp_sweep()
    rows += T.fig10_hybrid_sweeps()
    rows += T.table3_invariance()
    rows += T.table4_nan_stats()
    rows += T.real_model_overflow()
    rows += T.kernel_timing()
    rows += PD.report()
    _write_json(NUMERICS_JSON, PD.numerics_rows(), "numerics")
    rows += PP.report()
    rows += SB.report()
    serving_rows = SB.serving_rows()
    _write_json(SERVING_JSON, serving_rows, "serving")
    _check_telemetry_overhead(serving_rows)
    _check_spec_decode(serving_rows)
    # empty when no dry-run artifacts exist (experiments/dryrun)
    rows += R.report()

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()

"""Run one cell of the chip benchmark once and print its result line.

    python3 bench/run.py --workload chain-relaxed --seed 7 --seconds 30 --trace 0

Run from the root of a checkout, on a machine with the chips the cell asks
for.  The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit).  The
checks are also the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits with code 3 and prints no result.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
same window with the serving loop's metrics registry on and the profiler on
over its second chunk, and reports the per-layer metrics.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# The checkout's own compile cache, at a fixed path, unless the environment
# names one; the program reads the same variable.
CACHE = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness

    trace_dir = ROOT / ".bench_trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        out = harness.run_cell(
            ROOT, args.workload, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), t0=T0, trace_dir=trace_dir,
        )
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

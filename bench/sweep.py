"""Offered-rate sweep of a cell, and the measured step time of each batch.

    python3 bench/sweep.py --workload chain-relaxed --seed 11 --seconds 8

One set-up, then a short window at each offered rate (0.8 to 1.2 of the
provisioned one) against the same plan.  Prints, per rate, the attainment,
p99 and served rate, and per (module, batch) the median measured step time
beside the planner's analytic one.  The cells themselves run at 1.0; this
says where each sits against its knee.  The last line is a JSON summary.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import CACHE, ROOT  # noqa: E402  (sets the compile cache and sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--scales", default="0.8,0.9,1.0,1.1,1.2")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness, manifest
    from repro.profiling import spec_for
    from repro.serving import LiveServiceTime, ServingEngine

    cell = manifest.load_cell(ROOT, args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no result: the sweep runs on a TPU", file=sys.stderr)
        return 3
    plan, executors, *_ = harness.build(cell, args.seed, spec_for(dev).name)
    print(plan.summary(), flush=True)
    analytic = {
        (m, a.config.batch): a.config.duration
        for m, s in plan.schedules.items() for a in s.allocs
    }
    live = LiveServiceTime(executors, cache=False)
    engine = ServingEngine(plan, executors=executors)
    n = int(cell.params["chunk_requests"])
    rows = []
    for scale in [float(x) for x in args.scales.split(",")]:
        live.reset()
        results, chunk_s, _, _ = harness.window(
            engine, live, cell.traffic, n, args.seed, args.seconds, scale=scale
        )
        lat = np.concatenate([np.asarray(r.e2e_latencies, float) for r in results])
        offered = sum(r.offered for r in results)
        row = {
            "scale": scale,
            "offered_rps": scale * float(cell.traffic["rate"]),
            "attainment": float((lat <= plan.workload.slo + 1e-9).sum() / offered),
            "p99_ms": 1e3 * float(np.quantile(lat, 0.99)),
            "served_rps": lat.size / sum(chunk_s),
            "requests": int(offered),
            "step_ms": {
                f"{m}.b{b}": [1e3 * float(np.median(v)), 1e3 * analytic[m, b], len(v)]
                for (m, b), v in sorted(live.measured.items())
            },
        }
        rows.append(row)
        print(f"scale {scale}: attainment {row['attainment']!r} p99 {row['p99_ms']!r} ms "
              f"served {row['served_rps']!r} req/s over {offered} requests", flush=True)
        for k, (meas, ana, cnt) in row["step_ms"].items():
            print(f"  {k}: measured median {meas!r} ms over {cnt} vs analytic {ana!r} ms "
                  f"(ratio {meas / ana!r})", flush=True)
    print(json.dumps({"workload": args.workload, "setup_s": time.perf_counter() - T0, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings that set a configuration's logit-gap limits, on the chip.

    python3 bench/control.py --config smollm360m-qwen15-4b-chain \
        --batches 32,16,8 --seeds 101,102,103 --control-seeds 3

For each seed, the benchmark's seeded weights and token blocks go into the
program's executors (the same compiled forward the window runs, at each
batch size), and the widest logit gap of their output against the float32
reference is read: the lower reading.  On the first ``--control-seeds`` seeds
the reference computed with float8 (e4m3) operands takes the program's place:
the control, whose gap is the upper reading.  A limit lies between the two.
The benchmark's own runs do not run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import CACHE, ROOT  # noqa: E402  (sets the compile cache and sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--batches", default="32,16,8,4,1")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import manifest, weights
    from bench.harness import _arch_config
    from repro.launch.serve import ModuleExecutor

    if jax.devices()[0].platform != "tpu":
        print("no result: the control runs on a TPU", file=sys.stderr)
        return 3
    man = manifest.load_manifest(ROOT)
    entry = next(c for c in man["configs"] if c["name"] == args.config)
    config = json.loads((ROOT / entry["file"]).read_text())
    ref = manifest.reference(config["reference"], ROOT / "bench")
    batches = [int(b) for b in args.batches.split(",")]
    seeds = [int(s) for s in args.seeds.split(",")]
    readings = []
    for i, mod in enumerate(config["modules"]):
        arch, eps = mod["arch"], mod["rms_norm_eps"]
        ex = ModuleExecutor(_arch_config(arch), seq=args.seq)
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), ex.params)
        for leaf in jax.tree.leaves(ex.params):
            leaf.delete()
        for k, seed in enumerate(seeds):
            ex.params = weights.make_params(shapes, seed, i)
            for b in batches:
                ex(b)
                toks = weights.make_tokens(seed, i, b, args.seq, arch["vocab_size"])
                ex._tokens[b] = toks
                out = ex(b)
                g, a = ref.widest_gap(ex.params, toks, out, arch, eps)
                row = {"module": mod["name"], "batch": b, "seed": seed, "program_gap": g,
                       "program_agree": a}
                if k < args.control_seeds:
                    gc, ac = ref.widest_gap(ex.params, toks, None, arch, eps, quant=ref.fp8)
                    row.update(control_gap=gc, control_agree=ac)
                del out
                readings.append(row)
                print(json.dumps(row), flush=True)
            for leaf in jax.tree.leaves(ex.params):
                leaf.delete()
        ex.compiled.clear()
        del ex
    summary = {}
    for mod in config["modules"]:
        rs = [r for r in readings if r["module"] == mod["name"]]
        summary[mod["name"]] = {
            "lower": max(r["program_gap"] for r in rs),
            "upper": min((r["control_gap"] for r in rs if "control_gap" in r), default=None),
            "seeds": len({r["seed"] for r in rs}),
        }
    print(json.dumps({"config": args.config, "summary": summary, "seconds": time.perf_counter() - T0}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

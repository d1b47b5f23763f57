"""Bring-up check: Harpagon's main path on one TPU at published widths.

    python chip_smoke.py

Runs in one process and starts none.  Phases, in order; any failure raises
and the script exits non-zero without printing a result:

1. device: a TPU must be JAX's default device (no CPU fallback); prints
   its platform, kind, count and the peaks-table entry it resolves to.
2. kernels: each Pallas kernel on the chip against `repro.kernels.ref` at
   the served shapes, within the bf16 tolerance of tests/test_kernels.py.
3. main path: ``python -m repro.launch.serve --arch smollm-360m,gemma3-1b
   --real --pipeline --seq 128`` (the `serve.main` a user calls), planned
   on the attached chip's profile and served through `ServingEngine` with
   `LiveServiceTime`; every forward must contain a Pallas kernel
   (``tpu_custom_call``), no attention or RMSNorm may fall back to `ref`,
   logits must be finite, and every offered request must be accounted for.
4. report: compile seconds, measured vs analytic step time per
   (module, batch), attainment, p99/SLO and peak device memory.  These are
   a bring-up run's numbers, not a benchmark.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCHS = ("smollm-360m", "gemma3-1b")
SEQ = 128
REQUESTS = 400
# feasible on the v5e-only analytic plan, with two batch sizes for gemma3
RATE, SLO = 500.0, 0.5
SERVE_ARGV = [
    "--arch", ",".join(ARCHS), "--real", "--pipeline", "--seq", str(SEQ),
    "--rate", str(RATE), "--slo", str(SLO), "--requests", str(REQUESTS),
]
BF16_TOL = 2e-2  # tests/test_kernels.py TOL[bfloat16]


def device_phase():
    from repro.kernels import ops
    from repro.profiling import spec_for

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no accelerator (default device platform "
            f"{dev.platform!r}); nothing was run"
        )
    spec = spec_for(dev)
    assert ops._mode() == "tpu", ops._mode()
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)} spec={spec}")
    return dev, spec


def _rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))


def kernel_phase() -> None:
    from repro.kernels import ops, ref
    from repro.kernels.decode_attention import flash_decode
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import fused_rmsnorm

    interpret = ops._mode() == "interpret"  # "tpu" on the chip
    keys = iter(jax.random.split(jax.random.key(0), 64))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    def check(name, out, exp):
        err = _rel_err(out, exp)
        print(f"kernel {name}: max rel err {err:.3e} (tol {BF16_TOL})")
        assert err < BF16_TOL, (name, err)

    # (name, B, S, Hq, Hkv, D, window): smollm, gemma3 global, gemma3 local
    for name, B, S, Hq, Hkv, D, window in (
        ("flash_attention smollm", 8, SEQ, 15, 5, 64, None),
        ("flash_attention gemma3-global", 8, SEQ, 4, 1, 256, None),
        ("flash_attention gemma3-local", 2, 1024, 4, 1, 256, 512),
    ):
        q, k, v = normal(B, S, Hq, D), normal(B, S, Hkv, D), normal(B, S, Hkv, D)
        out = flash_attention(q, k, v, window=window, interpret=interpret)
        check(f"{name} B{B} S{S} Hq{Hq} Hkv{Hkv} D{D} w{window}", out,
              ref.attention(q, k, v, window=window))

    # d_model of smollm and gemma3, and gemma3's qk-norm head width
    for shape, gemma in (((8, SEQ, 960), False), ((8, SEQ, 1152), True),
                         ((8, SEQ, 4, 256), True)):
        x, w = normal(*shape), normal(shape[-1])
        check(f"fused_rmsnorm {shape} gemma={gemma}",
              fused_rmsnorm(x, w, gemma=gemma, interpret=interpret),
              ref.rmsnorm(x, w, gemma=gemma))

    for name, B, S, Hq, Hkv, D, window in (
        ("flash_decode smollm", 8, 1024, 15, 5, 64, None),
        ("flash_decode gemma3", 8, 1024, 4, 1, 256, None),
        ("flash_decode gemma3-local", 8, 1024, 4, 1, 256, 512),
    ):
        q, kc, vc = normal(B, Hq, D), normal(B, S, Hkv, D), normal(B, S, Hkv, D)
        lengths = jnp.asarray(
            [(S * (i + 1)) // (B + 1) + 1 for i in range(B)], jnp.int32
        )
        out = flash_decode(q, kc, vc, lengths, window=window, interpret=interpret)
        check(f"{name} B{B} S{S} Hq{Hq} Hkv{Hkv} D{D} w{window}", out,
              ref.decode_attention(q, kc, vc, lengths, window=window))


def check_kernels_compiled(run) -> None:
    """Every served forward holds a Pallas kernel; no op fell to `ref`."""
    from repro.kernels import ops

    for m, ex in run.executors.items():
        for b, compiled in ex.compiled.items():
            assert "tpu_custom_call" in compiled.as_text(), (m, b)
    paths = dict(sorted(ops.TAKEN.items()))
    print("op paths at the served shapes (op, path) -> traces:", paths)
    for op in ("attention", "rmsnorm"):
        assert (op, "ref") not in paths, (op, paths)
        assert any(o == op and p != "ref" for o, p in paths), (op, paths)


def main_path_phase(spec):
    from repro.launch import serve

    run = serve.main(SERVE_ARGV)
    assert run is not None and run.live is not None
    plan, res = run.plan, run.result
    batches = serve.plan_batches(plan)
    hw = {a.config.hardware for s in plan.schedules.values() for a in s.allocs}
    assert hw == {spec.name}, hw
    check_kernels_compiled(run)
    for m, bs in batches.items():
        for b in bs:
            logits = run.executors[m](b)
            cfg = run.executors[m].cfg
            assert logits.shape == (b, SEQ, cfg.vocab_size), logits.shape
            assert bool(jnp.isfinite(logits).all()), (m, b)
            assert (m, b) in run.live.measured, (m, b, sorted(run.live.measured))
    n_done = len(res.e2e_latencies)
    assert n_done + res.shed + res.dropped == res.offered == REQUESTS, (
        n_done, res.shed, res.dropped, res.offered)
    return run, batches


def report(run, batches) -> None:
    plan, res, live = run.plan, run.result, run.live
    print("bring-up run (not a benchmark):")
    for m, bs in batches.items():
        analytic = {a.config.batch: a.config.duration
                    for a in plan.schedules[m].allocs}
        for b in bs:
            obs = live.measured[(m, b)][live.warmup:] or live.measured[(m, b)]
            step = sum(obs) / len(obs)
            print(f"  {m} b{b}: compile {run.executors[m].compile_s[b]:.3f} s, "
                  f"step {step * 1e3:.3f} ms measured ({len(obs)} samples) vs "
                  f"{analytic[b] * 1e3:.3f} ms analytic, ratio "
                  f"{step / analytic[b]:.3f}")
    slo = plan.workload.slo
    print(f"  attainment {res.attainment:.4f}, p99/SLO {res.p99 / slo:.4f} "
          f"(completed {len(res.e2e_latencies)}, shed {res.shed}, "
          f"dropped {res.dropped}, offered {res.offered})")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")


def main() -> None:
    dev, spec = device_phase()
    kernel_phase()
    run, batches = main_path_phase(spec)
    report(run, batches)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

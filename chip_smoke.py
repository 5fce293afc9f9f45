"""Chip smoke test: the main path on a TPU, through its normal entry points.

  PYTHONPATH=src python chip_smoke.py             # one chip
  PYTHONPATH=src python chip_smoke.py --chips 4   # elastic resize, 4 chips

With no option it runs, in one process, SmolLM-135M at its published
widths with random weights from ``--seed``:

- train: ``ElasticTrainer`` built as ``repro.launch.train`` builds it, at
  seq 2048 and global batch 8 for a few steps; then an asynchronous
  checkpoint of the whole state and a bit-equal restore onto the same mesh;
- serve: ``Server`` built as ``repro.launch.serve`` builds it, at batch 1,
  answering requests one after another; then logits of prefill + decode
  through the KV cache against ``model.forward``, at full width in float32;
- kernels: the three Pallas kernels with ``impl="auto"`` at real widths,
  each compiled as a ``tpu_custom_call`` and matched against its ``ref.py``.

With ``--chips 4`` it runs only the elastic path: the job starts on four
slices, a scripted rival job makes the RMS shrink it and then expand it
back, and its losses are compared step by step with the same training on
one device without resizes.

Every phase prints what it checked.  A failed phase is reported with its
traceback and the script exits non-zero; only when all pass does the last
line give the device as one JSON object.  Without a TPU it exits non-zero
at once.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
CKPT_DIR = os.path.join(ROOT, ".smoke_ckpt")
ARCH = "smollm-135m"
SEQ, GLOBAL_BATCH = 2048, 8

# The first loss of a randomly initialised LM is ln(vocab) plus half the
# variance of its logits; at this init the logits have a std of about 0.1,
# which adds about 0.005.  0.1 leaves room for bf16 rounding and still
# catches a loss that is not a mean token cross-entropy.
FIRST_LOSS_TOL = 0.1
# Decode through the cache must give the full forward pass's logits.  The
# randomly initialised 30-layer model amplifies a rounding difference
# about a thousandfold (fp32 on the CPU: 1e-7 in, 5e-5 out at 30 layers),
# so in bf16 the two paths differ by tens of percent of the logit scale
# from rounding alone and a tolerance could not tell a bug from it.  The
# check therefore runs the same weights in float32 with full-precision
# matmuls, where rounding leaves 1e-4 at most and a wrong cache slot,
# position or mask moves logits by the order of their scale; the bf16
# served model's numbers are printed for the record.
DECODE_TOL = 1e-3
# bf16 outputs round at 2^-9 of each value and the kernels sum in another
# order than their references: the bound the interpret-mode tests hold
# bf16 kernels to.
KERNEL_TOL = 2e-2
# Four slices and one device differ only in how the fp32 gradient sum is
# reassociated across chips; the bf16 forward turns that into loss
# differences of a few bf16 ulps of the logits, averaged over 16k tokens.
# 0.02 is about 0.2% of the loss.
ELASTIC_LOSS_TOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


class Check(Exception):
    """A phase's result is wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Check(what)


def device_or_exit(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform "
              f"{devs[0].platform!r} ({len(devs)} device(s)); this test "
              f"runs only on a TPU", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} TPU chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        sys.exit(2)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: the repro package is not under {SRC}",
              file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, SRC)
    return devs


def peak_bytes(dev) -> int:
    return dev.memory_stats()["peak_bytes_in_use"]


# -- one chip -----------------------------------------------------------------


def phase_train(seed: int, steps: int = 5):
    import jax
    import numpy as np

    from repro.launch.train import build

    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    trainer, _ = build(ARCH, seq_len=SEQ, global_batch=GLOBAL_BATCH,
                       steps=steps, ckpt_dir=CKPT_DIR)
    vocab = trainer.model.cfg.vocab_size
    starts = []
    t0 = time.perf_counter()
    state = trainer.train(seed=seed,
                          on_step=lambda s: starts.append(time.perf_counter()))
    jax.block_until_ready(state)
    log(f"  first step, compile included: {starts[1] - starts[0]:.1f} s; "
        f"{steps} steps in {time.perf_counter() - t0:.1f} s (host clock)")
    losses = [m["loss"] for m in trainer.metrics]
    log(f"  losses: {losses}")
    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(all(math.isfinite(x) for x in losses), "a loss is not finite")
    check(abs(losses[0] - math.log(vocab)) < FIRST_LOSS_TOL,
          f"first loss {losses[0]} not within {FIRST_LOSS_TOL} of "
          f"ln({vocab}) = {math.log(vocab):.4f}")
    log(f"  peak_bytes_in_use after training: "
        f"{peak_bytes(jax.devices()[0])}")

    t0 = time.perf_counter()
    trainer.store.save_async(steps, state)
    trainer.store.wait()
    restored = trainer.restore(steps)
    jax.block_until_ready(restored)
    log(f"  checkpoint save_async + restore: "
        f"{time.perf_counter() - t0:.1f} s")
    pairs = zip(jax.tree.leaves(state), jax.tree.leaves(restored))
    check(jax.tree.structure(state) == jax.tree.structure(restored),
          "restored tree differs")
    for a, b in pairs:
        check(a.sharding == b.sharding and a.dtype == b.dtype,
              f"restored leaf placed as {b.sharding}/{b.dtype}, saved as "
              f"{a.sharding}/{a.dtype}")
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              "restored leaf is not bit-equal")
    log("  checkpoint restore is bit-equal")
    shutil.rmtree(CKPT_DIR, ignore_errors=True)


def phase_serve(seed: int):
    import numpy as np

    from repro.launch.serve import build
    from repro.runtime import Request

    server, cfg = build(ARCH, batch=1, max_len=256, seed=seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 17, 40)]
    prompts.append(prompts[0])   # a repeat must reproduce its first answer
    reqs = [Request(rid=i, prompt=p, max_new_tokens=16)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    done = server.run(reqs)
    log(f"  {len(reqs)} requests in {time.perf_counter() - t0:.1f} s "
        f"(host clock, compilation included)")
    for r in reqs:
        out = done.get(r.rid)
        log(f"  request {r.rid}: prompt {len(r.prompt)} tokens -> {out}")
        check(out is not None and len(out) == r.max_new_tokens,
              f"request {r.rid} returned {out}")
        check(all(0 <= t < cfg.vocab_size for t in out),
              f"request {r.rid} emitted a token outside the vocabulary")
    check(done[0] == done[len(reqs) - 1],
          "the same prompt served twice gave different greedy tokens")
    return server


def decode_vs_forward(model, params, seed: int):
    """Logits of prefill + decode through the cache, and of the full
    forward pass, at the prefill's last position and each decoded one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    s0, steps = 56, 8
    total = s0 + steps
    toks = jax.random.randint(jax.random.PRNGKey(seed), (1, total), 0,
                              model.cfg.vocab_size)
    full, _ = jax.jit(model.forward)(params, toks)
    pre, cache = jax.jit(model.prefill, static_argnums=2)(
        params, toks[:, :s0], total)
    decode = jax.jit(model.decode_step)
    got = [pre[:, 0]]
    for t in range(s0, total):
        logits, cache = decode(params, cache, toks[:, t:t + 1], jnp.int32(t))
        got.append(logits[:, 0])
    got = np.asarray(jnp.stack(got, axis=1), np.float32)
    want = np.asarray(full[:, s0 - 1:], np.float32)
    scale = float(np.abs(want).max())
    err = np.abs(got - want)
    log(f"  {model.cfg.dtype}: prefill {s0} + decode {steps} vs forward: "
        f"max |diff| / max |logit| = {err.max() / scale:.3e}, mean = "
        f"{err.mean() / scale:.3e} (scale {scale:.3f}); argmax agrees at "
        f"{int((got.argmax(-1) == want.argmax(-1)).sum())}/{steps + 1}")
    return float(err.max() / scale)


def phase_decode(server, seed: int):
    import dataclasses

    import jax

    from repro.models import build_model

    decode_vs_forward(server.model, server.params, seed)
    f32 = build_model(dataclasses.replace(server.model.cfg, dtype="float32"))
    with jax.default_matmul_precision("highest"):
        err = decode_vs_forward(f32, server.params, seed)
    check(err <= DECODE_TOL,
          f"float32 decode diverges from forward: {err} > {DECODE_TOL}")


def _kernel_case(name, op, ref, args, static):
    import jax
    import numpy as np

    t0 = time.perf_counter()
    compiled = op.lower(*args, impl="auto", **static).compile()
    log(f"  {name}: compiled in {time.perf_counter() - t0:.1f} s")
    check("tpu_custom_call" in compiled.as_text(),
          f"{name} compiled without a tpu_custom_call")
    out = np.asarray(op(*args, impl="auto", **static), np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref(*args, **static), np.float32)
    err = float(np.abs(out - want).max() / (np.abs(want).max() + 1e-6))
    log(f"  {name}: max |kernel - ref| / max |ref| = {err:.3e}")
    check(out.shape == want.shape and np.isfinite(out).all(),
          f"{name} output is {out.shape}, not finite or not {want.shape}")
    check(err <= KERNEL_TOL, f"{name} differs from its reference")


def phase_kernels(seed: int):
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rglru.ops import rglru_op
    from repro.kernels.rglru.ref import rglru_ref
    from repro.kernels.ssd.ops import ssd_op
    from repro.kernels.ssd.ref import ssd_ref

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    # SmolLM-135M: 9 query heads over 3 KV heads of width 64
    _kernel_case("flash_attention d=64",
                 flash_attention_op, attention_ref,
                 (normal((2, 9, SEQ, 64)), normal((2, 3, SEQ, 64)),
                  normal((2, 3, SEQ, 64))), {"causal": True})
    # width 128 with a sliding window and a logit softcap
    _kernel_case("flash_attention d=128 window softcap",
                 flash_attention_op, attention_ref,
                 (normal((1, 8, SEQ, 128)), normal((1, 4, SEQ, 128)),
                  normal((1, 4, SEQ, 128))),
                 {"causal": True, "window": 1024, "softcap": 50.0})
    # Mamba2-130M: 24 heads of width 64, state 128, chunk 128
    dt = jax.nn.softplus(normal((2, SEQ, 24), jnp.float32) - 2.0)
    _kernel_case("ssd_scan", ssd_op,
                 lambda *a, **k: ssd_ref(*a),
                 (normal((2, SEQ, 24, 64)), dt,
                  normal((24,), jnp.float32) * 0.5,
                  normal((2, SEQ, 128)), normal((2, SEQ, 128))),
                 {"chunk": 128})
    # RG-LRU at width 4096 in bf16
    a = jax.nn.sigmoid(normal((2, SEQ, 4096), jnp.float32)) * 0.99
    _kernel_case("rglru_scan", rglru_op, lambda *a, **k: rglru_ref(*a),
                 (a.astype(jnp.bfloat16), normal((2, SEQ, 4096))), {})


# -- four chips ---------------------------------------------------------------


def phase_elastic(seed: int, chips: int):
    import jax

    from repro.launch.train import build
    from repro.runtime import scripted_rival

    steps, period = 6, 2
    trainer, rms = build(ARCH, seq_len=SEQ, global_batch=GLOBAL_BATCH,
                         steps=steps, slices=chips, elastic=True,
                         check_period=period)
    state = trainer.train(seed=seed, on_step=scripted_rival(
        rms, submit_at=period, finish_at=2 * period, log=log))
    for r in trainer.resize_log:
        log(f"  resize_log: {r}")
    actions = [r["action"] for r in trainer.resize_log]
    check(actions == ["SHRINK", "EXPAND"], f"resizes {actions}")
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree.leaves(state)}
    log(f"  after the expand every state leaf spans {sorted(spans)} "
        f"devices")
    check(spans == {chips}, f"state leaves span {spans} devices")
    elastic = [(m["step"], m["slices"], m["loss"]) for m in trainer.metrics]
    del state, trainer

    one, _ = build(ARCH, seq_len=SEQ, global_batch=GLOBAL_BATCH,
                   steps=steps, slices=1)
    one.train(seed=seed)
    fixed = [m["loss"] for m in one.metrics]
    diffs = [abs(e[2] - f) for e, f in zip(elastic, fixed)]
    for (step, slices, loss), f, d in zip(elastic, fixed, diffs):
        log(f"  step {step}: {slices} slices loss {loss:.6f}, one device "
            f"{f:.6f}, |diff| {d:.2e}")
    check(len(elastic) == len(fixed) == steps, "missing losses")
    check(max(diffs) <= ELASTIC_LOSS_TOL,
          f"losses differ by {max(diffs)} > {ELASTIC_LOSS_TOL}")


# -- main ---------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the elastic path across four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and data")
    args = ap.parse_args()

    devs = device_or_exit(args.chips)
    from repro.launch.compile_cache import enable_compilation_cache
    import jax
    log(f"jax {jax.__version__}; {len(devs)} x {devs[0].device_kind}; "
        f"compilation cache {enable_compilation_cache()}")

    if args.chips == 1:
        phases = [("train", lambda: phase_train(args.seed)),
                  ("serve", lambda: phase_decode(phase_serve(args.seed),
                                                 args.seed)),
                  ("kernels", lambda: phase_kernels(args.seed))]
    else:
        phases = [("elastic", lambda: phase_elastic(args.seed, args.chips))]

    failed = []
    for name, run in phases:
        log(f"== {name}")
        t0 = time.perf_counter()
        try:
            run()
        except Exception:   # report the phase, go on with the next one
            traceback.print_exc()
            failed.append(name)
            log(f"== {name}: FAILED")
            continue
        log(f"== {name}: ok ({time.perf_counter() - t0:.1f} s)")
    if failed:
        log(f"chip_smoke: failed phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

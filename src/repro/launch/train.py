"""Training launcher: any assigned arch, optional elasticity.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 100 [--reduced] [--slices 4 --elastic] [--devices 8]

The job starts on ``--slices`` data-parallel slices.  With ``--elastic`` a
LocalRMS holds one node per slice the devices can host, and the job
expands and shrinks under its DMR decisions.  ``--devices N`` asks for N
CPU host devices before JAX starts, so that elasticity runs on one host
without an accelerator; on a TPU the chips are the devices.
"""
import argparse
import os
import sys


def build(arch: str = "smollm-135m", *, reduced: bool = False,
          seq_len: int = 128, global_batch: int = 16, grad_accum: int = 1,
          lr: float = 3e-3, steps: int = 100, slices: int = 1,
          model_ways: int = 1, elastic: bool = False,
          check_period: int = 10, ckpt_dir=None):
    """The launcher's trainer: returns ``(trainer, rms)``, ``rms`` None
    unless elastic."""
    import jax

    from repro.data import DataConfig
    from repro.models import build_model, get_model, reduced_config
    from repro.optim import AdamWConfig
    from repro.rms.job import Job
    from repro.runtime import ElasticTrainer, LocalRMS, TrainerConfig

    _, cfg = get_model(arch)
    if reduced:
        cfg = reduced_config(cfg)
    model = build_model(cfg)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                      global_batch=global_batch,
                      frontend=cfg.frontend,
                      frontend_tokens=cfg.frontend_tokens,
                      d_model=cfg.d_model, enc_dec=cfg.family == "encdec")
    opt = AdamWConfig(lr=lr, warmup_steps=max(steps // 20, 1),
                      total_steps=steps)
    rms = None
    max_slices = slices
    if elastic:
        rms = LocalRMS(num_nodes=max(jax.device_count() // model_ways, 1))
        max_slices = rms.cluster.num_nodes
        rms.submit(Job(job_id=0, app=f"lm:{cfg.name}", submit_time=0.0,
                       work=steps, min_nodes=1, max_nodes=max_slices,
                       preferred=None, requested_nodes=slices), start=True)
    trainer = ElasticTrainer(
        model, opt, data,
        TrainerConfig(steps=steps, grad_accum=grad_accum,
                      model_ways=model_ways, slices=slices,
                      max_slices=max_slices, check_period=check_period,
                      log_period=max(steps // 10, 1), ckpt_dir=ckpt_dir),
        rms=rms, job_id=0)
    return trainer, rms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--slices", type=int, default=1)
    ap.add_argument("--model-ways", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0,
                    help="request N CPU host devices before jax init")
    ap.add_argument("--elastic", action="store_true",
                    help="attach a LocalRMS and honour DMR decisions")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    trainer, _ = build(
        args.arch, reduced=args.reduced, seq_len=args.seq_len,
        global_batch=args.global_batch, grad_accum=args.grad_accum,
        lr=args.lr, steps=args.steps, slices=args.slices,
        model_ways=args.model_ways, elastic=args.elastic,
        ckpt_dir=args.ckpt_dir)
    trainer.train()
    for m in trainer.metrics:
        print(f"step {m['step']:5d} loss {m['loss']:.4f} "
              f"slices {m['slices']}")
    if trainer.resize_log:
        print("resizes:", trainer.resize_log)
    return 0


if __name__ == "__main__":
    sys.exit(main())

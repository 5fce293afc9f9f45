"""Live elastic training: a malleable LM job expands and shrinks under
the DMR API against an in-process RMS, resharding its TrainState on the
fly (the paper's §5 protocol, end to end).

The job starts on every device JAX finds.  A scripted rival job queues
for half of them, so the RMS shrinks the job; when the rival finishes the
job expands back.  It needs at least 2 devices: on a TPU host, its chips;
without an accelerator, CPU host devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/elastic_train.py
"""
import jax

from repro.launch.train import build
from repro.runtime import scripted_rival

STEPS, CHECK = 120, 20


def main():
    print(f"devices: {jax.device_count()}")
    trainer, rms = build("smollm-135m", reduced=True, seq_len=64,
                         global_batch=8, steps=STEPS,
                         slices=jax.device_count(), elastic=True,
                         check_period=CHECK)
    trainer.train(on_step=scripted_rival(rms, submit_at=2 * CHECK,
                                         finish_at=4 * CHECK, log=print))
    for m in trainer.metrics:
        print(f"step {m['step']:4d} loss {m['loss']:.4f} "
              f"slices {m['slices']}")
    for r in trainer.resize_log:
        print(f"[step {r['step']}] DMR {r['action']} {r['from']} -> "
              f"{r['to']} slices (resize {r['resize_s'] * 1e3:.0f} ms)")
    actions = [r["action"] for r in trainer.resize_log]
    assert actions == ["SHRINK", "EXPAND"], actions
    print("OK: job shrank under queue pressure and expanded back — the "
          "paper's malleability loop, live.")


if __name__ == "__main__":
    main()

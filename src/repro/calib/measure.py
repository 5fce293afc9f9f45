"""Measurement harness: time real redistribute runs on device meshes.

Two backends close the loop between the jax runtime and the RMS simulator:

- ``jax`` — the *real* path: for each grid geometry ``(p, q)`` an array of
  ``data_bytes`` is laid out over a ``p``-slice mesh
  (:func:`repro.core.meshes.make_mesh`) and resharded onto a ``q``-slice
  mesh with ``jax.device_put`` — exactly the transfer the factor-based
  plans of :mod:`repro.core.redistribute` describe (the reshard tests pin
  that equivalence).  ``migrate_slice`` (the straggler path) is timed the
  same way, and RMS scheduling latency is sampled from real
  ``ReconfigPolicy.decide`` calls.  Each reading is one warm-up call, then
  the best of ``repeats`` timed calls (device work is waited for with
  ``block_until_ready``, :func:`_best_of`).  On a host with fewer devices
  than a geometry needs (the 1-device CI CPU default), the harness falls
  back to a *link proxy*: it times a
  host→device ``device_put`` of the plan's busiest-link bytes, which is
  the quantity the Fig. 3 model divides by ``link_bw`` — honest bandwidth
  measurement, no synthetic numbers.  Multi-device CPU meshes are
  available by setting ``XLA_FLAGS=--xla_force_host_platform_device_count
  =8`` in a fresh process (the CI calibration step does).

- ``plan`` — the *deterministic* backend behind the committed golden
  artifact: samples are generated from hidden "ground truth" parameters
  (:data:`TRUE_PARAMS`, deliberately different from the paper-fit
  constants) plus seeded multiplicative noise, so measure → fit → artifact
  is byte-reproducible and the fitter's recovery accuracy is testable.
  Artifacts are labelled with their backend, so a ``plan`` calibration can
  never masquerade as a hardware measurement.

CLI (also the CI smoke step)::

    PYTHONPATH=src python -m repro.calib.measure --backend plan \\
        [--out calib.json] [--check tests/data/golden_calibration.json]
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m repro.calib.measure --backend jax --quick
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.calib.artifact import SAMPLE_DIGITS

MiB = 1024 ** 2
GiB = 1024 ** 3

#: The CI CPU-mesh grid: factor-2 geometries across the Fig. 3 x-axis and
#: three data sizes.  ``(p, q)`` with ``q > p`` is an expand; every
#: geometry is also measured in the shrink direction ``(q, p)``.
CI_GEOMETRIES: Tuple[Tuple[int, int], ...] = (
    (1, 2), (2, 4), (4, 8), (8, 16), (16, 32), (32, 64))
CI_DATA_BYTES: Tuple[int, ...] = (64 * MiB, 256 * MiB, GiB)
CI_SCHED_NODES: Tuple[int, ...] = (2, 4, 8, 16, 32, 64)

#: Hidden ground truth of the ``plan`` backend — what the fitter must
#: recover.  Deliberately off the paper-fit constants so a fit that just
#: echoes the defaults fails the recovery test.
TRUE_PARAMS: Dict[str, float] = {
    "link_bw": 4.6e9, "spawn_s": 0.055, "shrink_sync_s": 0.0045,
    "sched_base_s": 0.38, "sched_per_node_s": 0.0028,
}
#: Multiplicative log-normal noise sigma of the ``plan`` backend.
PLAN_NOISE_SIGMA = 0.03


@dataclasses.dataclass(frozen=True)
class MeasureConfig:
    """One measurement campaign: geometries × data sizes (+ sched nodes)."""
    geometries: Tuple[Tuple[int, int], ...] = CI_GEOMETRIES
    data_bytes: Tuple[int, ...] = CI_DATA_BYTES
    sched_nodes: Tuple[int, ...] = CI_SCHED_NODES
    repeats: int = 3
    seed: int = 2026
    backend: str = "plan"            # "plan" | "jax"

    def grid_doc(self) -> Dict[str, object]:
        return {"geometries": [list(g) for g in self.geometries],
                "data_bytes": list(self.data_bytes),
                "sched_nodes": list(self.sched_nodes),
                "repeats": self.repeats, "seed": self.seed}


def _sample(kind: str, old: int, new: int, nbytes: int,
            participants: int, busiest: int, seconds: float
            ) -> Dict[str, object]:
    return {"kind": kind, "old": old, "new": new, "bytes": nbytes,
            "participants": participants, "busiest_bytes": busiest,
            "seconds": round(seconds, SAMPLE_DIGITS)}


def resize_features(kind: str, p: int, q: int, nbytes: int
                    ) -> Tuple[int, int]:
    """``(participants, busiest_bytes)`` of the (p → q, nbytes) plan."""
    # Deferred: repro.core.redistribute imports jax, and this module's
    # grid/config surface must stay importable from jax-free consumers
    # (the sweep driver imports repro.calib.artifact in every worker).
    from repro.core.redistribute import expand_plan, plan_stats, shrink_plan
    plan = expand_plan(p, q, nbytes) if kind == "expand" else \
        shrink_plan(p, q, nbytes)
    return plan_stats(plan)


# ---------------------------------------------------------------------------
# plan backend — deterministic synthetic measurement
# ---------------------------------------------------------------------------

def _measure_plan(config: MeasureConfig
                  ) -> Tuple[List[Dict[str, object]], Dict[str, object]]:
    rng = np.random.default_rng(config.seed)
    tp = TRUE_PARAMS
    samples: List[Dict[str, object]] = []

    def noisy(t: float) -> float:
        return t * float(np.exp(PLAN_NOISE_SIGMA * rng.standard_normal()))

    for p, q in config.geometries:
        for nbytes in config.data_bytes:
            for kind, a, b in (("expand", p, q), ("shrink", q, p)):
                parts, busiest = resize_features(kind, a, b, nbytes)
                sync = tp["shrink_sync_s"] if kind == "shrink" else 0.0
                true_t = (tp["spawn_s"] + busiest / tp["link_bw"]
                          + sync * parts)
                for _ in range(config.repeats):
                    samples.append(_sample(kind, a, b, nbytes, parts,
                                           busiest, noisy(true_t)))
    for nodes in config.sched_nodes:
        true_t = tp["sched_base_s"] + tp["sched_per_node_s"] * nodes
        for _ in range(config.repeats):
            samples.append(_sample("sched", nodes, nodes, 0, nodes, 0,
                                   noisy(true_t)))
    env = {"backend": "plan", "noise_sigma": PLAN_NOISE_SIGMA,
           "true_params": dict(TRUE_PARAMS)}
    return samples, env


# ---------------------------------------------------------------------------
# jax backend — real device-mesh measurement
# ---------------------------------------------------------------------------

def _best_of(fn, repeats: int) -> float:
    """One warm-up call, then the best of N timed calls."""
    import jax
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _elems_for(nbytes: int, slices: int) -> int:
    """float32 element count ≈ nbytes, divisible by the slice count."""
    per_slice = max(nbytes // 4 // slices, 1)
    return per_slice * slices


def _measure_resize_jax(kind: str, p: int, q: int, nbytes: int,
                        repeats: int, devices) -> float:
    """Time the real reshard: device_put from a p-slice to a q-slice mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.meshes import make_mesh

    elems = _elems_for(nbytes, max(p, q))
    old = NamedSharding(make_mesh(p, 1, devices=devices), P("data"))
    new = NamedSharding(make_mesh(q, 1, devices=devices), P("data"))
    x = jax.device_put(np.zeros(elems, np.float32), old)
    return _best_of(lambda: jax.device_put(x, new), repeats)


def _measure_link_proxy(busiest: int, repeats: int, device) -> float:
    """Single-device fallback: time a host→device copy of the busiest-link
    bytes — the quantity the model divides by ``link_bw``."""
    import jax
    buf = np.zeros(max(busiest // 4, 1), np.float32)
    return _best_of(lambda: jax.device_put(buf, device), repeats)


def _measure_migrate_jax(slices: int, nbytes: int, repeats: int,
                         devices) -> float:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.meshes import make_mesh
    from repro.core.redistribute import migrate_slice

    mesh = make_mesh(slices, 1, devices=devices)
    elems = _elems_for(nbytes, slices)
    x = jax.device_put(np.zeros(elems, np.float32),
                       NamedSharding(mesh, P("data")))
    return _best_of(lambda: migrate_slice(x, mesh, 0, slices - 1), repeats)


def _measure_sched_jax(nodes: int, repeats: int) -> float:
    """Real in-process RMS policy latency (the measured part of Fig. 3a)."""
    from repro.rms.cluster import Cluster
    from repro.rms.job import Job, JobState
    from repro.rms.policy import ReconfigPolicy

    pol = ReconfigPolicy()
    cluster = Cluster(2 * nodes)
    job = Job(job_id=0, app="fs", submit_time=0, work=2, min_nodes=1,
              max_nodes=2 * nodes, preferred=None, requested_nodes=nodes)
    job.state = JobState.RUNNING
    job.nodes = nodes
    cluster.allocate(0, nodes)
    pol.decide(cluster, [], job, minimum=nodes, maximum=nodes, factor=2)
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        pol.decide(cluster, [], job, minimum=nodes, maximum=nodes, factor=2)
        best = min(best, time.perf_counter() - t0)
    return best


def _measure_jax(config: MeasureConfig
                 ) -> Tuple[List[Dict[str, object]], Dict[str, object]]:
    import jax

    devices = jax.devices()
    samples: List[Dict[str, object]] = []
    proxied = 0
    for p, q in config.geometries:
        for nbytes in config.data_bytes:
            for kind, a, b in (("expand", p, q), ("shrink", q, p)):
                parts, busiest = resize_features(kind, a, b, nbytes)
                if max(a, b) <= len(devices):
                    secs = _measure_resize_jax(kind, a, b, nbytes,
                                               config.repeats, devices)
                else:
                    secs = _measure_link_proxy(busiest, config.repeats,
                                               devices[0])
                    proxied += 1
                samples.append(_sample(kind, a, b, nbytes, parts, busiest,
                                       secs))
        if 2 <= p <= len(devices):
            nbytes = config.data_bytes[0]
            secs = _measure_migrate_jax(p, nbytes, config.repeats, devices)
            samples.append(_sample("migrate", p, p, nbytes, 2,
                                   nbytes // p, secs))
    for nodes in config.sched_nodes:
        samples.append(_sample("sched", nodes, nodes, 0, nodes, 0,
                               _measure_sched_jax(nodes, config.repeats)))
    env = {"backend": "jax",
           "device_kind": devices[0].device_kind,
           "num_devices": len(devices),
           "link_proxy_samples": proxied}
    return samples, env


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def measure_grid(config: MeasureConfig
                 ) -> Tuple[List[Dict[str, object]], Dict[str, object]]:
    """Run the campaign; returns ``(samples, environment)``."""
    if config.backend == "plan":
        return _measure_plan(config)
    if config.backend == "jax":
        return _measure_jax(config)
    raise ValueError(f"unknown backend {config.backend!r} "
                     f"(expected 'plan' or 'jax')")


def calibrate(config: Optional[MeasureConfig] = None) -> Dict[str, object]:
    """measure → fit → artifact in one call."""
    from repro.calib.artifact import make_artifact
    from repro.calib.fit import fit_samples

    config = MeasureConfig() if config is None else config

    samples, env = measure_grid(config)
    fitted, residuals, checks = fit_samples(samples)
    return make_artifact(samples=samples, fitted=fitted,
                         residuals=residuals, checks=checks,
                         grid=config.grid_doc(), backend=config.backend,
                         environment=env)


QUICK_GEOMETRIES = ((1, 2), (2, 4), (4, 8))
QUICK_DATA_BYTES = (4 * MiB, 16 * MiB)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", choices=("plan", "jax"), default="plan")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2026)
    ap.add_argument("--quick", action="store_true",
                    help="small grid (fits single-device CI in seconds)")
    ap.add_argument("--out", default=None,
                    help="write the calibration artifact here")
    ap.add_argument("--check", default=None,
                    help="golden artifact to byte-compare against "
                         "(exit 1 on mismatch)")
    args = ap.parse_args(argv)

    kw: Dict[str, object] = dict(backend=args.backend,
                                 repeats=args.repeats, seed=args.seed)
    if args.quick:
        kw.update(geometries=QUICK_GEOMETRIES, data_bytes=QUICK_DATA_BYTES)
    doc = calibrate(MeasureConfig(**kw))

    f = doc["fitted"]
    print(f"# calibration {doc['calibration_id']} backend={doc['backend']} "
          f"samples={len(doc['samples'])}")
    print(f"# fitted: link_bw={f['link_bw']:.4g} B/s "
          f"spawn_s={f['spawn_s']:.4g} shrink_sync_s="
          f"{f['shrink_sync_s']:.4g} sched_base_s={f['sched_base_s']:.4g} "
          f"sched_per_node_s={f['sched_per_node_s']:.4g}")
    print(f"# residuals: {doc['residuals']}")
    print(f"# checks: {doc['checks']}")
    if not all(doc["checks"].values()):
        print("# FAIL: fitted model violates the Fig. 3 shape checks")
        return 2
    if args.out:
        from repro.calib.artifact import write_calibration
        write_calibration(args.out, doc)
        print(f"# wrote {args.out}")
    if args.check:
        from repro.calib.artifact import dumps_calibration, load_calibration
        golden = dumps_calibration(load_calibration(args.check))
        if dumps_calibration(doc) != golden:
            print(f"# MISMATCH against {args.check}: calibration bytes "
                  f"differ (grid or fitter changed — regenerate the golden "
                  f"only for intentional changes)")
            return 1
        print(f"# artifact matches {args.check}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""One run of one cell: set-up, the measured window, the comparison.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

- ``configs/<config>.json``: the model's published sizes, its source, the
  reference (``references/*.py``) that computes it, and how the program is
  told them (``program``: the model options the harness sets, the fields
  it checks, what the program runs where it has no option);
- ``mixes/<traffic>.json``: the job (sequence length, global batch, slices,
  optimizer), the window's segments and, for an elastic job, the rival;
- ``layer_metrics/<metric>.py``: ``read(ctx)`` gives the metric or None;
- ``limits/<workload>.json``: the limit of every number ``correct`` compares.

The program is reached only through its entry: ``repro.launch.train.build``
builds the trainer (with a ``LocalRMS`` when the mix is elastic), and the
window drives ``ElasticTrainer.train`` in segments, each ended by a wait for
the returned state.  The benchmark replaces the trainer's feed with its own
(``traffic.TokenFeed``), makes the weights itself from the seed in one
jitted call, and wraps the trainer's bound callables in trace annotations.

Adding a configuration takes new files and appended entries alone:

- files: ``configs/<config>.json`` (with its ``cpu_test`` sizes, which the
  CPU tests read), ``references/<family>.py`` where no reference computes
  it yet, ``mixes/<traffic>.json``, ``limits/<workload>.json`` and, for a
  new per-layer metric, ``layer_metrics/<metric>.py``;
- entries appended to ``BENCHMARK.json``: one under ``configs``, one under
  ``workloads`` for each cell, and the cell's name in the ``workloads``
  list of each ``per_layer`` metric it reports, or a new such metric.

Memory, in float32 copies of the parameters on the cell's first chip:
the program holds its own state and step (16 bytes a parameter with fp32
weights, gradients and the two AdamW moments).  Set-up adds at most one
copy beside it: ``make_state`` holds its outputs alone, and
``delta_norm_fn`` makes the start again leaf by leaf inside the norms'
fusions.  The reference runs once the program's state is freed and holds
at most five copies (20 bytes a parameter) and one row's activations:
the parameters, the two moments, the gradient, and one row's gradient
while the gradient is summed.  ``compile_rehearsal.py`` prints each of
these for a described v5e before any chip run.
"""
from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import compare  # noqa: E402
import traffic  # noqa: E402
from references import train as ref_train  # noqa: E402


class NoChip(RuntimeError):
    """The platform or the number of devices is not what the cell needs."""


class BadRun(RuntimeError):
    """The run cannot be measured as the cell defines it."""


# -- loading a cell from data -------------------------------------------------


def load_json(rel: str) -> Any:
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


def load_module(rel: str):
    path = os.path.join(HERE, rel)
    name = "chipbench_" + rel.replace("/", "_").replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    reference: Any
    per_layer: List[str]

    @property
    def elastic(self) -> bool:
        return self.mix["kind"] == "elastic"


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.load(open(os.path.join(ROOT, conf["file"])))
    per_layer = [m["name"] for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return Cell(name=name, chips=w["chips"], config=config,
                mix=load_json(f"mixes/{w['traffic']}.json"),
                limits=load_json(f"limits/{name}.json"),
                reference=load_module(config["reference"]),
                per_layer=per_layer)


# -- the program --------------------------------------------------------------


def set_options(trainer, cell: Cell) -> None:
    """Give the program the configuration's values of the model options it
    has (``program.options``: ``ModelConfig`` field -> configuration key),
    through ``repro.models.build_model`` as ``build`` itself builds the
    model, before any state or step exists."""
    opts = cell.config["program"].get("options", {})
    if not opts:
        return
    from repro.models import build_model
    cfg = dataclasses.replace(trainer.model.cfg, **{
        field: cell.config[key] for field, key in opts.items()})
    trainer.model = build_model(cfg)


def departures(trainer, cell: Cell) -> List[str]:
    """Where the program runs other than the configuration and the mix
    state: its model fields, what it runs for a key it has no option for
    (``program.no_option``), its optimizer."""
    prog = cell.config["program"]
    got = trainer.model.cfg
    out = []
    for field, key in prog["fields"].items():
        if getattr(got, field) != cell.config[key]:
            out.append(f"the program runs {field}={getattr(got, field)!r} "
                       f"where the configuration states {key}="
                       f"{cell.config[key]!r}")
    for field, want in prog["values"].items():
        have = getattr(got, field)
        have = list(have) if isinstance(have, tuple) else have
        if have != want:
            out.append(f"the program runs {field}={have!r}, the "
                       f"configuration states {want!r}")
    for key, runs in prog.get("no_option", {}).items():
        if runs != cell.config[key]:
            out.append(f"the program has no option for {key} and runs "
                       f"{runs!r} where the configuration states "
                       f"{cell.config[key]!r}")
    opt = dataclasses.asdict(trainer.opt_cfg)
    for k, v in cell.mix["optimizer"].items():
        if opt[k] != v:
            out.append(f"the program's optimizer has {k}={opt[k]!r}, the "
                       f"mix states {v!r}")
    return out


def check_program(trainer, cell: Cell) -> None:
    """The program must run what the configuration and the mix state."""
    found = departures(trainer, cell)
    if found:
        raise BadRun("; ".join(found))


def build_trainer(cell: Cell, reduced: bool):
    from repro.launch.train import build
    mix = cell.mix
    trainer, rms = build(
        cell.config["program"]["arch"], reduced=reduced,
        seq_len=mix["seq_len"], global_batch=mix["global_batch"],
        lr=mix["optimizer"]["lr"], steps=mix["optimizer"]["total_steps"],
        slices=mix["slices"], elastic=cell.elastic,
        check_period=mix.get("check_period", 10))
    set_options(trainer, cell)
    check_program(trainer, cell)
    return trainer, rms


class Recorder:
    """Wraps the trainer's bound callables: a trace annotation around each
    call into a layer, and, while ``keep`` is on, the losses the step
    returns and the first moments after the first step."""

    def __init__(self, trainer, mu_norms):
        import jax
        from jax.profiler import TraceAnnotation
        from repro.core import mesh_num_slices
        self.keep = False
        self.losses: list = []
        self.mu = None
        self.fingerprints: list = []
        self.check_fingerprints = False
        self._wrapped: dict = {}
        self.layouts: dict = {}     # slices -> (compiled step, mesh)
        self._mu_norms = mu_norms
        step_fn = trainer.step_fn

        def wrapped_step_fn(mesh):
            fn = step_fn(mesh)
            self.layouts[mesh_num_slices(mesh)] = (fn, mesh)
            if fn not in self._wrapped:
                def step(state, batch):
                    with TraceAnnotation("train_step"):
                        new, metrics = fn(state, batch)
                    if self.keep:
                        self.losses.append(metrics["loss"])
                        if self.mu is None:
                            self.mu = self._mu_norms(new["opt"]["mu"])
                    return new, metrics
                self._wrapped[fn] = step
            return self._wrapped[fn]
        trainer.step_fn = wrapped_step_fn

        if trainer.dmr is None:
            return
        reconf = trainer.maybe_reconfigure
        fingerprint = jax.jit(_fingerprint)

        def maybe_reconfigure(state):
            before = len(trainer.resize_log)
            with TraceAnnotation("maybe_reconfigure"):
                new = reconf(state)
            if len(trainer.resize_log) > before:
                with TraceAnnotation("layout", slices=trainer.slices):
                    pass
                if self.check_fingerprints:
                    self.fingerprints.append(
                        (fingerprint(state), fingerprint(new)))
            return new
        trainer.maybe_reconfigure = maybe_reconfigure

        check = trainer.dmr.check_status

        def check_status(**kw):
            with TraceAnnotation("dmr.check_status"):
                return check(**kw)
        trainer.dmr.check_status = check_status


def _fingerprint(state):
    """Per leaf, an exact position-weighted sum of the bits mod 2**32."""
    import jax
    import jax.numpy as jnp

    def one(x):
        bits = x if x.dtype == jnp.uint32 else \
            jax.lax.bitcast_convert_type(x, jnp.uint32)
        pos = jnp.arange(x.size, dtype=jnp.uint32).reshape(x.shape)
        return jnp.sum(bits * (pos * jnp.uint32(2654435761) + 1),
                       dtype=jnp.uint32)
    return jax.tree.map(one, state)


def make_state(trainer, cell: Cell, seed: int):
    """The trainer's whole state, made on the device in one jitted call: the
    reference's initial weights from the seed in the program's layout, the
    rest as the program starts it (zero moments, step 0)."""
    import jax
    import jax.numpy as jnp
    ref, cfg = cell.reference, cell.config
    shardings = trainer._state_shardings(trainer.mesh)
    abstract = jax.eval_shape(trainer.init_state, 0)
    key = traffic.seed_key(seed, traffic.WEIGHTS)
    want = jax.eval_shape(lambda: ref.to_program(ref.init_params(key, cfg),
                                                 cfg))
    if jax.tree.structure(want) != jax.tree.structure(abstract["params"]) \
            or jax.tree.leaves(jax.tree.map(
                lambda a, b: a.shape != b.shape or a.dtype != b.dtype,
                want, abstract["params"])).count(True):
        raise BadRun("the program's parameters are not laid out as "
                     f"{cell.config['reference']} maps them")

    def make(key):
        state = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract)
        state["params"] = ref.to_program(ref.init_params(key, cfg), cfg)
        return state
    return jax.jit(make, out_shardings=shardings)(key)


def unit_norm_fn(cell: Cell):
    import jax
    ref, cfg = cell.reference, cell.config
    return jax.jit(lambda t: ref_train.norm_arrays(ref.from_program(t, cfg)))


def delta_norm_fn(cell: Cell):
    """Per-unit norms of the parameters' change since a seed's weights:
    ``fn(params, key)``."""
    import jax
    ref, cfg = cell.reference, cell.config

    def fn(params, key):
        start = ref.to_program(ref.init_params(key, cfg), cfg)
        d = jax.tree.map(lambda a, b: a - b, params, start)
        return ref_train.norm_arrays(ref.from_program(d, cfg))
    return jax.jit(fn)


# -- one run ------------------------------------------------------------------


@dataclasses.dataclass
class Segment:
    first: int
    end: int
    resize: bool
    t0: float
    t1: float
    slices0: int
    slices1: int
    resizes: int


def init_jax(cell: Cell, chip: bool = True):
    """Look for the chips (unless ``chip`` is off), and keep every compiled
    program in the program's persistent cache, however fast it compiled.
    Returns the devices the cell runs on."""
    import jax
    devs = jax.devices()
    if chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX found "
                     f"{len(devs)}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    return devs[:cell.chips]


@dataclasses.dataclass
class Job:
    """The trainer the benchmark drives, with what it wraps around it."""
    trainer: Any
    rms: Any
    rec: Recorder
    hook: Any


def start_job(cell: Cell, reduced: bool = False, plant=None) -> Job:
    """Build the trainer through the program's entry and wrap it; for an
    elastic mix, with the rival as its ``on_step`` hook."""
    trainer, rms = build_trainer(cell, reduced)
    if plant is not None:
        plant(trainer)
    hook = None
    if cell.elastic:
        from repro.rms.job import Job as RmsJob
        hook = traffic.Rival(rms, RmsJob, cell.mix["rival"]["nodes"])
    return Job(trainer, rms, Recorder(trainer, unit_norm_fn(cell)), hook)


def first_steps(job: Job, cell: Cell, seed: int):
    """The seed's weights and feed, then the first steps through the
    window's own call and feed: for an elastic job a shrink after the first
    and an expand after the second, so that both layouts and the reshard
    are warm and compared.  Returns the state and the program's readings."""
    import jax
    mix, trainer, rec = cell.mix, job.trainer, job.rec
    trainer.data = traffic.TokenFeed(seed, cell.config["vocab_size"],
                                     mix["seq_len"], mix["global_batch"])
    state = make_state(trainer, cell, seed)
    n_resize = len(trainer.resize_log)
    if cell.elastic:
        job.hook.plan(mix["setup"]["arrive"], mix["setup"]["leave"])
        trainer.cfg.check_period = mix["setup"]["check_period"]
        rec.check_fingerprints = True
    rec.keep, rec.losses, rec.mu = True, [], None
    trainer.cfg.steps = int(state["step"]) + mix["setup_steps"]
    state = trainer.train(state, on_step=job.hook)
    jax.block_until_ready(state)
    rec.keep = False
    prog = {"losses": [float(x) for x in rec.losses],
            "mu": ref_train.expand(rec.mu),
            "delta": ref_train.expand(delta_norm_fn(cell)(
                state["params"], traffic.seed_key(seed, traffic.WEIGHTS))),
            "resizes": [(e["from"], e["to"])
                        for e in trainer.resize_log[n_resize:]]}
    return state, prog


def reshard_mismatch(rec: Recorder) -> int:
    """Units of the state whose fingerprint a resize changed."""
    import jax
    n = sum(int(a != b) for before, after in rec.fingerprints
            for a, b in zip(jax.tree.leaves(jax.device_get(before)),
                            jax.tree.leaves(jax.device_get(after))))
    rec.fingerprints = []
    return n


def numbers(cell: Cell, prog: dict, ref: dict, mismatch: int) -> dict:
    nums = compare.numbers(prog, ref, cell.mix["optimizer"]["beta1"])
    if cell.elastic:
        want = [tuple(x) for x in cell.mix["setup"]["resizes"]]
        nums["reshard_mismatch"] = {
            "value": mismatch + (0 if prog["resizes"] == want else 1000),
            "at": f"set-up resizes {prog['resizes']}"}
    return nums


def run_window(trainer, cell: Cell, rec: Recorder, hook, state,
               seconds: float):
    """Segments of ``ElasticTrainer.train`` until ``seconds`` have passed,
    ending only after a segment without a resize and, for an elastic mix,
    only where a cycle of the rival ends, so that every window holds
    whole shrink-expand cycles."""
    import jax
    from jax.profiler import TraceAnnotation
    mix = cell.mix
    start = int(state["step"])
    resizes: List[int] = []
    ends = None        # steps the window may end at; None: any
    if cell.elastic:
        cp = mix["check_period"]
        trainer.cfg.check_period = cp
        arrive, leave = traffic.cycle_events(
            start, cp, mix["rival"]["wide_steps"],
            mix["rival"]["narrow_steps"], cycles=10_000)
        hook.plan(arrive, leave)
        resizes = sorted(arrive + leave)
        ends = {a - 1 for a in arrive}   # whole cycles only
    plan = traffic.plan_segments(start, start + 10_000_000, resizes,
                                 mix["segment_steps"])
    segs: List[Segment] = []
    n_resize0 = len(trainer.resize_log)
    with TraceAnnotation("window"):
        t_start = time.perf_counter()
        for first, end, resize in plan:
            s0, r0 = trainer.slices, len(trainer.resize_log)
            trainer.cfg.steps = end
            t0 = time.perf_counter()
            with TraceAnnotation("segment"):
                state = trainer.train(state, on_step=hook)
                with TraceAnnotation("sync"):
                    jax.block_until_ready(state)
            t1 = time.perf_counter()
            segs.append(Segment(first, end, resize, t0, t1, s0,
                                trainer.slices,
                                len(trainer.resize_log) - r0))
            if t1 - t_start >= seconds and not resize and (
                    ends is None or end in ends):
                break
    window = (t_start, segs[-1].t1)
    log = trainer.resize_log[n_resize0:]
    got = [e["step"] for e in log]
    want = [r for r in resizes if r < segs[-1].end]
    if got != want:
        raise BadRun(f"the window resized at steps {got}, the mix "
                     f"schedules {want}")
    return state, segs, window, log


def steady_step_times(segs: List[Segment]) -> Dict[int, float]:
    per: Dict[int, List[float]] = {}
    for s in segs:
        if not s.resize:
            t, n = per.get(s.slices0, (0.0, 0))
            per[s.slices0] = (t + s.t1 - s.t0, n + s.end - s.first)
    return {k: t / n for k, (t, n) in per.items()}


def reconfig_stalls(segs: List[Segment]) -> List[float]:
    """Per resize: its segment's time less the steady time of its two
    steps, one on each layout."""
    steady = steady_step_times(segs)
    out = []
    for s in segs:
        if s.resize:
            if s.resizes != 1:
                raise BadRun(f"segment {s.first}-{s.end} holds {s.resizes} "
                             f"resizes, not 1")
            if s.slices0 not in steady or s.slices1 not in steady:
                raise BadRun("the window has no steady segment on "
                             f"{s.slices0} or {s.slices1} slices")
            out.append(s.t1 - s.t0 - steady[s.slices0] - steady[s.slices1])
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process: float, reduced: bool = False,
             chip: bool = True, plant=None) -> dict:
    """Run the cell once and return the result line's dict; the numbers
    compared go to stderr as well.  ``chip=False`` skips the look for a
    chip (CPU rehearsals); ``plant(trainer)`` breaks the timed path under
    the harness (the fault tests)."""
    import jax
    devices = init_jax(cell, chip)
    mix = cell.mix

    # -- set-up: the trainer, its weights, the first steps ----------------
    job = start_job(cell, reduced, plant)
    trainer, rec, hook = job.trainer, job.rec, job.hook
    state, prog = first_steps(job, cell, seed)
    history0 = len(trainer.dmr.history) if trainer.dmr else 0
    setup_s = time.perf_counter() - t_process

    # -- the window -----------------------------------------------------------
    if trace:   # a traced window is short: the trace grows with its steps
        seconds = min(seconds, mix["trace_seconds"])
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # no event per Python call
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        state, segs, window, _ = run_window(trainer, cell, rec, hook,
                                            state, seconds)
    finally:
        if trace:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            t_stop = time.perf_counter() - t_stop
    steps = sum(s.end - s.first for s in segs)
    tokens = steps * mix["global_batch"] * mix["seq_len"]
    per_step = sorted((s.t1 - s.t0) / (s.end - s.first) for s in segs
                      if not s.resize)
    print(f"window: {len(segs)} segments, {steps} steps in "
          f"{window[1] - window[0]:.3f} s; steady segments' time a step "
          f"min {per_step[0]:.4f} median {per_step[len(per_step) // 2]:.4f}"
          f" max {per_step[-1]:.4f} s", file=sys.stderr)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(st.get("peak_bytes_in_use", 0) for st in stats)
    held = step_bytes(trainer, rec, state)
    print(f"memory: peak_bytes_in_use {peak}; compiled train step a device, "
          f"arguments + outputs not aliased + temporaries, by slices: "
          f"{held}; memory_stats of the first chip: {stats[0]}",
          file=sys.stderr, flush=True)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(max([peak, *held.values()]))}

    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        t_read = time.perf_counter()
        ctx = Context(cell=cell, segs=segs,
                      dmr_history=(trainer.dmr.history[history0:]
                                   if trainer.dmr else []),
                      device_kind=devices[0].device_kind,
                      trace=read_trace(tdir), slices0=segs[0].slices0)
        import trace_reduce
        held = trace_reduce.held_intervals(ctx.trace, ctx.slices0)
        device["busy_s"] = trace_reduce.busy_s(ctx.trace, held)
        device["window_s"] = ctx.trace.window_s()
        breakdown = trace_reduce.breakdown(ctx.trace)
        for name in cell.per_layer:
            v = load_module(f"layer_metrics/{name}.py").read(ctx)
            if v is not None:
                metrics[name] = {"value": v[0], "unit": v[1]}
        del ctx
        print(f"trace: {len(segs)} segments traced; writing the trace took "
              f"{t_stop:.1f} s, reading and reducing it "
              f"{time.perf_counter() - t_read:.1f} s", file=sys.stderr)
    else:
        metrics["train_tokens_per_s"] = {
            "value": tokens / (window[1] - window[0]), "unit": "tokens/s"}
        if cell.elastic:
            stalls = reconfig_stalls(segs)
            metrics["reconfig_s"] = {"value": sum(stalls) / len(stalls),
                                     "unit": "s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    # -- the comparison, with the program's state freed -----------------------
    mismatch = reshard_mismatch(rec)
    del state, trainer, rec, hook, job
    jax.clear_caches()
    nums = numbers(cell, prog, reference_readings(cell, seed), mismatch)
    correct, checks, lines = compare.judge(nums, cell.limits)

    out = {"correct": correct, "attempted": steps, "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    return out


def read_trace(tdir: str):
    import trace_reduce
    try:
        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise BadRun(f"the profiler wrote {len(files)} traces")
        return trace_reduce.load(files[0])
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def step_bytes(trainer, rec, state) -> Dict[int, int]:
    """Per layout the run used, what its compiled step holds on a device
    while it runs: arguments, the outputs not aliased to them, and
    temporaries (``peak_bytes_in_use`` leaves the temporaries out)."""
    import jax
    batch = trainer.data.at(0)

    def abstract(tree, shardings):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, shardings)
    out = {}
    for slices, (fn, mesh) in sorted(rec.layouts.items()):
        with mesh:
            ma = fn.lower(abstract(state, trainer._state_shardings(mesh)),
                          abstract(batch, trainer._batch_shardings(mesh))
                          ).compile().memory_analysis()
        out[slices] = held_bytes(ma)
    return out


def held_bytes(ma) -> int:
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def reference_readings(cell: Cell, seed: int, **kw) -> dict:
    """The reference's first steps from the seed's weights and batches;
    ``kw`` goes to ``references.train.train`` (the control, the faults)."""
    import jax
    ref, cfg, mix = cell.reference, cell.config, cell.mix
    key = traffic.seed_key(seed, traffic.WEIGHTS)
    params = jax.jit(lambda k: ref.init_params(k, cfg))(key)
    feed = traffic.TokenFeed(seed, cfg["vocab_size"], mix["seq_len"],
                             mix["global_batch"])
    batches = [feed.at(k) for k in range(mix["setup_steps"])]
    return ref_train.train(ref, cfg, mix["optimizer"], params, batches, **kw)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reads."""
    cell: Cell
    segs: List[Segment]
    dmr_history: list
    device_kind: str
    trace: Any
    slices0: int

    def flops_per_token(self) -> float:
        return self.cell.reference.flops_per_token(
            self.cell.config, self.cell.mix["seq_len"])

    def tokens_per_step(self) -> int:
        return self.cell.mix["global_batch"] * self.cell.mix["seq_len"]

"""The program's own trace of a reconfiguration: spans inside the trainer,
the DMR and the RMS, the bytes a resize moves and the time its transfer
takes on ``ResizeHandler``, the reader of ``reshard_gbps``, and the named
scopes on the train step's device work.

The resize cycle runs in a child process on four virtual CPU devices (as
``tests/test_multidevice.py`` does), since the test process must keep its
single CPU device.
"""
import importlib.util
import json
import os
import re
import subprocess
import sys
import textwrap
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
PROGRAM_SPANS = {
    "train.reconfigure", "train.batch", "train.step", "train.compile",
    "train.log_sync", "train.save", "train.restore", "reshard.plan",
    "reshard.transfer", "reshard.relayout", "dmr.query", "dmr.expand_wait",
    "rms.decide"}

CYCLE = """
import glob, json, tempfile
import jax
from repro.core import moved_bytes
from repro.launch.train import build
from repro.rms.job import Job
import repro.runtime.trainer as trainer_mod

counted = []
def counting(state, shardings):
    counted.append(1)
    return moved_bytes(state, shardings)
trainer_mod.moved_bytes = counting

trainer, rms = build("smollm-135m", reduced=True, seq_len=32,
                     global_batch=8, steps=10, slices=4, elastic=True,
                     check_period=2)

def on_step(step):
    # a rival wanting 2 of the 4 nodes arrives at steps 2 and 6 and
    # leaves at 4 and 8: shrink, expand, shrink, expand
    if step in (2, 6):
        rms.submit(Job(job_id=step, app="rival", submit_time=0.0, work=1e9,
                       min_nodes=2, max_nodes=2, preferred=None,
                       requested_nodes=2))
    if step in (4, 8):
        rms.finish(step - 2)
    rms.start_pending()

state4 = trainer.init_state(0)
layouts = {n: trainer._state_shardings(
    trainer_mod.make_mesh(n, 1, devices=trainer.devices)) for n in (2, 4)}

def split(sharding):   # over the data axis; the model axis is 1 wide
    return any("data" in (ax if isinstance(ax, tuple) else (ax,))
               for ax in sharding.spec)

def by_hand():
    # ZeRO-1 moments split one dim over the data axis, in 4 or 2 parts in
    # device order; everything else is replicated on every chip held.
    # 4 -> 2: chip 0 holds [0, 1/4), needs [0, 1/2); chip 1 holds
    # [1/4, 1/2), needs [1/2, 1): 3/4 of each split leaf, nothing else.
    # 2 -> 4: chip 1 needs [1/4, 1/2) outside its [1/2, 1); chips 2, 3 are
    # new: 3/4 of each split leaf, and two whole copies of the rest.
    leaves = zip(jax.tree.leaves(state4), jax.tree.leaves(layouts[4]),
                 jax.tree.leaves(layouts[2]))
    sp = rep = 0
    for x, s4, s2 in leaves:
        assert split(s4) == split(s2), "a leaf split in one layout only"
        if split(s4):
            sp += x.nbytes
        else:
            rep += x.nbytes
    return {"SHRINK": 3 * sp // 4, "EXPAND": 3 * sp // 4 + 2 * rep}

hand = by_hand()
params_replicated = not any(split(s)
                            for s in jax.tree.leaves(layouts[4]["params"]))
params_on_shrink = moved_bytes(state4["params"], layouts[2]["params"])

tdir = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(tdir, profiler_options=opts)
state = trainer.train(state4, on_step=on_step)
jax.block_until_ready(state)
jax.profiler.stop_trace()

from jax.profiler import ProfileData
path, = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
spans = []
for plane in ProfileData.from_file(path).planes:
    if plane.name != "/host:CPU":
        continue
    for line in plane.lines:
        for e in line.events:
            if e.name.split(".")[0] in ("train", "reshard", "dmr", "rms"):
                spans.append([e.name, e.start_ns, e.end_ns,
                              {k: str(v) for k, v in e.stats}])
print(json.dumps({
    "handlers": [[h.action.name, h.old_slices, h.new_slices,
                  h.resize_time_s, h.transfer_s, h.moved_bytes]
                 for h in trainer.dmr.history
                 if h.action.name in ("EXPAND", "SHRINK")],
    "host_leaves": [[h.host_leaves for h in trainer.dmr.history
                     if h.action.name in ("EXPAND", "SHRINK")],
                    [r["host_leaves"] for r in trainer.resize_log]],
    "hand": hand, "counted": len(counted),
    "params_replicated": params_replicated,
    "params_on_shrink": params_on_shrink,
    "spans": sorted(spans, key=lambda s: s[1])}))
"""


@pytest.fixture(scope="module")
def cycle():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(CYCLE)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _load_bench(rel):
    spec = importlib.util.spec_from_file_location(
        "test_" + rel.replace("/", "_")[:-3], os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_resize_records_transfer_time_and_bytes(cycle):
    got = [(a, f, t) for a, f, t, *_ in cycle["handlers"]]
    assert got == [("SHRINK", 4, 2), ("EXPAND", 2, 4)] * 2
    for _, _, _, resize_s, transfer_s, moved in cycle["handlers"]:
        assert 0 < transfer_s <= resize_s
        assert moved > 0


def test_moved_bytes_is_the_count_by_hand(cycle):
    for action, _, _, _, _, moved in cycle["handlers"]:
        assert moved == cycle["hand"][action]
    assert cycle["params_replicated"]
    assert cycle["params_on_shrink"] == 0


def test_moved_bytes_is_counted_once_a_layout_pair(cycle):
    assert len(cycle["handlers"]) == 4
    assert cycle["counted"] == 2


def _spans(cycle, name):
    return [s for s in cycle["spans"] if s[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_reshard_spans_lie_in_order_inside_the_reconfiguration(cycle):
    transfers = _spans(cycle, "reshard.transfer")
    assert len(transfers) == 4
    steps = []
    for plan, transfer in zip(_spans(cycle, "reshard.plan"), transfers):
        assert plan[2] <= transfer[1]
        outer = [r for r in _spans(cycle, "train.reconfigure")
                 if _inside(plan, r)]
        assert len(outer) == 1 and _inside(transfer, outer[0])
        steps.append(int(outer[0][3]["step"]))     # the resize's step
        assert plan[3] == transfer[3]
        assert (plan[3]["action"], plan[3]["from"], plan[3]["to"]) in (
            ("SHRINK", "4", "2"), ("EXPAND", "2", "4"))
    assert steps == [2, 4, 6, 8]


def test_each_transfer_holds_one_relayout_with_its_labels(cycle):
    transfers = _spans(cycle, "reshard.transfer")
    relayouts = _spans(cycle, "reshard.relayout")
    assert len(relayouts) == len(transfers) == 4
    for relayout, transfer in zip(relayouts, transfers):
        assert _inside(relayout, transfer)
        assert relayout[3] == transfer[3]


def test_no_state_leaf_crosses_host_memory(cycle):
    handlers, log = cycle["host_leaves"]
    assert handlers == log == [0, 0, 0, 0]


def test_the_rms_decision_lies_inside_the_dmr_query(cycle):
    decides = _spans(cycle, "rms.decide")
    queries = _spans(cycle, "dmr.query")
    assert len(decides) == len(queries) == 4       # steps 2, 4, 6, 8
    for d, q in zip(decides, queries):
        assert _inside(d, q)
        assert any(_inside(q, r) for r in _spans(cycle, "train.reconfigure"))
    assert len(_spans(cycle, "dmr.expand_wait")) == 2


def test_each_layout_compiles_once_in_a_span_of_its_own(cycle):
    assert [int(s[3]["step"]) for s in _spans(cycle, "train.compile")] \
        == [0, 2]
    assert len(_spans(cycle, "train.step")) == 8
    assert len(_spans(cycle, "train.batch")) == 10
    assert len(_spans(cycle, "train.log_sync")) == 10


def test_no_program_span_takes_a_name_the_benchmark_reads():
    sys.path.insert(0, BENCH)
    import trace_reduce
    names = set()
    for root, _, files in os.walk(os.path.join(REPO, "src", "repro")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    names |= set(re.findall(r'TraceAnnotation\(\s*"([^"]+)"',
                                            fh.read()))
    names |= {"train.step", "train.compile"}      # chosen at run time
    assert names == PROGRAM_SPANS
    assert not names & set(trace_reduce.HOST_SPANS)


def test_the_traced_cycle_holds_only_known_program_spans(cycle):
    assert {s[0] for s in cycle["spans"]} <= PROGRAM_SPANS


def _handler(action, moved, seconds):
    from repro.core import Action, ResizeHandler
    return ResizeHandler(job_id=0, action=Action[action], old_slices=4,
                         new_slices=2, moved_bytes=moved, transfer_s=seconds)


def test_reshard_gbps_reads_bytes_over_transfer_time():
    read = _load_bench("layer_metrics/reshard_gbps.py").read
    ctx = types.SimpleNamespace(dmr_history=[
        _handler("SHRINK", 810_000_000, 1.8),
        _handler("NO_ACTION", 0, 0.0),
        _handler("EXPAND", 1_890_000_000, 1.2)])
    value, unit = read(ctx)
    assert unit == "GB/s"
    assert value == pytest.approx(2.7e9 / 3.0 / 1e9)


def test_reshard_gbps_reads_nothing_without_a_resize():
    read = _load_bench("layer_metrics/reshard_gbps.py").read
    assert read(types.SimpleNamespace(dmr_history=[])) is None
    assert read(types.SimpleNamespace(
        dmr_history=[_handler("NO_ACTION", 0, 0.0)])) is None
    # a program whose handlers carry neither field (the one before them)
    old = types.SimpleNamespace(action=types.SimpleNamespace(name="SHRINK"),
                                resize_time_s=2.0)
    assert read(types.SimpleNamespace(dmr_history=[old])) is None


SCOPE = r"(^|[/(]){}[/)]"


def test_train_step_device_ops_carry_the_layer_scopes():
    import jax
    from repro.launch.train import build
    trainer, _ = build("smollm-135m", reduced=True, seq_len=32,
                       global_batch=4, steps=2)
    state = jax.eval_shape(lambda: trainer._fresh_state(0))
    fn = trainer.step_fn(trainer.mesh)
    with trainer.mesh:
        text = fn.lower(state, trainer.data.batch(0)).compile().as_text()
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("attn", "mlp", "lm_head_loss", "adamw"):
        assert any(re.search(SCOPE.format(scope), op) for op in ops), scope
    assert not any(re.search(SCOPE.format("ssd"), op) for op in ops)

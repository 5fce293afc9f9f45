"""Multi-device semantics on CPU host devices (subprocess, 8 devices).

Runs on several devices what the single-device tests cannot: elastic
resharding across meshes of different sizes (values + Listing-3
ownership), slice migration, and an elastic train loop that expands
mid-run without changing the math.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    prelude = "import json, jax, jax.numpy as jnp, numpy as np\n"
    proc = subprocess.run([sys.executable, "-c",
                           prelude + textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_reshard_expand_preserves_values_and_layout():
    out = run_sub("""
    from repro.core import make_mesh, reshard, ownership_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jnp.arange(64.0).reshape(8, 8)
    m2 = make_mesh(2, 1)
    m4 = make_mesh(4, 1)
    x2 = jax.device_put(x, NamedSharding(m2, P("data")))
    x4 = reshard(x2, NamedSharding(m4, P("data")))
    own = ownership_map(x4)
    # Listing 3 expand: old rank r's rows split between new ranks 2r, 2r+1
    starts = sorted(idx[0].start or 0 for idx in own.values())
    print(json.dumps({
        "equal": bool((np.asarray(x4) == np.asarray(x)).all()),
        "ndev": len(own), "starts": starts}))
    """)
    assert out["equal"] and out["ndev"] == 4
    assert out["starts"] == [0, 2, 4, 6]


@pytest.mark.slow
def test_reshard_shrink_and_roundtrip():
    out = run_sub("""
    from repro.core import make_mesh, reshard
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    m8, m2 = make_mesh(8, 1), make_mesh(2, 1)
    x8 = jax.device_put(x, NamedSharding(m8, P("data")))
    x2 = reshard(x8, NamedSharding(m2, P("data")))
    back = reshard(x2, NamedSharding(m8, P("data")))
    print(json.dumps({
        "shrink_ok": bool(np.allclose(np.asarray(x2), np.asarray(x))),
        "roundtrip_ok": bool(np.allclose(np.asarray(back),
                                         np.asarray(x)))}))
    """)
    assert out["shrink_ok"] and out["roundtrip_ok"]


@pytest.mark.slow
def test_migrate_slice_swaps_shards():
    out = run_sub("""
    from repro.core import make_mesh, migrate_slice
    from jax.sharding import NamedSharding, PartitionSpec as P
    m = make_mesh(4, 1)
    x = jnp.repeat(jnp.arange(4.0)[:, None], 3, axis=1)   # row i = i
    xs = jax.device_put(x, NamedSharding(m, P("data")))
    y = migrate_slice(xs, m, 0, 2)
    print(json.dumps({"rows": np.asarray(y)[:, 0].tolist()}))
    """)
    assert out["rows"] == [2.0, 1.0, 0.0, 3.0]


@pytest.mark.slow
def test_elastic_training_expand_matches_fixed():
    """A job that expands 2->4 slices mid-run must compute the same math
    (same loss trajectory) as one that never resizes."""
    out = run_sub("""
    import dataclasses
    from repro.core import Action, Decision
    from repro.models import build_model, get_model, reduced_config
    from repro.runtime import ElasticTrainer, TrainerConfig
    from repro.optim import AdamWConfig
    from repro.data import DataConfig

    class ScriptedRMS:
        def __init__(self, script):
            self.script = dict(script)
            self.calls = 0
        def request_reconfig(self, job_id, *, current, minimum, maximum,
                             factor, preferred):
            self.calls += 1
            return self.script.get(self.calls,
                                   Decision(Action.NO_ACTION, current))
        def confirm_resize(self, job_id, decision, timeout_s):
            return True, 0.0

    _, full = get_model("smollm-135m")
    cfg = dataclasses.replace(reduced_config(full), dtype="float32")
    model = build_model(cfg)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=30)

    def run(rms, slices):
        tr = ElasticTrainer(model, opt, data,
                            TrainerConfig(steps=20, model_ways=1,
                                          slices=2 if rms else slices,
                                          max_slices=slices,
                                          check_period=5, log_period=5),
                            rms=rms)
        tr.train()
        return [m["loss"] for m in tr.metrics], tr.resize_log

    base_losses, _ = run(None, 4)
    rms = ScriptedRMS({1: Decision(Action.EXPAND, 4)})
    el_losses, resizes = run(rms, 4)
    diffs = [abs(a - b) for a, b in zip(base_losses, el_losses)]
    print(json.dumps({"max_diff": max(diffs), "resizes": len(resizes)}))
    """)
    assert out["resizes"] == 1
    # resharding changes psum reduction topology -> float reassociation;
    # trajectories must agree to well under 1% of the loss scale (~7.6)
    assert out["max_diff"] < 0.05


@pytest.mark.slow
def test_compressed_allreduce_error_feedback_converges():
    """Single-shot int8 sync has bounded error; with error feedback the
    *running average* of synced gradients converges to the true mean —
    the property that preserves SGD convergence."""
    out = run_sub("""
    from repro.core import make_mesh
    from repro.optim.compression import compressed_psum_grads
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh(4, 1)
    key = jax.random.PRNGKey(0)
    g_all = jax.random.normal(key, (4, 64))   # per-slice gradients

    def body(g):
        e = jnp.zeros_like(g[0])
        acc = jnp.zeros_like(g[0])
        first_err = None
        for t in range(12):
            mean, errs = compressed_psum_grads(
                {"g": g[0]}, mesh, axes=("data",), errors={"g": e})
            e = errs["g"]
            acc = acc + mean["g"]
            if t == 0:
                first_err = mean["g"]
        return first_err[None], (acc / 12)[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=(P("data"), P("data")), check_vma=False)
    first, avg = fn(g_all)
    truth = np.asarray(g_all).mean(axis=0)
    rel1 = np.abs(np.asarray(first)[0] - truth).max() / \
        (np.abs(truth).max() + 1e-9)
    relN = np.abs(np.asarray(avg)[0] - truth).max() / \
        (np.abs(truth).max() + 1e-9)
    print(json.dumps({"rel_single": float(rel1), "rel_avg": float(relN)}))
    """)
    assert out["rel_single"] < 0.25          # bounded single-shot error
    assert out["rel_avg"] < out["rel_single"]  # EF drives the bias down
    assert out["rel_avg"] < 0.05


def test_launcher_elastic_job_shrinks_and_expands_under_a_rival():
    """The launcher's elastic job holds all 8 devices; a scripted rival
    makes the RMS shrink it, then the job expands back onto all of them."""
    out = run_sub("""
    from repro.launch.train import build
    from repro.runtime import scripted_rival
    trainer, rms = build("smollm-135m", reduced=True, seq_len=32,
                         global_batch=8, steps=6, slices=8, elastic=True,
                         check_period=2)
    state = trainer.train(on_step=scripted_rival(rms, submit_at=2,
                                                 finish_at=4))
    print(json.dumps({
        "resizes": [(r["action"], r["to"]) for r in trainer.resize_log],
        "spans": sorted({len(leaf.sharding.device_set)
                         for leaf in jax.tree.leaves(state)}),
        "losses": [m["loss"] for m in trainer.metrics]}))
    """)
    assert out["resizes"] == [["SHRINK", 4], ["EXPAND", 8]]
    assert out["spans"] == [8]
    assert len(out["losses"]) == 6


# A full ZeRO-1 TrainState of the reduced SmolLM on ``big`` slices, and its
# shardings on ``big`` and ``small`` slices of the same device prefix.
ZERO1_STATE = """
import sys
import jax._src.array as jax_array
from repro.core import crosses_host, make_mesh, plan_reshard, reshard
from repro.launch.train import build

big, small = {big}, {small}
trainer, _ = build("smollm-135m", reduced=True, seq_len=32, global_batch=8,
                   slices=big)
state = trainer.init_state(0)
lay = {{n: trainer._state_shardings(make_mesh(n, 1, devices=trainer.devices))
       for n in (big, small)}}

host_pulls = []        # each array JAX fetched to host memory to re-place
value = jax_array.ArrayImpl._value
jax_array.ArrayImpl._value = property(
    lambda self: host_pulls.append(1) or value.fget(self))

def pulls(fn, *args, **kw):
    host_pulls.clear()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, len(host_pulls)
"""


@pytest.mark.parametrize("big,small", [(4, 2), (8, 2)])
def test_zero1_state_reshard_is_bit_exact_and_stays_on_the_devices(big, small):
    """Resized big -> small -> big, the bridged reshard gives every leaf the
    shards ``jax.device_put`` gives it, bit for bit, on the same devices;
    the plain device_put sends each split moment through host memory, the
    bridged path none."""
    out = run_sub(ZERO1_STATE.format(big=big, small=small) + """
def same(a, b):
    return a.sharding == b.sharding and all(
        sa.device == sb.device and sa.index == sb.index
        and np.asarray(sa.data).tobytes() == np.asarray(sb.data).tobytes()
        for sa, sb in zip(a.addressable_shards, b.addressable_shards))

split = sum(any(ax is not None for ax in s.spec)
            for s in jax.tree.leaves(lay[small]["opt"]))
res = {"split_moments": split}
x = state
for step, b in enumerate((small, big)):
    plan = plan_reshard(x, lay[b])
    plain, plain_pulls = pulls(jax.device_put, x, lay[b])
    y, bridged_pulls = pulls(reshard, x, lay[b], plan=plan)
    res[step] = {
        "same": all(jax.tree.leaves(jax.tree.map(same, y, plain))),
        "plain_rule": sum(crosses_host(l.sharding, s, l.shape)
                           for l, s in zip(jax.tree.leaves(x),
                                           jax.tree.leaves(lay[b]))),
        "bridged": sum(p is not None for p in plan.bridges),
        "host_leaves": plan.host_leaves, "note": plan.note,
        "plain_pulls": plain_pulls, "bridged_pulls": bridged_pulls}
    x = y
res["back"] = all(jax.tree.leaves(jax.tree.map(
    lambda a, b: np.asarray(a).tobytes() == np.asarray(b).tobytes(),
    x, state)))
print(json.dumps(res))
""")
    assert out["split_moments"] > 0 and out["back"]
    for step in ("0", "1"):
        r = out[step]
        assert r["same"]
        assert r["plain_rule"] == r["plain_pulls"] == out["split_moments"]
        assert r["bridged"] == out["split_moments"]
        assert r["host_leaves"] == r["bridged_pulls"] == 0
        assert r["note"] == ""


def test_a_second_resize_of_a_pair_does_not_retrace_the_relayouts():
    out = run_sub(ZERO1_STATE.format(big=4, small=2) + """
traces = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: traces.append(1)
    if name == "/jax/core/compile/jaxpr_trace_duration" else None)

plans = {(4, 2): plan_reshard(state, lay[2])}
x = reshard(state, lay[2], plan=plans[(4, 2)])
plans[(2, 4)] = plan_reshard(x, lay[4])
x = jax.block_until_ready(reshard(x, lay[4], plan=plans[(2, 4)]))
first = len(traces)
for _ in range(2):
    x = reshard(x, lay[2], plan=plans[(4, 2)])
    x = jax.block_until_ready(reshard(x, lay[4], plan=plans[(2, 4)]))
again = len(traces) - first
x = reshard(x, lay[2])            # a plan made anew finds the same jit
x = jax.block_until_ready(reshard(x, lay[4]))
print(json.dumps({"first": first, "again": again,
                  "fresh_plan": len(traces) - first - again}))
""")
    assert out["first"] == 2           # one relayout each way
    assert out["again"] == 0 and out["fresh_plan"] == 0


def test_elastic_losses_are_the_same_bridged_and_plain():
    """An elastic job that shrinks 8 -> 4 and expands back computes the same
    losses, and ends on the same bits, whether its reshard bridges the
    moments or sends them through host memory."""
    out = run_sub("""
    import repro.runtime.trainer as trainer_mod
    from repro.launch.train import build
    from repro.runtime import scripted_rival

    def run():
        trainer, rms = build("smollm-135m", reduced=True, seq_len=32,
                             global_batch=8, steps=8, slices=8, elastic=True,
                             check_period=2)
        state = trainer.train(on_step=scripted_rival(rms, submit_at=2,
                                                     finish_at=4))
        return ([m["loss"] for m in trainer.metrics],
                [(r["action"], r["host_leaves"]) for r in trainer.resize_log],
                [np.asarray(x).tobytes() for x in jax.tree.leaves(state)])

    bridged = run()
    trainer_mod.reshard = lambda state, shardings, **kw: jax.device_put(
        state, shardings)
    plain = run()
    print(json.dumps({"losses": bridged[0] == plain[0],
                      "bits": bridged[2] == plain[2],
                      "resizes": bridged[1], "n": len(bridged[0])}))
    """)
    assert out["resizes"] == [["SHRINK", 0], ["EXPAND", 0]]
    assert out["n"] == 8 and out["losses"] and out["bits"]

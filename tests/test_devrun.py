"""repro.devrun — real multi-device rounds.

In-process tests cover what a 1-CPU pytest process can see: the
``devices:D`` topology grammar, the error on too few devices, the packed
wire format's bitwise pack→gather→unpack→sum equivalence with the
in-process reduction, and the trace-time wire accounting.  Everything
that needs real devices spawns a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the device
count is locked at first jax init): golden pinning, compressed-
collective HLO measurement, skip-branch structure, donation.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm as comm_lib
from repro import devrun
from repro.core import lag
from repro.engine import rounds as engine_rounds
from repro.engine.topology import DeviceWorkers, make_topology
from repro.fastpath.layout import FlatLayout

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


def _run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# Topology registry + too few devices (in-process, 1 CPU device)
# ---------------------------------------------------------------------------

def test_devices_topology_grammar():
    topo = make_topology("devices:8")
    assert isinstance(topo, DeviceWorkers)
    assert topo.name == "devices"
    assert topo.num_devices() == 8
    # the pytest process has 1 CPU device → the real plane is unavailable
    assert not topo.available()
    bare = make_topology("devices")
    assert bare.num_devices() == len(jax.devices())
    assert bare.num_devices(default=4) == 4
    with pytest.raises(ValueError, match="unit count"):
        make_topology("devices:0")
    with pytest.raises(ValueError, match="'@' suffix"):
        make_topology("devices:4@2")


def test_devices_mesh_shape_matches_unit_count():
    # buildable on this process only at its actual device count
    topo = make_topology(f"devices:{len(jax.devices())}")
    mesh = topo.device_mesh()
    assert mesh.axis_names == ("workers",)
    assert mesh.shape["workers"] == len(jax.devices())


def test_fallback_builders_match_sync_trainer(tiny_cfg):
    """There is no fallback: on a process without the D devices, both
    devrun builders (and so Experiment and launch.train) raise, naming D
    and the devices found, instead of quietly running one device."""
    from repro.dist import lag_trainer

    tcfg = lag_trainer.TrainerConfig(algo="lag-wk", num_workers=4, lr=0.05)
    topo = make_topology("devices:4")
    assert not topo.available(4)
    found = f"sees {len(jax.devices())}"
    with pytest.raises(RuntimeError, match=f"devices:4 needs 4 devices.*{found}"):
        devrun.init_device_state(jax.random.PRNGKey(0), tiny_cfg, tcfg,
                                 topology=topo)
    with pytest.raises(RuntimeError, match=f"devices:4 needs 4 devices.*{found}"):
        devrun.make_device_step(tiny_cfg, tcfg, topology=topo)


def test_make_device_step_rejects_foreign_topology(tiny_cfg):
    from repro.dist import lag_trainer
    tcfg = lag_trainer.TrainerConfig(algo="lag-wk", num_workers=2)
    with pytest.raises(ValueError, match="DeviceWorkers"):
        devrun.make_device_step(tiny_cfg, tcfg,
                                topology=make_topology("shards"))


@pytest.fixture(scope="module")
def tiny_cfg():
    from repro.configs import get_config
    return get_config("llama3.2-1b", num_layers=1, d_model=16, num_heads=2,
                      num_kv_heads=1, head_dim=8, d_ff=32, vocab_size=64)


# ---------------------------------------------------------------------------
# Wire format: pack → gather → unpack → sum ≡ the in-process reduction,
# bitwise (in-process over the stacked worker dim — the same arrays the
# device plane moves, minus the transport)
# ---------------------------------------------------------------------------

def _params_template():
    return {"w": jnp.zeros((37, 5), jnp.float32),
            "b": jnp.zeros((63,), jnp.float32),
            "s": jnp.zeros((), jnp.float32)}


def _policy_state(policy, params, W):
    z = lambda p: jnp.zeros((W,) + p.shape, p.dtype)
    grad0 = jax.tree_util.tree_map(z, params)
    theta0 = jax.tree_util.tree_map(z, params) \
        if policy.needs_theta_hat else None
    st = dict(policy.init_state(grad0, theta0))
    st.update(hist=lag.hist_init(10),
              L_m=jnp.full((W,), 2.0, jnp.float32))
    return st


@pytest.mark.parametrize("spec,hist_scale", [
    ("gd", 0.0),            # every worker uploads
    ("lag-wk", 0.0),        # all-upload round (rhs 0)
    ("lag-wk", 1e9),        # all-quiet round (absorbing slots only)
    ("laq@4", 0.0),
    ("laq@3", 0.0),
    ("laq@8", 1e9),
    ("laq@16", 0.0),
    ("cyc-laq@8", 0.0),     # mixed mask: exactly one worker uploads
])
def test_wire_sum_bitwise_equals_engine_reduction(spec, hist_scale):
    W = 4
    params = _params_template()
    policy = comm_lib.make_policy(spec, fastpath="off")
    lagcfg = lag.LAGConfig(num_workers=W, alpha=0.1, D=10, xi=0.1)
    key = jax.random.PRNGKey(7)
    grads = {k: jax.random.normal(jax.random.fold_in(key, i),
                                  (W,) + v.shape, v.dtype)
             for i, (k, v) in enumerate(sorted(params.items()))}
    st = _policy_state(policy, params, W)
    st["hist"] = st["hist"] + hist_scale
    layout = FlatLayout.for_tree(params)

    comm, delta, _, wire = engine_rounds.policy_rounds(
        policy, lagcfg, params, grads, st,
        step=jnp.asarray(1, jnp.int32), wire_layout=layout)
    ref = engine_rounds.sum_reduce(comm, delta)

    # the device plane's reduction: gathered wire arrays → unpack → sum
    # in worker order → unflatten.  Bitwise equal, including the packed
    # LAQ codes + transmitted quantizer steps.
    buf = policy.wire_unpack(layout, wire)
    got = layout.unflatten(jnp.sum(buf, axis=0), like=jnp.float32)
    if hist_scale:                      # all-quiet: everything exactly 0
        assert not bool(jnp.any(comm))
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(got[k]), err_msg=k)


def test_wire_slot_bytes_match_array_sizes():
    """Declared slot bytes are the literal nbytes of the packed arrays —
    the quantity the HLO gather measurement is predicted from."""
    W = 2
    params = _params_template()
    layout = FlatLayout.for_tree(params)
    lagcfg = lag.LAGConfig(num_workers=W, alpha=0.1, D=10, xi=0.1)
    for spec in ("gd", "lag-wk", "laq@3", "laq@4", "laq@8", "laq@16"):
        policy = comm_lib.make_policy(spec, fastpath="off")
        grads = jax.tree_util.tree_map(
            lambda p: jnp.ones((W,) + p.shape, p.dtype), params)
        st = _policy_state(policy, params, W)
        _, _, _, wire = engine_rounds.policy_rounds(
            policy, lagcfg, params, grads, st,
            step=jnp.asarray(0, jnp.int32), wire_layout=layout)
        slots = policy.wire_slot_bytes(layout)
        assert set(slots) == set(wire), spec
        for name, arr in wire.items():
            per_worker = arr.nbytes // W
            assert per_worker == slots[name], (spec, name)


# ---------------------------------------------------------------------------
# Trace-time wire accounting (framing ratios are exact constants)
# ---------------------------------------------------------------------------

def test_framing_ratio_pinned_on_ci_model():
    """The padding/width components of FRAMING_TOLERANCE, pinned exactly
    on the CI llama config: dense and b ∈ {4, 8, 16} pay only flat-buffer
    padding; b = 3 additionally pays the exact 4/3 width rounding."""
    from repro.configs import get_config
    from repro.dist import lag_trainer
    from repro.models import model

    cfg = get_config("llama3.2-1b").reduced(dtype="float32",
                                            param_dtype="float32")
    params = jax.eval_shape(lambda k: model.init(k, cfg),
                            jax.random.PRNGKey(0))
    pad = devrun.framing_ratio(comm_lib.make_policy("lag-wk"), params)
    assert 1.0 <= pad < 1.05                       # padding only
    for bits, extra in ((4, 1.0), (8, 1.0), (16, 1.0), (3, 4.0 / 3.0)):
        r = devrun.framing_ratio(comm_lib.make_policy(f"laq@{bits}"),
                                 params)
        # steps side-channel perturbs the ratio below the padding bound
        assert abs(r - pad * extra) < 0.01, (bits, r, pad * extra)
        assert r <= 1.0 + devrun.FRAMING_TOLERANCE, (bits, r)


def test_predicted_collective_bytes_formula():
    params = _params_template()
    policy = comm_lib.make_policy("laq@4", fastpath="off")
    pred = devrun.predicted_collective_bytes(policy, params, n_devices=8)
    layout = FlatLayout.for_tree(params)
    slot_total = sum(policy.wire_slot_bytes(layout).values())
    assert pred["slot_total"] == slot_total
    assert pred["gather_bytes"] == slot_total * 7          # ring (n−1)
    assert pred["total"] == pred["gather_bytes"] + pred["mask_bytes"] \
        + pred["loss_bytes"]


# ---------------------------------------------------------------------------
# Real multi-device execution (subprocesses, 8 forced host devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_devices_reproduces_lag_wk_golden():
    """Acceptance criterion: the device plane on 8 real (host) devices
    reproduces tests/golden/lag_wk_50step.json — the EXACT upload
    decisions of the sync run, losses to float tolerance (the per-device
    backward reassociates matmul reductions, a ≤ 1-ulp wiggle)."""
    gold = json.load(open(os.path.join(GOLDEN_DIR, "lag_wk_50step.json")))
    code = f"""
import json, jax, numpy as np
from repro.engine import Experiment
from repro.engine.topology import make_topology
assert len(jax.devices()) == 8
topo = make_topology("devices:4")
assert topo.available(4)
r = Experiment(model="llama3.2-1b", algo="lag-wk", steps=50, workers=4,
               lr=0.05, batch=8, seq=64, topology="devices:4").run()
print(json.dumps({{"losses": r.losses.tolist(),
                   "comm_this_round": r.comms_per_iter.tolist(),
                   "comm_per_worker": r.uploads_per_worker.tolist(),
                   "comm_total": int(r.total_comms),
                   "topology": r.topology}}))
"""
    got = json.loads(_run_py(code).strip().splitlines()[-1])
    assert got["topology"] == "devices"
    assert got["comm_this_round"] == gold["comm_this_round"]
    assert got["comm_per_worker"] == gold["comm_per_worker"]
    assert got["comm_total"] == gold["comm_total"]
    np.testing.assert_allclose(got["losses"], gold["losses"], rtol=1e-4)


@pytest.mark.slow
def test_devices8_matches_vmapped_shards():
    """devices:8 (one worker per device) vs the in-process 8-worker vmap:
    identical upload decisions, float-close losses, LAQ payloads moving
    as packed codes the whole way."""
    code = """
import jax, numpy as np
from repro.configs import get_config
from repro.data import TokenStream, make_heterogeneous_inputs
from repro.dist.lag_trainer import TrainerConfig, init_state, make_train_step
from repro import devrun
from repro.engine.topology import make_topology

cfg = get_config("llama3.2-1b").reduced(dtype="float32", param_dtype="float32")
tcfg = TrainerConfig(algo="laq", num_workers=8, lr=0.05, laq_bits=4)
stream = TokenStream(vocab=cfg.vocab_size, seed=0)
batch = make_heterogeneous_inputs(cfg, stream, 0, 8, 8, 64)

s_ref = init_state(jax.random.PRNGKey(0), cfg, tcfg)
step_ref = jax.jit(make_train_step(cfg, tcfg))
topo = make_topology("devices:8")
s_dev = devrun.init_device_state(jax.random.PRNGKey(0), cfg, tcfg,
                                 topology=topo)
step_dev = devrun.jit_device_step(cfg, tcfg, topology=topo)
for k in range(6):
    s_ref, m_ref = step_ref(s_ref, batch)
    s_dev, m_dev = step_dev(s_dev, batch)
    assert (np.asarray(m_ref["comm_mask"])
            == np.asarray(m_dev["comm_mask"])).all(), k
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_dev["loss"]),
                               rtol=1e-5)
assert int(jax.device_get(s_dev["lag"]["comm_total"])) \
    == int(jax.device_get(s_ref["lag"]["comm_total"]))
print("PARITY OK")
"""
    assert "PARITY OK" in _run_py(code)


@pytest.mark.slow
def test_measured_wire_bytes_match_prediction():
    """Close the loop on the REAL compiled 8-device HLO: measured
    collective bytes (hlo_analysis ring costs) ≈ the wire-format
    prediction, for both the dense and the LAQ-compressed plane — and
    LAQ's measured traffic is genuinely ~8× smaller at b = 4."""
    code = """
import jax
from repro.configs import get_config
from repro.data import TokenStream, make_heterogeneous_inputs
from repro.dist.lag_trainer import TrainerConfig
from repro import devrun
from repro.engine.topology import make_topology

cfg = get_config("llama3.2-1b").reduced(dtype="float32", param_dtype="float32")
stream = TokenStream(vocab=cfg.vocab_size, seed=0)
batch = make_heterogeneous_inputs(cfg, stream, 0, 8, 8, 64)
measured = {}
for algo in ("lag-wk", "laq"):
    tcfg = TrainerConfig(algo=algo, num_workers=8, laq_bits=4)
    topo = make_topology("devices:8")
    policy = tcfg.comm_policy()
    state = devrun.init_device_state(jax.random.PRNGKey(0), cfg, tcfg,
                                     policy=policy, topology=topo)
    step = devrun.jit_device_step(cfg, tcfg, policy=policy, topology=topo)
    hlo = devrun.compiled_hlo(step, state, batch)
    acct = devrun.assert_wire_accounting(hlo, policy, state["params"], 8)
    measured[algo] = acct["measured_total_bytes"]
    print(algo, "rel_err", round(acct["gather_rel_err"], 4),
          "framing", round(acct["framing_ratio"], 4))
ratio = measured["lag-wk"] / measured["laq"]
assert 7.0 < ratio < 9.0, ratio
print("WIRE OK", round(ratio, 2))
"""
    out = _run_py(code)
    assert "WIRE OK" in out


@pytest.mark.slow
def test_payload_gather_sits_inside_conditional():
    """Structural proof of the lazy skip at device scale: the wire
    gather lives in an HLO conditional, so an all-quiet round moves only
    the trigger mask (the pod-LAG move, now on real devices)."""
    code = """
import jax
from repro.configs import get_config
from repro.data import TokenStream, make_heterogeneous_inputs
from repro.dist.lag_trainer import TrainerConfig
from repro import devrun
from repro.engine.topology import make_topology

cfg = get_config("llama3.2-1b").reduced(dtype="float32", param_dtype="float32")
tcfg = TrainerConfig(algo="laq", num_workers=8, laq_bits=4)
topo = make_topology("devices:8")
state = devrun.init_device_state(jax.random.PRNGKey(0), cfg, tcfg,
                                 topology=topo)
stream = TokenStream(vocab=cfg.vocab_size, seed=0)
batch = make_heterogeneous_inputs(cfg, stream, 0, 8, 8, 64)
step = devrun.jit_device_step(cfg, tcfg, topology=topo)
txt = devrun.compiled_hlo(step, state, batch)
assert "conditional" in txt, "no conditional in HLO"
assert "all-gather" in txt, "no all-gather in HLO"
# the u8 packed-code gather exists (LAQ wire, not dense f32)
assert any("u8[" in l and "all-gather" in l for l in txt.splitlines()), \\
    "no uint8 all-gather: LAQ payload is not crossing packed"
print("COND OK")
"""
    assert "COND OK" in _run_py(code)


@pytest.mark.slow
def test_device_step_donates_round_state():
    """donate_argnums=(0,) actually consumes the previous round state:
    the donated param buffers are deleted after dispatch."""
    code = """
import jax
from repro.configs import get_config
from repro.data import TokenStream, make_heterogeneous_inputs
from repro.dist.lag_trainer import TrainerConfig
from repro import devrun
from repro.engine.topology import make_topology

cfg = get_config("llama3.2-1b").reduced(dtype="float32", param_dtype="float32")
tcfg = TrainerConfig(algo="lag-wk", num_workers=8)
topo = make_topology("devices:8")
state = devrun.init_device_state(jax.random.PRNGKey(0), cfg, tcfg,
                                 topology=topo)
stream = TokenStream(vocab=cfg.vocab_size, seed=0)
batch = make_heterogeneous_inputs(cfg, stream, 0, 8, 8, 64)
step = devrun.jit_device_step(cfg, tcfg, topology=topo)
leaf0 = jax.tree_util.tree_leaves(state["params"])[0]
state2, m = step(state, batch)
state3, m = step(state2, batch)
assert leaf0.is_deleted(), "input round state was not donated"
assert not jax.tree_util.tree_leaves(state3["params"])[0].is_deleted()
print("DONATE OK")
"""
    assert "DONATE OK" in _run_py(code)


@pytest.mark.slow
def test_run_rounds_loop():
    """The dispatch-ahead driver: N rounds, metrics fetched once."""
    code = """
import jax
from repro.configs import get_config
from repro.data import TokenStream, make_heterogeneous_inputs
from repro.dist.lag_trainer import TrainerConfig
from repro import devrun
from repro.engine.topology import make_topology

cfg = get_config("llama3.2-1b").reduced(dtype="float32", param_dtype="float32")
tcfg = TrainerConfig(algo="laq", num_workers=8, laq_bits=4)
topo = make_topology("devices:8")
state = devrun.init_device_state(jax.random.PRNGKey(0), cfg, tcfg,
                                 topology=topo)
stream = TokenStream(vocab=cfg.vocab_size, seed=0)
batch = make_heterogeneous_inputs(cfg, stream, 0, 8, 8, 64)
step = devrun.jit_device_step(cfg, tcfg, topology=topo)
state, ms = devrun.run_rounds(step, state, [batch] * 5)
assert len(ms) == 5
losses = [float(m["loss"]) for m in ms]
assert losses[-1] < losses[0], losses
print("LOOP OK")
"""
    assert "LOOP OK" in _run_py(code)

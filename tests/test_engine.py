"""The ``repro.engine`` redesign: golden equivalence through the engine
path, bit-exact ScheduledPolicy vs the recorded comm_override
trajectories, and the full (policy × server-optimizer × topology) smoke
matrix."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm, engine
from repro.core import convex, simulate
from repro.engine import Experiment

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


# ---------------------------------------------------------------------------
# The registered axes (kept in sync with the registries programmatically,
# so a new registration automatically joins the matrix)
# ---------------------------------------------------------------------------

# distinct policy classes (adam/lag-adam are registry aliases of gd/lag-wk
# triggers — the server axis covers them) + one schedule per registered
# prefix (cyc-/num-), including a scheduled-payload composition
POLICY_SPECS = ("gd", "lag-wk", "lag-ps", "laq@3", "lasg-wk",
                "cyc-iag", "num-iag", "cyc-laq@3")
SERVER_SPECS = ("sgd", "momentum@0.9", "adam", "prox-l1@0.001")
# devices:1 runs repro.devrun's real shard_map plane on the one CPU
# device of the test process (devices:D with fewer devices raises);
# tests/test_devrun.py covers D > 1 in subprocesses with forced host
# devices
DEEP_TOPOLOGY_SPECS = ("shards", "pods:2", "async:2@1", "devices:1",
                       "fleet:64@8", "graph:2@complete")


def _deep_matrix_dims(topology):
    """(batch, mask_width) for one deep-matrix cell: fleet splits the
    batch over its 8 cohort slots and reports an (N,)-wide population
    mask; graph:2@complete reports a mask per DIRECTED edge (E = 2);
    devices:1 runs its one worker; everything else runs 2 workers."""
    if topology.startswith("fleet"):
        return 8, 64
    if topology.startswith("devices"):
        return 4, 1
    return 4, 2


def test_matrix_covers_every_registration():
    """If someone registers a new policy/server/topology, the smoke matrix
    must grow with it — this test is the tripwire."""
    alias = {"adam", "lag-adam"}
    bases = {s.split("@")[0] for s in POLICY_SPECS}
    for name in comm.POLICIES:
        assert name in bases or name in alias, \
            f"policy {name!r} missing from POLICY_SPECS"
    for prefix in comm.SCHEDULES:
        assert any(s.startswith(prefix + "-") for s in POLICY_SPECS), \
            f"schedule prefix {prefix!r} missing from POLICY_SPECS"
    for name in engine.SERVERS:
        assert any(s.split("@")[0] == name for s in SERVER_SPECS), \
            f"server {name!r} missing from SERVER_SPECS"
    for name in engine.TOPOLOGIES:
        assert name == "sim" or any(
            s.split(":")[0] == name for s in DEEP_TOPOLOGY_SPECS), \
            f"topology {name!r} missing from DEEP_TOPOLOGY_SPECS"


def test_every_registered_policy_declares_fastpath_route():
    """Fast-path tripwire, registry half: every registered policy class
    must explicitly implement ``fast_precompute`` — route its reductions
    through the batched plane (repro.fastpath) or visibly ``return
    None`` — never inherit the base method, whose raise is what makes a
    silent bypass impossible.  (The runtime half is the forced-on matrix
    below: an unimplemented policy fails there too.)"""
    for name, cls in comm.POLICIES.items():
        assert cls.fast_precompute is not comm.CommPolicy.fast_precompute, \
            f"policy {name!r} ({cls.__name__}) inherits the base " \
            f"fast_precompute — implement it (or explicitly return None)"
    assert comm.ScheduledPolicy.fast_precompute \
        is not comm.CommPolicy.fast_precompute


# ---------------------------------------------------------------------------
# Bit-exact equivalence: ScheduledPolicy vs the recorded comm_override
# trajectories (satellite: the override path is deleted)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def iag_golden():
    return json.load(open(os.path.join(GOLDEN_DIR, "iag_sched_80step.json")))


@pytest.mark.parametrize("algo", ["cyc-iag", "num-iag"])
def test_scheduled_policy_reproduces_comm_override_golden(iag_golden, algo):
    """cyc-IAG/num-IAG expressed as ``ScheduledPolicy`` reproduce the
    pre-engine ``comm_override`` trajectories BIT-EXACTLY: same seed ⇒
    same comm_mask, same losses (the recorded golden was generated by the
    old driver-side mask path before its deletion)."""
    g = iag_golden
    spec = g["problem"]
    prob = convex.synthetic(spec["kind"], num_workers=spec["num_workers"],
                            n_per=spec["n_per"], d=spec["d"],
                            seed=spec["seed"])
    r = simulate.run(prob, algo, K=g["K"], seed=g["seed"])
    want = g["algos"][algo]
    np.testing.assert_array_equal(np.asarray(r.comm_mask, int),
                                  np.asarray(want["comm_mask"]))
    # float32 → json double round-trips exactly: equality, not allclose
    np.testing.assert_array_equal(np.asarray(r.losses, np.float64),
                                  np.asarray(want["losses"]))
    # the schedule really is one-upload-per-round
    assert (r.comms_per_iter == 1).all()


def test_policy_override_keeps_algo_schedule(tiny_problem):
    """Pre-engine, ``policy=`` only swapped the payload — the schedule
    came from the algo.  The shim preserves that: a custom policy under a
    scheduled algo still uploads one worker per round."""
    r = simulate.run(tiny_problem, "cyc-iag", K=6, policy=comm.GDPolicy())
    assert (r.comms_per_iter == 1).all()


def test_run_round_has_no_override_side_door():
    """The override path is gone: run_round's signature is policy-only."""
    import inspect
    params = inspect.signature(comm.run_round).parameters
    assert "comm_override" not in params


# ---------------------------------------------------------------------------
# Golden: the 50-step lag-wk deep trajectory through the Experiment door
# ---------------------------------------------------------------------------

def test_experiment_reproduces_lag_wk_golden():
    """tests/golden/lag_wk_50step.json passes unchanged through the
    engine path: same losses, same per-worker upload counts (acceptance
    criterion — the Experiment front door IS the old trainer loop)."""
    gold = json.load(open(os.path.join(GOLDEN_DIR, "lag_wk_50step.json")))
    r = Experiment(model="llama3.2-1b", algo="lag-wk", steps=50,
                   workers=4, lr=0.05, batch=8, seq=64).run()
    np.testing.assert_allclose(r.losses, gold["losses"], rtol=1e-4)
    assert r.comms_per_iter.tolist() == gold["comm_this_round"]
    assert r.uploads_per_worker.tolist() == gold["comm_per_worker"]
    assert r.total_comms == gold["comm_total"]
    assert r.server == "sgd" and r.topology == "shards"


# ---------------------------------------------------------------------------
# Smoke matrix: every policy × server × topology
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_problem():
    return convex.synthetic("linreg", num_workers=3, n_per=6, d=4, seed=1)


@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import get_config
    return get_config("llama3.2-1b", num_layers=1, d_model=16, num_heads=2,
                      num_kv_heads=1, head_dim=8, d_ff=32, vocab_size=64)


@pytest.mark.parametrize("server", SERVER_SPECS)
@pytest.mark.parametrize("algo", POLICY_SPECS)
def test_matrix_convex(tiny_problem, algo, server):
    r = Experiment(problem=tiny_problem, algo=algo, server=server,
                   steps=3, opt_loss=0.0).run()
    assert np.isfinite(r.losses).all()
    assert r.comm_mask.shape == (3, 3)
    assert r.server == server.split("@")[0]
    assert r.bytes_per_upload > 0


@pytest.mark.parametrize("topology", DEEP_TOPOLOGY_SPECS)
@pytest.mark.parametrize("server", SERVER_SPECS)
@pytest.mark.parametrize("algo", POLICY_SPECS)
def test_matrix_deep(tiny_model, algo, server, topology):
    batch, width = _deep_matrix_dims(topology)
    r = Experiment(model=tiny_model, algo=algo, server=server,
                   topology=topology, steps=2, workers=2, batch=batch,
                   seq=8).run()
    assert np.isfinite(r.losses).all()
    assert r.comm_mask.shape == (2, width)
    assert r.topology == topology.split(":")[0]
    if topology.startswith("pods"):
        assert "rounds_skipped" in r.extras
    if algo.startswith(("cyc-", "num-")):
        assert (r.comms_per_iter <= 1).all()


# ---------------------------------------------------------------------------
# Smoke matrix, fast path FORCED ON (fastpath="on", interpret-mode Pallas):
# the runtime half of the fast-path tripwire — every registered policy ×
# every deep topology must run THROUGH the batched plane (a policy whose
# fast_precompute is missing raises NotImplementedError here, so new
# registrations cannot silently bypass it), and the upload decisions must
# match the oracle path exactly.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", POLICY_SPECS)
def test_matrix_convex_fastpath_forced(tiny_problem, algo):
    r0 = Experiment(problem=tiny_problem, algo=algo, steps=3,
                    opt_loss=0.0).run()
    r1 = Experiment(problem=tiny_problem, algo=algo, steps=3, opt_loss=0.0,
                    fastpath="on").run()
    assert np.isfinite(r1.losses).all()
    np.testing.assert_array_equal(np.asarray(r1.comm_mask),
                                  np.asarray(r0.comm_mask))


@pytest.mark.parametrize("topology", DEEP_TOPOLOGY_SPECS)
@pytest.mark.parametrize("algo", POLICY_SPECS)
def test_matrix_deep_fastpath_forced(tiny_model, algo, topology):
    batch, width = _deep_matrix_dims(topology)
    r = Experiment(model=tiny_model, algo=algo, topology=topology,
                   steps=2, workers=2, batch=batch, seq=8,
                   fastpath="on").run()
    assert np.isfinite(r.losses).all()
    assert r.comm_mask.shape == (2, width)


# ---------------------------------------------------------------------------
# The two newly-expressible scenarios (EXPERIMENTS.md §Engine scenarios)
# ---------------------------------------------------------------------------

def test_new_scenario_deep_prox_l1_descends():
    """Proximal LAG on the deep trainer — previously convex-only.  The
    composite objective descends and the reported loss includes the l1
    term."""
    r = Experiment(model="llama3.2-1b", algo="lag-wk",
                   server="prox-l1@1e-4", steps=12, workers=2, batch=4,
                   seq=32).run()
    assert np.isfinite(r.losses).all()
    assert r.losses[-1] < r.losses[0]
    assert r.server == "prox-l1"


def test_new_scenario_convex_lag_adam_runs(tiny_problem):
    """LAG-Adam in the convex sim — previously trainer-only.  Smoke-level
    by design: the trigger's α-coupling pathology under preconditioning
    is documented (EXPERIMENTS.md), not asserted away."""
    r = simulate.run(tiny_problem, "lag-adam", K=20, opt_loss=0.0)
    assert np.isfinite(r.losses).all()
    assert r.server == "adam"
    r2 = simulate.run(tiny_problem, "lag-wk", K=20, server="adam",
                      opt_loss=0.0)
    np.testing.assert_allclose(r.losses, r2.losses, rtol=1e-6)


# ---------------------------------------------------------------------------
# Engine internals: server init/apply contracts, report accessors
# ---------------------------------------------------------------------------

def test_sgd_server_matches_server_update():
    """The sgd server step is bit-exact with lag.server_update's
    parameter math (the refactor's equivalence anchor)."""
    from repro.core import lag
    cfg = lag.LAGConfig(num_workers=3, alpha=0.07, D=4, xi=0.25)
    theta = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    nabla = {"w": jnp.asarray([0.5, 0.25, -1.0])}
    want, _, _ = lag.server_update(theta, {"w": jnp.zeros(3)}, nabla,
                                   lag.hist_init(4), cfg)
    got, opt = engine.SGDServer().apply(theta, None, nabla,
                                        jnp.zeros((), jnp.int32), cfg)
    assert opt is None
    np.testing.assert_array_equal(np.asarray(want["w"]),
                                  np.asarray(got["w"]))


def test_prox_server_soft_thresholds_and_reports_composite():
    from repro.core import lag
    cfg = lag.LAGConfig(num_workers=1, alpha=1.0, D=1, xi=0.1)
    srv = engine.ProxL1Server(l1=0.5)
    theta = {"w": jnp.asarray([2.0, -0.2, 0.0])}
    got, _ = srv.apply(theta, None, {"w": jnp.zeros(3)},
                       jnp.zeros((), jnp.int32), cfg)
    # prox at α·λ = 0.5: 2.0 → 1.5, −0.2 → 0, 0 → 0
    np.testing.assert_allclose(np.asarray(got["w"]), [1.5, 0.0, 0.0])
    np.testing.assert_allclose(
        float(srv.composite_loss(jnp.asarray(1.0), theta)),
        1.0 + 0.5 * 2.2)


def test_stateless_servers_keep_state_layout():
    """sgd/prox-l1 init to None ⇒ the trainer state has no 'opt' entry —
    the pre-engine layout, so old checkpoints restore unchanged."""
    from repro.configs import get_config
    from repro.dist import TrainerConfig, init_state
    cfg = get_config("llama3.2-1b", num_layers=1, d_model=16, num_heads=2,
                     num_kv_heads=1, head_dim=8, d_ff=32, vocab_size=64)
    s = init_state(jax.random.PRNGKey(0), cfg,
                   TrainerConfig(algo="lag-wk", num_workers=2))
    assert "opt" not in s
    s = init_state(jax.random.PRNGKey(0), cfg,
                   TrainerConfig(algo="adam", num_workers=2))
    assert set(s["opt"]) == {"mu", "nu"}
    s = init_state(jax.random.PRNGKey(0), cfg,
                   TrainerConfig(algo="lag-wk", num_workers=2,
                                 server="prox-l1@0.01"))
    assert "opt" not in s


def test_report_wire_accessors_unify_convex_and_deep(tiny_problem,
                                                     tiny_model):
    rc = Experiment(problem=tiny_problem, algo="laq@4", steps=5,
                    opt_loss=0.0).run()
    rd = Experiment(model=tiny_model, algo="laq@4", steps=2, workers=2,
                    batch=4, seq=8).run()
    for r in (rc, rd):
        assert r.wire_bytes == r.total_comms * r.bytes_per_upload
        assert r.cum_wire_bytes.shape == r.losses.shape
        row = r.summary(eps=np.inf)
        assert row["algo"] == "laq@4" and row["comms_to_eps"] is not None


def test_conflicting_server_specs_raise(tiny_problem):
    with pytest.raises(ValueError, match="conflicting server specs"):
        Experiment(problem=tiny_problem, algo="lag-wk", l1=5.0,
                   server="adam").run()
    with pytest.raises(ValueError, match="conflicting server specs"):
        Experiment(problem=tiny_problem, algo="lag-adam", l1=5.0).run()


def test_fresh_batches_per_step(tiny_model):
    """fixed_batch=False feeds the step index to the data pipeline —
    the minibatch regime the stochastic policies are built for."""
    fixed = Experiment(model=tiny_model, algo="gd", steps=4, workers=2,
                       batch=4, seq=8).run()
    fresh = Experiment(model=tiny_model, algo="gd", steps=4, workers=2,
                       batch=4, seq=8, fixed_batch=False).run()
    assert np.isfinite(fresh.losses).all()
    # same step-0 batch, different data afterwards
    assert fresh.losses[0] == fixed.losses[0]
    assert not np.array_equal(fresh.losses[1:], fixed.losses[1:])


# ---------------------------------------------------------------------------
# Spec-parse fuzz: every registry factory must reject junk specs with an
# actionable ValueError that NAMES the valid grammar (never a bare
# int()-cast traceback or a KeyError)
# ---------------------------------------------------------------------------

# (junk spec, regex the error must match — a grammar hint, not just "bad")
TOPOLOGY_JUNK = (
    ("", r"non-empty string"),
    ("warp", r"known:.*shards"),
    ("fleet", r"fleet:<population>@<cohort>"),
    ("fleet:", r"fleet:<population>@<cohort>"),
    ("fleet:@", r"integer population.*fleet:<population>@<cohort>"),
    ("fleet:64", r"fleet:<population>@<cohort>"),
    ("fleet:64@", r"integer cohort.*fleet:<population>@<cohort>"),
    ("fleet:x@8", r"integer population"),
    ("fleet:64@y", r"integer cohort"),
    ("fleet:0@5", r"population must be >= 1"),
    ("fleet:8@0", r"cohort must be in \[1, population"),
    ("fleet:8@9", r"cohort must be in \[1, population"),
    ("pods:", r"integer unit count.*pods:2"),
    ("pods:0", r"unit count must be >= 1"),
    ("shards:x", r"integer unit count"),
    ("pods:2@1", r"only 'async', 'fleet' and 'graph'"),
    ("async:4@x", r"integer staleness.*async:4@2"),
    ("async:4@-1", r"staleness must be >= 0"),
    # graph:<nodes>@<family> — every reject names the grammar
    ("graph", r"BOTH a node count and a family.*graph:<nodes>@<family>"),
    ("graph:8", r"BOTH a node count and a family.*graph:<nodes>@<family>"),
    ("graph:x@ring", r"not an integer node count.*graph:<nodes>@<family>"),
    ("graph:1@ring", r"needs >= 2 nodes.*graph:<nodes>@<family>"),
    ("graph:8@warp", r"unknown graph family.*graph:<nodes>@<family>"),
    ("graph:8@ring:3", r"'ring' takes no argument"),
    ("graph:8@torus:3x3", r"covers 9 nodes but the spec names 8"),
    ("graph:6@torus:x2", r"torus takes ':RxC'"),
    ("graph:4@torus:1x4", r"torus sides must both be >= 2"),
    ("graph:8@expander:0", r"expander degree must satisfy 2 <= d < nodes"),
    ("graph:8@expander:9", r"expander degree must satisfy 2 <= d < nodes"),
    ("graph:8@expander:z", r"not an integer expander degree"),
    ("graph:5@expander:3", r"3-regular graph on 5 nodes does not exist"),
    ("graph:8@smallworld:4", r"smallworld takes ':k@p'"),
    ("graph:8@smallworld:3@0.1", r"smallworld k must be even"),
    ("graph:8@smallworld:4@1.5", r"probability must be in \[0, 1\]"),
    ("graph:8@smallworld:4@x", r"not a rewiring probability"),
)

POLICY_JUNK = (
    ("", r"non-empty string"),
    ("warp", r"known algos:.*lag-wk"),
    ("laq@", r"integer bit width.*laq@8"),
    ("laq@x", r"integer bit width.*laq@8"),
    ("laq@0", r"bits must be in \[2, 16\]"),
    ("lag-wk@2", r"only 'laq' takes"),
    ("cyc-", r"known algos"),
    ("@3", r"known algos"),
)

CLUSTER_JUNK = (
    ("", r"non-empty string"),
    ("warp:4@1ms/1Gbps", r"unknown cluster profile.*<profile>"),
    ("uniform:", r"integer worker count"),
    ("uniform:0@1ms/1Gbps", r"worker count must be >= 1"),
    ("uniform@1ms/1Gbps", r"omits the worker count"),
    ("uniform:4@1ms", r"<latency>/<bandwidth>"),
    ("uniform:4@fast/1Gbps", r"not a latency.*10ms"),
    ("uniform:4@1ms/big", r"not a bandwidth.*Gbps"),
    ("fleet:4@1ms/", r"not a bandwidth"),
)


@pytest.mark.parametrize("spec,msg", TOPOLOGY_JUNK)
def test_fuzz_make_topology_rejects_junk_actionably(spec, msg):
    with pytest.raises(ValueError, match=msg):
        engine.make_topology(spec)


@pytest.mark.parametrize("spec,msg", POLICY_JUNK)
def test_fuzz_make_policy_rejects_junk_actionably(spec, msg):
    with pytest.raises(ValueError, match=msg):
        comm.make_policy(spec)


@pytest.mark.parametrize("spec,msg", CLUSTER_JUNK)
def test_fuzz_make_cluster_rejects_junk_actionably(spec, msg):
    from repro.netsim import make_cluster
    with pytest.raises(ValueError, match=msg):
        make_cluster(spec)


def test_launcher_topology_help_covers_registry():
    """Tripwire for stale launcher docs: the ``--topology`` help string in
    ``repro.launch.train`` must NAME every registered topology family
    (except the convex-only 'sim') — registering a new backend without
    updating the user-facing help fails here, the way 'fleet'/'devices'
    once shipped while the help still listed three families."""
    from repro.launch.train import build_argparser
    parser = build_argparser()
    action = next(a for a in parser._actions
                  if "--topology" in a.option_strings)
    for name in engine.TOPOLOGIES:
        if name == "sim":
            continue
        assert name in action.help, \
            f"registered topology {name!r} missing from the --topology " \
            f"help in repro.launch.train"


def test_experiment_workload_validation(tiny_problem, tiny_model):
    with pytest.raises(ValueError, match="exactly one"):
        Experiment().run()
    with pytest.raises(ValueError, match="exactly one"):
        Experiment(problem=tiny_problem, model=tiny_model).run()
    with pytest.raises(ValueError, match="sim"):
        Experiment(problem=tiny_problem, topology="pods:2").run()
    with pytest.raises(ValueError, match="'shards' or 'pods:N'"):
        Experiment(model=tiny_model, topology="sim").run()

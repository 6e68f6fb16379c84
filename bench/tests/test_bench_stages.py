"""Device time per stage of the round (bench/stages.py): the stage of an
op name, the op names of a compiled module, a CPU trace of a tiny cell's
step, and the step compiled for a described TPU v5e."""
import os
import re
import sys
import time
import types

import pytest

from _bench import BENCH, TINY, run, tiny_cell

stages = run._module(BENCH / "stages.py")
trace = run._module(BENCH / "trace.py")

G = "jit(train_step)/lag.grad/"


@pytest.mark.parametrize("op_name,want", [
    (G + "vmap(jvp())/bsd,dhk->bshk/dot_general", "forward"),
    (G + "vmap(transpose(jvp()))/mul", "backward"),
    # remat's recomputed forward runs inside the VJP
    (G + "vmap(transpose(jvp()))/while/body/closed_call/checkpoint/"
         "rematted_computation/mul", "backward"),
    ("jit(train_step)/lag.encode/lag.layout/concatenate", "layout"),
    ("jit(train_step)/vmap(lag.encode)/lag.layout/pad", "layout"),
    ("jit(train_step)/vmap(lag.encode)/sub", "encode"),
    ("jit(train_step)/lag.fold/lag.layout/slice", "layout"),
    ("jit(train_step)/lag.gather/cond/branch_1_fun/lag.wire/and", "wire"),
    ("jit(train_step)/lag.gather/cond/branch_1_fun/all_gather", "gather"),
    ("jit(train_step)/lag.server/add", "server"),
    ("jit(train_step)/mul", "unscoped"),
    ("jit(train_step)/flag.grad/mul", "unscoped"),
    ("state['params']['embed']", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_stage_of_an_op_name(op_name, want):
    assert stages.stage(op_name) == want


def test_the_stage_names_are_the_programs():
    from repro.engine import rounds
    assert tuple(f"lag.{s}" for s in stages.SCOPES) == rounds.STAGES
    with pytest.raises(ValueError, match="not one of the round's stages"):
        rounds.stage("backward")


def test_op_names_cover_the_branches_of_a_cond():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("lag.gather"):
            def send(x):
                with jax.named_scope("lag.wire"):
                    return jnp.cos(x) * 3.0
            return jax.lax.cond(jnp.sum(x) > 0, send, jnp.sin, x)

    text = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
    names = stages.op_names(text)
    entry = text[text.index("\nENTRY"):]
    wire = [n for n, v in names.items() if stages.stage(v) == "wire"]
    assert wire and all(f"%{n} " not in entry for n in wire)
    assert any(stages.stage(v) == "gather" for v in names.values())
    assert stages.scoped(names.values())
    assert not stages.scoped(["jit(f)/mul", ""])


def _traced_steps(cell, seed, steps, tmp_path):
    """The cell's step compiled and traced on the CPU over ``steps``
    steps inside a ``bench.window`` span: (trace, the compiled text)."""
    import jax
    st = run.start(cell, seed)
    state = st.state
    with st.prog.context():
        text = st.prog.step.lower(state, st.feed(0)).compile().as_text()
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation("bench.window"):
            for k in range(steps):
                state, out = st.call(state, st.feed(k))
            jax.block_until_ready((state, out))
            time.sleep(0.01)
        jax.profiler.stop_trace()
    return trace.load(trace.find_xplane(str(tmp_path)), cpu=True), text


@pytest.fixture(scope="module")
def tiny_trace(tmp_path_factory):
    cell = tiny_cell("granite-8b-l1v8.shards2.lag-wk")
    seed, steps = 2 ** 33 + 7, 2
    t, text = _traced_steps(cell, seed, steps, tmp_path_factory.mktemp("tr"))
    ctx = types.SimpleNamespace(trace=t, window=types.SimpleNamespace(
        steps=steps))
    return cell, seed, ctx, text


def test_a_cpu_trace_of_the_tiny_step_splits_into_stages(tiny_trace):
    _, _, ctx, text = tiny_trace
    t, steps, found = ctx.trace, ctx.window.steps, stages.instructions(text)
    # every op the trace ran is an instruction of the compiled module
    ran = {stages.instruction(e) for c in t.ops for e in t.in_window(c)}
    assert ran and ran <= set(found)
    ms = {s: stages.stage_ms(ctx, s, found) for s in stages.STAGES}
    for s in ("forward", "backward", "encode", "fold", "server"):
        assert ms[s] > 0.0, (s, ms)
    total = sum(e.self_time for c in t.ops for e in t.in_window(c)) \
        / len(t.ops)
    assert sum(ms.values()) == pytest.approx(1e-6 * total / steps,
                                             rel=1e-9)
    # a program without the scopes reads nothing
    bare = {n: (op, re.sub(r"lag\.\w+/?", "", v))
            for n, (op, v) in found.items()}
    assert stages.stage_ms(ctx, "forward", bare) is None


def test_the_stages_are_read_from_the_step_compiled_again(tiny_trace,
                                                          monkeypatch):
    """Without names at hand, a reader compiles the step of the run's
    own cell and seed again: the window's instructions, op names and
    all."""
    cell, seed, ctx, text = tiny_trace
    found = stages.instructions(text)
    # the same instructions; only the source locations of the debug
    # tables differ, as the call that lowered it did
    assert stages.instructions(stages.compiled_step(cell, seed)) == found
    monkeypatch.setattr(sys, "argv", ["bench/run.py", "--workload",
                                      cell.name, "--seed", str(seed),
                                      "--seconds", "20", "--trace", "1"])
    assert stages.run_args() == (cell.name, seed)
    stages.step_instructions.cache_clear()
    monkeypatch.setattr(run, "load_cell", lambda name: cell)
    assert stages.stage_ms(ctx, "backward") == stages.stage_ms(
        ctx, "backward", found)
    stages.step_instructions.cache_clear()


def test_names_of_another_program_read_no_stage(capsys):
    """An op whose opcode is not that of the step's instruction of its
    name: the names belong to another compile, and nothing is read."""
    ev = trace.Event("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), "
                     "kind=kLoop, calls=%fused_computation.3", 0.0, 5.0, {})
    ev.self_time = 5.0
    tr = types.SimpleNamespace(ops={0: [ev]}, in_window=lambda c: [ev])
    ctx = types.SimpleNamespace(trace=tr,
                                window=types.SimpleNamespace(steps=1))
    ok = {"fusion.3": ("fusion", "jit(f)/lag.fold/mul")}
    assert stages.stage_ms(ctx, "fold", ok) == pytest.approx(5e-6)
    other = {"fusion.3": ("copy", "jit(f)/lag.fold/copy")}
    assert stages.stage_ms(ctx, "fold", other) is None
    assert "is not the step's copy" in capsys.readouterr().err


# -- the step compiled for a described v5e ---------------------------------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def on_described_tpu(monkeypatch):
    """The plane's kernels compile for Mosaic, as on the chip, and no
    described-chip compile goes to the persistent cache."""
    import jax
    import repro.kernels
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _described_step(topo, name, monkeypatch):
    """The tiny cell ``name``'s step, fast plane on, compiled for the
    described chips; its HLO text."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    import program
    from repro.dist import (TrainerConfig, batch_shardings, lag_trainer,
                            tree_shardings)
    from repro.engine import make_topology, topology as topo_lib
    from repro.launch.mesh import make_mesh
    from repro.models import model

    cell = tiny_cell(name)
    work = cell.work
    cfg = program.model_config(TINY[cell.spec["config"]])
    tcfg = TrainerConfig(algo=work["algo"], num_workers=work["workers"],
                         lr=work["lr"], D=work["D"], xi=work["xi"],
                         fastpath="on")
    if work["topology"] == "shards":
        topology = None
        mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
        place = lambda st: tree_shardings(st, mesh)
    else:
        topology = make_topology(work["topology"])
        D = topology.num_devices()
        mesh = make_mesh((D,), ("workers",), devices=topo.devices[:D])
        monkeypatch.setattr(topo_lib.DeviceWorkers, "device_mesh",
                            lambda self, default=None: mesh)
        per_worker = set(tcfg.comm_policy().state_keys) | {
            "comm_per_worker", "L_m"}

        def place(st):
            def one(path, _):
                keys = [getattr(k, "key", None) for k in path[:2]]
                split = keys[0] == "lag" and keys[1] in per_worker
                return NamedSharding(mesh, P("workers") if split else P())
            return jax.tree_util.tree_map_with_path(one, st)

    def spec(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    state = jax.eval_shape(lambda k: lag_trainer.state_for_params(
        model.init(k, cfg), tcfg, topology=topology), jax.random.PRNGKey(0))
    tokens = np.zeros((cell.traffic["batch"], cell.traffic["seq"]), np.int32)
    batch = {"tokens": tokens, "targets": tokens}
    step = program.make_step(cfg, tcfg, topology)
    with jax.set_mesh(mesh):
        return jax.jit(step, donate_argnums=(0,)).lower(
            spec(state, place(state)),
            spec(batch, batch_shardings(batch, mesh))).compile().as_text()


#: the ops that do the step's work; each must carry a stage
WORK = ("fusion", "custom-call", "dot", "convolution", "all-gather",
        "all-gather-start")


def _work_stages(text):
    """(stage, opcode, instruction, op name, reads operands) of each
    working op that runs as an event of its own (not inside a fusion),
    from the compiled text."""
    names = stages.op_names(text)
    fused = set(re.findall(r"\bcalls=%([\w.\-]+)", text))
    out, inside = [], False
    for line in text.splitlines():
        if line[:1] not in ("", " ", "}"):     # a computation's header
            inside = line.split(" ")[0].lstrip("%") in fused
        m = re.match(r"\s*(?:ROOT\s+)?%(\S+) = .*?\s([a-z][\w\-]*)\((.)",
                     line)
        if m and m.group(2) in WORK and not inside:
            out.append((stages.stage(names[m.group(1)]), m.group(2),
                        m.group(1), names[m.group(1)], m.group(3) != ")"))
    return out


@pytest.mark.parametrize("name,want", [
    ("granite-8b-l1v8.shards2.lag-wk",
     {"forward", "backward", "encode", "layout", "fold", "server"}),
    ("granite-8b-l1v8.devices4.laq4",
     {"forward", "backward", "encode", "layout", "fold", "server", "wire",
      "gather"}),
])
def test_every_working_op_of_the_v5e_step_has_a_stage(
        topo, on_described_tpu, monkeypatch, name, want):
    ops = _work_stages(_described_step(topo, name, monkeypatch))
    assert {op for _, op, _, _, _ in ops} >= {"fusion", "custom-call"}
    assert {s for s, _, _, _, _ in ops} >= want
    # every op of the program carries a stage, but for constants, which
    # read no operand: JAX hoists the causal mask out of the rematted
    # layer scan under a name stack of its own.  XLA's own ops (the
    # layout copies and pads it fuses) carry no op name at all.
    unscoped = [(op, n, on) for s, op, n, on, reads in ops
                if s == "unscoped" and on.startswith("jit(") and reads]
    assert unscoped == []

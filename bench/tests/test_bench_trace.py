"""The trace reduction, on hand-made events and on a trace recorded on
the CPU."""
import time

import pytest

from _bench import BENCH, run

trace = run._module(BENCH / "trace.py")
plane = run._module(BENCH / "metrics" / "plane_kernel_ms.py")
E = trace.Event

# operations as a TPU v5e trace names them: the whole HLO instruction
# (granite-8b-l1v8, W = 2, 2,097,408 plane rows)
ROWS = 2_097_408
MASKED = ("%train_step.3 = f32[2,2097408,128]{2,1,0:T(8,128)} custom-call("
          "f32[2,2097408,128]{2,1,0:T(8,128)} %broadcast_dynamic-update-"
          "slice_fusion.3, f32[2,2097408,128]{2,1,0:T(8,128)} %broadcast_"
          "dynamic-update-slice_fusion.2, f32[2,1]{1,0:T(2,128)S(1)} "
          "%copy-done.133), custom_call_target=\"tpu_custom_call\", "
          "operand_layout_constraints={f32[2,2097408,128]{2,1,0}, "
          "f32[2,2097408,128]{2,1,0}, f32[2,1]{1,0}}, output_to_operand_"
          "aliasing={{}: (1, {})}, frontend_attributes={kernel_metadata={}}")
SQ = ("%train_step.2 = f32[8193,2,32]{2,1,0:T(2,128)S(1)} custom-call("
      "f32[2,2097408,128]{2,1,0:T(8,128)} %broadcast_dynamic-update-slice_"
      "fusion.3), custom_call_target=\"tpu_custom_call\", operand_layout_"
      "constraints={f32[2,2097408,128]{2,1,0}}, frontend_attributes="
      "{kernel_metadata={}}")
# the gated gather of the four-chip cell, and the unpack that reads its
# output: the reader's text names the gather too
GATHER = ("%all-gather.6 = u8[4,1048704,1,128]{3,2,1,0:T(8,128)(4,1)} "
          "all-gather(u8[1,1048704,1,128]{3,2,1,0:T(8,128)(4,1)} %fusion.22)"
          ", channel_id=7, replica_groups=[1,4]<=[4], dimensions={0}, "
          "use_global_device_ids=true")
UNPACK = ("%shift-right-logical_and_fusion.1 = (u8[4,1048704,1,128]{3,2,1,0:"
          "T(8,128)(4,1)}, u8[4,1048704,1,128]{3,2,1,0:T(8,128)(4,1)}) fusion("
          "u8[4,1048704,1,128]{3,2,1,0:T(8,128)(4,1)} %all-gather.6), "
          "kind=kLoop, calls=%fused_computation.77")
FUSION = ("%fusion.279 = (f32[2,458752,128]{2,1,0:T(8,128)}, f32[2,458752,"
          "128]{2,1,0:T(8,128)}) fusion(f32[2,2097408,128]{2,1,0:T(8,128)} "
          "%train_step.3), kind=kLoop, calls=%fused_computation.405")


def hand_trace():
    """Window [0, 100); chip 0 busy [10, 30) ∪ [25, 40) ∪ [60, 70) with a
    nested op inside the first; host spans name the gaps."""
    ops = [E("fusion.1", 10, 20, {}), E(SQ, 12, 5, {}),
           E("fusion.2", 25, 15, {}), E(GATHER, 60, 6, {}),
           E(UNPACK, 66, 4, {})]
    spans = [E("bench.window", 0, 100, {}), E("bench.input", 0, 10, {}),
             E("bench.dispatch", 40, 5, {}), E("bench.throttle", 45, 15, {}),
             E("bench.input", 70, 30, {}), E("bench.wait", 40, 60, {})]
    for evs in ([ops],):
        trace._self_times(evs[0])
    return trace.Trace(ops={0: ops}, spans=spans)


def test_busy_union_and_window():
    t = hand_trace()
    assert t.busy_intervals(0) == [(10, 40), (60, 70)]
    assert t.window_s() == pytest.approx(100e-9)
    assert t.busy_s() == pytest.approx(40e-9)


def test_idle_gaps_named_by_the_main_thread_span():
    gaps = hand_trace().idle_gaps()
    # [70, 100) under input; [40, 60) mostly throttle; [0, 10) input
    assert [g[0] for g in gaps] == ["input", "throttle", "input"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 20e-9, 10e-9])


def test_self_time_and_matching_by_name():
    t = hand_trace()
    top = dict(t.top_ops())
    assert top["fusion.1"] == pytest.approx(15e-9)      # 20 less nested 5
    found = t.matching(lambda e: plane.kernel_of(e, ROWS) is not None)
    assert [e.name for e in found[0]] == [SQ]
    assert [e.name for e in t.matching(lambda e: e.opcode == "fusion")[0]] \
        == [UNPACK]


def test_collective_ms_counts_the_gather_not_its_readers():
    import types
    collective = run._module(BENCH / "metrics" / "collective_ms.py")
    ctx = types.SimpleNamespace(trace=hand_trace(),
                                window=types.SimpleNamespace(steps=2))
    # the gather's 6 ns over 2 steps; the unpack's 4 ns are not counted
    assert collective.read(ctx) == pytest.approx(3e-6)
    assert trace.opcode(GATHER) == "all-gather"
    assert trace.opcode(UNPACK) == "fusion"


def test_a_trace_recorded_on_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("bench.input"):
                time.sleep(0.01)
            y.block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    with pytest.raises(ValueError, match="no device operations"):
        trace.load(path)        # a trace off the device is no TPU trace
    t = trace.load(path, cpu=True)
    assert {s.name for s in t.spans} >= {"bench.window", "bench.dispatch",
                                         "bench.input"}
    assert t.ops and any(t.ops.values())
    assert 0.0 < t.busy_s() < t.window_s()
    gaps = t.idle_gaps()
    assert gaps and gaps[0][0] == "input" and gaps[0][1] >= 0.005
    dots = t.matching(lambda e: "dot" in e.name)
    assert sum(len(v) for v in dots.values()) >= 3


@pytest.mark.parametrize("name,kernel", [(MASKED, "_masked_kernel"),
                                         (SQ, "_sq_kernel"), (FUSION, None)])
def test_plane_kernels_told_by_signature(name, kernel):
    assert plane.kernel_of(E(name, 0, 1, {}), ROWS) == kernel
    assert plane.kernel_of(E(name, 0, 1, {}), ROWS + 256) is None


def test_labels_keep_name_opcode_and_shape():
    assert trace.label(MASKED) == \
        "%train_step.3 custom-call tpu_custom_call f32[2,2097408,128]"
    assert trace.label(FUSION) == \
        "%fusion.279 fusion (f32[2,458752,128], f32[2,458752,128])"
    assert trace.label("fusion.1") == "fusion.1"


def test_step_p90_is_over_every_interval():
    # alternate slow and fast steps: the tail is the slow ones, unsmoothed
    intervals = [0.1, 0.3] * 50
    assert run.step_p90_ms(intervals) == pytest.approx(300.0)
    assert run.step_p90_ms([0.12] * 99 + [1.0]) == pytest.approx(120.0)

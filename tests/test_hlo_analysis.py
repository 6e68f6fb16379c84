"""Collective-bytes HLO parser unit tests (+ one measured-vs-predicted
check against a REAL compiled 8-host-device module)."""
import os
import subprocess
import sys

import pytest

from repro.dist.hlo_analysis import collective_bytes, _shape_bytes

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_shape_bytes():
    assert _shape_bytes("f32[128,4]") == 128 * 4 * 4
    assert _shape_bytes("bf16[10]") == 20
    assert _shape_bytes("(f32[4], bf16[2])") == 16 + 4


def test_all_reduce_ring_estimate():
    hlo = """
ENTRY %main {
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    st = collective_bytes(hlo)
    # 2 · 4096B · 3/4 = 6144
    assert abs(st.by_kind["all-reduce"] - 6144.0) < 1e-6
    assert st.by_kind_count["all-reduce"] == 1


def test_all_gather_and_permute():
    hlo = """
  %ag = bf16[64,256]{1,0} all-gather(%y), replica_groups={{0,1},{2,3}}, dimensions={0}
  %cp = f32[128]{0} collective-permute(%z), source_target_pairs={{0,1},{1,0}}
"""
    st = collective_bytes(hlo)
    assert abs(st.by_kind["all-gather"] - 64 * 256 * 2 * 0.5) < 1e-6
    assert st.by_kind["collective-permute"] == 512.0


def test_start_done_counted_once():
    hlo = """
  %ars = f32[100]{0} all-reduce-start(%x), replica_groups={{0,1}}
  %ard = f32[100]{0} all-reduce-done(%ars)
"""
    st = collective_bytes(hlo)
    assert st.by_kind_count["all-reduce"] == 1


def test_cross_pod_classification():
    hlo = """
  %a = f32[100]{0} all-reduce(%x), replica_groups={{0,1}}, to_apply=%add
  %b = f32[100]{0} all-reduce(%y), replica_groups={{0,4}}, to_apply=%add
"""
    st = collective_bytes(hlo, pod_size=4)
    assert st.cross_pod_bytes > 0
    assert st.cross_pod_bytes < st.total_bytes


def test_iota_replica_groups():
    hlo = """
  %a = f32[256]{0} all-reduce(%x), replica_groups=[2,2]<=[4], to_apply=%add
"""
    st = collective_bytes(hlo, pod_size=2)
    assert st.by_kind_count["all-reduce"] == 1
    # groups [[0,1],[2,3]] with pod_size=2 → no crossing
    assert st.cross_pod_bytes == 0.0


def test_empty_replica_groups_uses_device_count():
    # replica_groups={} means "all devices in one group"
    hlo = """
  %a = f32[1024]{0} all-reduce(%x), channel_id=1, replica_groups={}, to_apply=%add
"""
    st = collective_bytes(hlo, pod_size=2, n_devices=4)
    assert abs(st.by_kind["all-reduce"] - 2 * 4096 * 3 / 4) < 1e-6
    assert st.cross_pod_bytes == st.total_bytes      # 4 devices span 2 pods
    # without n_devices: asymptotic ring factor, not silently zero
    st2 = collective_bytes(hlo)
    assert abs(st2.by_kind["all-reduce"] - 2 * 4096) < 1e-6


def test_async_start_tuple_counts_result_only():
    # -start tuple shape is (operand, result): charge the result buffer,
    # not the tuple sum
    hlo = """
  %ags = (bf16[64,128]{1,0}, bf16[64,256]{1,0}) all-gather-start(%x), replica_groups={{0,1}}, dimensions={1}
  %agd = bf16[64,256]{1,0} all-gather-done(%ags)
"""
    st = collective_bytes(hlo)
    assert st.by_kind_count["all-gather"] == 1
    assert abs(st.by_kind["all-gather"] - 64 * 256 * 2 * 0.5) < 1e-6


def test_non_collectives_ignored():
    hlo = """
  %d = f32[8,8]{1,0} dot(%a, %b)
  %c = f32[8]{0} add(%e, %f)
"""
    st = collective_bytes(hlo)
    assert st.total_bytes == 0.0


@pytest.mark.slow
def test_parser_against_real_compiled_8device_hlo():
    """Measured vs predicted on a REAL compiled module, not synthetic
    text: an 8-host-device shard_map with one all-gather (u8 payload,
    the devrun wire dtype) and one psum.  Whatever spelling/replica-
    group form this XLA emits, the parser's ring-cost totals must land
    on the closed-form prediction exactly."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.dist.hlo_analysis import collective_bytes

D = 8
mesh = make_mesh((D,), ("w",))

def body(x, y):
    g = jax.lax.all_gather(x, "w", tiled=True)     # u8: (D*256, 128)
    s = jax.lax.psum(y, "w")                       # f32[64] all-reduce
    return g.astype(jnp.float32).sum() + s.sum()

f = jax.shard_map(body, mesh=mesh, in_specs=(P("w"), P("w")),
                  out_specs=P(), check_vma=False)
x = jnp.zeros((D * 256, 128), jnp.uint8)
y = jnp.zeros((D * 64,), jnp.float32)
hlo = jax.jit(f).lower(x, y).compile().as_text()
st = collective_bytes(hlo, n_devices=D)
# ring costs: all-gather B(n-1)/n with B the FULL gathered output;
# all-reduce 2B(n-1)/n on the per-device reduced buffer
ag = D * 256 * 128 * 1 * (D - 1) / D
ar = 2 * 64 * 4 * (D - 1) / D
got_ag = st.by_kind.get("all-gather", 0.0)
got_ar = st.by_kind.get("all-reduce", 0.0)
assert abs(got_ag - ag) < 1e-6, (got_ag, ag, dict(st.by_kind))
assert abs(got_ar - ar) < 1e-6, (got_ar, ar, dict(st.by_kind))
assert st.total_bytes == got_ag + got_ar
print("REAL HLO OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "REAL HLO OK" in out.stdout

"""bench/counts.py and bench/peaks.py against hand counts."""
import json

import pytest

from _bench import BENCH, ROOT, run

counts = run._module(BENCH / "counts.py")
peaks = run._module(BENCH / "peaks.py")


def model(config):
    c = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    return c, dict(c["model"]["sizes"], norm_eps=c["model"]["rms_norm_eps"])


def test_mamba2_matmul_parameters_and_flops():
    c, m = model("mamba2-370m")
    ref = run._module(BENCH / "reference" / "mamba2.py")
    # 48 x (in_proj 1024 x 4384 + out_proj 2048 x 1024) + head 50280 x 1024
    assert ref.matmul_params(m) == 48 * (1024 * 4384 + 2048 * 1024) \
        + 50280 * 1024 == 367_632_384
    assert counts.flops_per_token(ref, m, 1024) == 6 * 367_632_384


def test_granite_matmul_parameters_and_flops():
    c, m = model("granite-8b-l1v8")
    ref = run._module(BENCH / "reference" / "dense.py")
    # q, o 4096 x 4096; k, v 4096 x 1024; 3 x 4096 x 14336; head 4096 x 6144
    assert ref.matmul_params(m) == 243_269_632
    # causal scores and values: 6 x 32 heads x 128 x (1024 + 1)
    assert counts.flops_per_token(ref, m, 1024) == \
        6 * 243_269_632 + 6 * 32 * 128 * 1025


def test_plane_rows_at_mamba2_370m():
    import jax
    c, m = model("mamba2-370m")
    ref = run._module(BENCH / "reference" / "mamba2.py")
    shapes = jax.eval_shape(lambda k: ref.init(k, m), jax.random.PRNGKey(0))
    sizes = [l.size for l in jax.tree_util.tree_leaves(shapes)]
    assert sum(sizes) == 368_338_432
    assert counts.plane_rows(sizes) == 2_877_696


@pytest.mark.parametrize("kernel,want", [
    # W = 2 slabs of 2,877,696 x 128 f32 = 2,946,760,704 bytes; partials
    # 2 x 359,712 sub-blocks x 4 bytes = 2,877,696 bytes
    ("_sq_kernel", 2_946_760_704 + 2_877_696),
    ("_delta_sq_kernel", 2 * 2_946_760_704 + 2_877_696),
    ("_absmax_kernel", 3 * 2_946_760_704 + 2_877_696),
    ("_laq_kernel", 5 * 2_946_760_704 + 2 * 2_877_696),
    ("_masked_kernel", 3 * 2_946_760_704 + 8),
])
def test_plane_bytes_per_kernel_at_mamba2_rows(kernel, want):
    assert counts.kernel_bytes(kernel, 2, 2_877_696) == want


def test_every_plane_kernel_has_a_byte_count():
    names = run._module(BENCH / "metrics" / "plane_kernel_ms.py").KERNELS
    assert set(names) == set(counts.KERNEL_IO)


def test_peak_table():
    p = peaks.peaks("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes) == \
        (197e12, 819e9, 16e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")

"""Each plain reference against the program's model at a size the CPU
runs: the same loss and gradients from the same weights and tokens.  The
references import nothing of the program; this test does, to tie them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bench import BENCH, TINY, run


@pytest.mark.parametrize("config,reference", [("mamba2-370m", "mamba2"),
                                              ("granite-8b-l1v8", "dense")])
def test_reference_matches_the_program_model(config, reference):
    import program
    from repro.models import model
    ref = run._module(BENCH / "reference" / f"{reference}.py")
    tiny = TINY[config]
    cfg = program.model_config(tiny)
    m = dict(tiny["sizes"], norm_eps=tiny["rms_norm_eps"])
    params = ref.init(jax.random.PRNGKey(7), m)
    rng = np.random.default_rng(7)
    tok = jnp.asarray(rng.integers(0, m["vocab_size"], (2, 64)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, m["vocab_size"], (2, 64)), jnp.int32)
    lp, gp = jax.value_and_grad(lambda p: model.loss_fn(
        p, cfg, {"tokens": tok, "targets": tgt}))(params)
    lr, gr = jax.value_and_grad(lambda p: ref.loss(p, m, tok, tgt))(params)
    # float32 on the CPU, two summation orders: the largest difference
    # measured is 5.5e-7 of a leaf's largest entry; 1e-5 leaves room
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 1e-5 * np.max(np.abs(b))

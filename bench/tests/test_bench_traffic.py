"""The traffic copy is deterministic in (seed, step, worker)."""
import numpy as np
import pytest

from _bench import BENCH, run

traffic = run._module(BENCH / "traffic.py")
BIG = 2 ** 33 + 12345


@pytest.mark.parametrize("h", [None, 0.0, 1.0])
def test_same_seed_step_same_batch(h):
    t = traffic.Traffic(batch=4, seq=32, regime="fresh", h=h)
    a = traffic.make_batch(t, 500, BIG, 7, 2)
    b = traffic.make_batch(t, 500, BIG, 7, 2)
    for k in ("tokens", "targets"):
        np.testing.assert_array_equal(a[k], b[k])
        assert a[k].shape == (4, 32) and a[k].dtype == np.int32
        assert a[k].min() >= 0 and a[k].max() < 500
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])


def test_steps_seeds_and_workers_differ():
    t = traffic.Traffic(batch=4, seq=32)
    a = traffic.make_batch(t, 500, BIG, 0, 2)["tokens"]
    assert not np.array_equal(a, traffic.make_batch(t, 500, BIG, 1, 2)["tokens"])
    assert not np.array_equal(a, traffic.make_batch(t, 500, BIG + 1, 0, 2)["tokens"])
    w0 = traffic.stream_batch(500, BIG, 3, 0, 2, 16)
    w1 = traffic.stream_batch(500, BIG, 3, 1, 2, 16)
    assert not np.array_equal(w0, w1)
    assert len({tuple(r) for r in a}) == 4          # every row differs


def test_a_mix_fixes_the_rows_per_worker():
    t = traffic.Traffic.from_dict({"batch": 4, "per_worker": 1, "seq": 16})
    assert traffic.make_batch(t, 100, BIG, 0, 4)["tokens"].shape == (4, 16)
    with pytest.raises(ValueError):
        traffic.make_batch(t, 100, BIG, 0, 2)


def test_fixed_regime_reuses_step_zero():
    t = traffic.Traffic(batch=4, seq=16, regime="fixed", h=1.0)
    np.testing.assert_array_equal(
        traffic.make_batch(t, 100, 3, 0, 2)["tokens"],
        traffic.make_batch(t, 100, 3, 9, 2)["tokens"])


@pytest.mark.parametrize("noise", [0.0, 0.1, 0.9])
def test_closed_form_matches_the_recurrence_step_by_step(noise):
    """The bulk solution equals x_{t+1} = (a*x_t + drift) mod V, reset by
    the same draws, taken one step at a time."""
    vocab, seed, step, worker, rows, length = 49152, BIG, 3, 1, 3, 300
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, worker]))
    a, drift = 6364136223846793005 % vocab, 1 + 97 * worker
    first = rng.integers(0, vocab, size=(rows, 1))
    resets = rng.integers(0, vocab, size=(rows, length - 1))
    use = rng.random((rows, length - 1)) < noise
    want = [first[:, 0]]
    for t in range(length - 1):
        want.append(np.where(use[:, t], resets[:, t],
                             (want[-1] * a + drift) % vocab))
    np.testing.assert_array_equal(
        traffic.stream_batch(vocab, seed, step, worker, rows, length, noise),
        np.stack(want, axis=1))


def test_noise_levels_dial():
    assert traffic.noise_levels(3, 0.0) == pytest.approx([0.205] * 3)
    assert traffic.noise_levels(3, 1.0) == pytest.approx([0.01, 0.205, 0.4])
    with pytest.raises(ValueError):
        traffic.Traffic(batch=2, seq=2, h=1.5)

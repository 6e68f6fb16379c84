"""``correct`` comes out true for a sound run and false for the control
and for each fault a cell can have, at a size a test run holds, under
each cell's own limits.

The control is the plain reference computed in bfloat16 put in the
program's place.  The faults are planted under the timed path through
``run_cell``'s step hook: a step that returns its state unchanged; a step
that leaves out half of the rows and takes the mean over the rest; and,
in a cell on several chips, the exchange between them left out.  A cell
on four chips runs in a child process that sees four CPU devices."""
import jax.numpy as jnp
import pytest

from _bench import BENCH, cells, drive, drive_in_subprocess, run, tiny_cell

check = run._module(BENCH / "check.py")
SEED = 2 ** 33 + 41


def faults(cell):
    many = run.load_cell(cell).chips > 1
    return ["frozen", "half"] + (["alone"] if many else [])


def result(cell, fault=None):
    if run.load_cell(cell).chips > 1:
        return drive_in_subprocess(cell, fault)
    return drive(cell, fault, SEED)


@pytest.mark.parametrize("cell", cells())
def test_sound_run_is_correct(cell):
    res = result(cell)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checked"
    assert res["metrics"]["tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("cell,fault",
                         [(c, f) for c in cells() for f in faults(c)])
def test_fault_under_the_timed_path_is_not_correct(cell, fault):
    res = result(cell, fault)
    assert res["correct"] is False and res["failed"] == 1


@pytest.mark.parametrize("cell", cells())
def test_control_in_bfloat16_is_not_correct(cell):
    c = tiny_cell(cell)
    ref = run.reference_readings(c, SEED)
    control = run.reference_readings(c, SEED, dtype=jnp.bfloat16)
    ok, table = check.judge(check.gaps(control, ref), c.work["limits"])
    assert not ok, table

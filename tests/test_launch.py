"""The launcher surfaces that a chip run depends on: where the compile
cache goes, the auto comm plane stepping aside under a partitioning mesh,
and ``devices:D`` through ``repro.launch.train``, which runs for real or
raises.

No test turns the persistent compile cache on: the launcher's call to it
is replaced by a recorder.
"""
import os

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro.fastpath import FastPathPlan
from repro.launch import cache
from repro.launch import train

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of making them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_placed_by_env(monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert config_updates == []          # JAX reads the variable itself


def test_compile_cache_defaults_to_checkout(monkeypatch, config_updates):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    where = cache.enable_compile_cache()
    assert config_updates == [("jax_compilation_cache_dir", where)]
    assert os.path.samefile(os.path.dirname(where), ROOT)
    assert os.path.basename(where) == ".jax_cache"
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("kind,size,partitioned", [
    (AxisType.Auto, 4, True),       # shards on a 4-chip host mesh
    (AxisType.Auto, 1, False),      # the one-chip host mesh
    (AxisType.Manual, 4, False),    # inside devrun's shard_map
])
def test_auto_plan_yields_to_partitioning_mesh(kind, size, partitioned):
    """Mosaic kernels cannot be auto-partitioned: under a context mesh
    that spreads the round over devices, an auto plan hands the round to
    the oracle; a forced (interpret-mode) plan never does."""
    mesh = AbstractMesh((size,), ("data",), axis_types=(kind,))
    with jax.sharding.use_abstract_mesh(mesh):
        assert FastPathPlan("auto").partitioned_by_context() is partitioned
        assert not FastPathPlan("on").partitioned_by_context()
    assert not FastPathPlan("auto").partitioned_by_context()


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)


TINY = ["--reduced", "--steps", "2", "--batch", "4", "--seq", "16",
        "--lr", "0.05"]


def test_train_devices_one_runs_the_device_plane(no_cache):
    """``--topology devices:1`` runs devrun's shard_map step under the
    workers' own mesh and reports per-step losses and upload masks."""
    run = train.main(TINY + ["--topology", "devices:1", "--workers", "1"])
    assert run.losses.shape == (2,) and np.isfinite(run.losses).all()
    assert run.comm_masks.shape == (2, 1)
    assert run.uploads == run.comm_masks.sum() and run.gd_uploads == 2
    assert run.first_step_seconds > 0 and run.step_seconds > 0
    assert "conditional" in run.compiled().as_text()


def test_train_devices_too_few_raises(no_cache):
    D = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match=f"devices:{D} needs {D} devices"):
        train.main(TINY + ["--topology", f"devices:{D}", "--workers",
                           str(D)])


def test_train_profile_names_steps_and_host_spans(no_cache, tmp_path):
    """``--profile DIR`` traces the steps after the first: the step
    annotation and the ``repro.*`` host spans are in the trace."""
    import glob
    from jax.profiler import ProfileData
    prof = tmp_path / "prof"
    train.main(TINY + ["--workers", "2", "--profile", str(prof),
                       "--ckpt-dir", str(tmp_path / "ck"),
                       "--ckpt-every", "1"])
    found = glob.glob(str(prof / "**" / "*.xplane.pb"), recursive=True)
    assert len(found) == 1
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(found[0]).planes
              for line in plane.lines for e in line.events]
    names = {n for n, _ in events}
    assert {"repro.input", "repro.log", "repro.ckpt"} <= names
    steps = [st for n, st in events if n == "train"]
    assert [int(st["step_num"]) for st in steps] == [1]  # step 0 compiles

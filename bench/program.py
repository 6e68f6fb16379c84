"""The system under test: every call the benchmark makes into ``repro``.

The window drives the jitted train step that ``repro.launch.train.main``
builds for the cell's topology, under ``--fastpath auto``:
``repro.dist.make_train_step`` for ``shards`` (W lazy workers batched on
one chip) and ``repro.devrun.make_device_step`` for ``devices:D`` (one
lazy worker per chip).  The state is built in place on the device, in one
jitted call, around the weights the benchmark makes from the seed.
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Callable, Dict

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.dist import (TrainerConfig, batch_shardings, lag_trainer,  # noqa: E402
                        make_train_step, tree_shardings)
from repro.launch.cache import enable_compile_cache  # noqa: E402,F401
from repro.launch.mesh import make_host_mesh  # noqa: E402


def model_config(model: Dict):
    """The program's config for ``model`` (a configuration file's
    ``model`` group); every size the file states must be the program's."""
    cfg = get_config(model["arch"], **model.get("overrides", {}))
    for key, want in model["sizes"].items():
        have = getattr(cfg, key)
        if have != want and not (isinstance(want, list)
                                 and tuple(want) == have):
            raise ValueError(f"{model['arch']}: the program runs {key}={have!r},"
                             f" the configuration file states {want!r}")
    return cfg


def make_step(cfg, tcfg, topology):
    """The unjitted ``(state, batch) -> (state, metrics)`` of the cell."""
    if topology is None:
        return make_train_step(cfg, tcfg)
    from repro import devrun
    return devrun.make_device_step(cfg, tcfg, topology=topology)


class Program:
    """The compiled step of one cell with its state."""

    def __init__(self, model: Dict, work: Dict, init: Callable, key):
        self.cfg = model_config(model)
        W = work["workers"]
        self.tcfg = TrainerConfig(algo=work["algo"], num_workers=W,
                                  lr=work["lr"], D=work["D"], xi=work["xi"],
                                  fastpath="auto")
        if work["topology"] == "shards":
            self.topology = None
            self.mesh = make_host_mesh()
            shardings = lambda st: tree_shardings(st, self.mesh)
        else:
            from repro.engine import make_topology
            self.topology = make_topology(work["topology"])
            self.mesh = self.topology.device_mesh(W)
            shardings = self._device_placement
        build = lambda k: lag_trainer.state_for_params(
            init(k), self.tcfg, topology=self.topology)
        want = jax.eval_shape(lambda k: _program_params(k, self.cfg), key)
        have = jax.eval_shape(init, key)
        if (jax.tree_util.tree_structure(want)
                != jax.tree_util.tree_structure(have)
                or jax.tree_util.tree_leaves(want)
                != jax.tree_util.tree_leaves(have)):
            raise ValueError("the benchmark's weights are not laid out as the "
                             "program's parameters")
        with jax.set_mesh(self.mesh):
            self.state = jax.jit(build, out_shardings=shardings(
                jax.eval_shape(build, key)))(key)
        self.step = jax.jit(make_step(self.cfg, self.tcfg, self.topology),
                            donate_argnums=(0,))
        self._batch_sharding = None

    def _device_placement(self, st):
        """devrun's placement: per-worker LAG leaves along the workers
        axis, everything else replicated."""
        policy = self.tcfg.comm_policy()
        per_worker = set(policy.state_keys) | {"comm_per_worker", "L_m"}

        def one(path, _):
            keys = [getattr(k, "key", None) for k in path[:2]]
            split = keys[0] == "lag" and keys[1] in per_worker
            return NamedSharding(self.mesh, P("workers") if split else P())
        return jax.tree_util.tree_map_with_path(one, st)

    def context(self):
        """The mesh the launcher traces and runs the step under."""
        return jax.set_mesh(self.mesh)

    def put(self, batch: Dict[str, np.ndarray]):
        """A host batch on the device, placed as the launcher places it."""
        if self._batch_sharding is None:
            self._batch_sharding = batch_shardings(batch, self.mesh)
        return jax.device_put(batch, self._batch_sharding)

    @staticmethod
    def nabla(state):
        """The aggregate gradient the server's optimizer steps with."""
        return state["lag"]["nabla"]

    @staticmethod
    def params(state):
        return state["params"]

    @staticmethod
    def outputs(metrics):
        """(loss, upload mask) of one step."""
        return metrics["loss"], metrics["comm_mask"]

    @staticmethod
    def plane_workers(work: Dict) -> int:
        """Workers per chip in the comm plane's flat buffer."""
        return 1 if work["topology"].startswith("devices") else work["workers"]


def _program_params(key, cfg):
    from repro.models import model
    return model.init(key, cfg)

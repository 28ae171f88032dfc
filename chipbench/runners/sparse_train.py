"""Runner for the sparse-rows embedding-table cells.

The step is built as ``repro.launch.train`` builds ``--workload
sparse_embedding``: ``make_sparse_embedding_step`` with the launcher's
``train_step`` body (∇ = table[ids] − target[ids] on the touched rows),
the target passed as an argument, the launcher's shardings, and
``jax.jit(..., donate_argnums=(0, 1))``; the window drives it through
``Trainer.fit``.  The table and the target are drawn by the benchmark
(``chipbench.weights``), streams 0 and 1 of the seed."""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import weights

RATE_METRIC = "ids_per_s"


def n_ids(cell) -> int:
    return int(cell.config["num_rows"])


def step_work(cell, batch) -> Dict[str, int]:
    """What one step's batch asks of the step: ids and unique ids."""
    ids = batch["tokens"].reshape(-1)
    return {"ids": int(ids.size), "unique": int(np.unique(ids).size)}


@functools.lru_cache(maxsize=None)
def _norm_fn():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))))


def norm(x):
    """‖x‖ in one fused program: no squared copy of a sketch on the
    device, so the check adds nothing to the run's peak memory."""
    return _norm_fn()(x)


def table_scale(cfg) -> float:
    return float(cfg["embedding_dim"]) ** -0.5


class Session:
    def __init__(self, cell, seed: int, pool: List[Dict],
                 fault: Optional[str] = None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.optimizers import SketchHParams
        from repro.distributed import sharding as shd
        from repro.kernels import registry
        from repro.launch.mesh import make_host_mesh
        from repro.train.steps import make_sparse_embedding_step
        from repro.train.trainer import Trainer, TrainerConfig, TrainState

        cfg = cell.config
        self.cfg = cfg
        self.seed = seed
        n_rows, dim = int(cfg["num_rows"]), int(cfg["embedding_dim"])
        opt_cfg = cfg["optimizer"]
        mesh = make_host_mesh(data=1, model=1)
        hp = SketchHParams(compression=float(cfg["compression"]),
                           backend=None)
        init_fn, step_fn, opt = make_sparse_embedding_step(
            n_rows, dim, lr=float(opt_cfg["lr"]), hparams=hp, mesh=mesh)
        self._check_specs(n_rows, dim, hp)
        self.work_per_step = int(pool[0]["tokens"].size)
        self.rate_metric = RATE_METRIC
        scale = table_scale(cfg)

        with shd.active_mesh(mesh):
            table = weights.draw(weights.seed_key(seed, 0),
                                 shape=(n_rows, dim), scale=scale)
            target = weights.draw(weights.seed_key(seed, 1),
                                  shape=(n_rows, dim), scale=scale)
            opt_state = opt.init()
            table_spec = NamedSharding(mesh, P())
            opt_spec = shd.named(mesh, shd.opt_specs_for_state(
                jax.eval_shape(lambda: opt_state), table, mesh))
            bshape = pool[0]["tokens"].shape
            bspec = shd.named(mesh, {"tokens": shd.batch_spec(mesh, bshape)})
            mspec = NamedSharding(mesh, P())

            def train_step(table, opt_state, batch, target):
                ids = batch["tokens"].reshape(-1).astype(jnp.int32)
                rows = table[ids] - target[ids]
                loss = jnp.mean(jnp.square(rows))
                if fault == "half_batch":
                    half = ids.shape[0] // 2
                    ids, rows = ids[:half], rows[:half]
                    loss = jnp.mean(jnp.square(rows))
                new_table, new_state = step_fn(table, opt_state, ids, rows)
                if fault == "state_unchanged":
                    new_table, new_state = table, opt_state
                gn = jnp.sqrt(jnp.sum(jnp.square(rows)))
                return new_table, new_state, {"loss": loss, "grad_norm": gn}

            jit_step = jax.jit(train_step,
                               in_shardings=(table_spec, opt_spec, bspec,
                                             table_spec),
                               out_shardings=(table_spec, opt_spec, mspec),
                               donate_argnums=(0, 1))
            table = jax.device_put(table, table_spec)
            opt_state = jax.device_put(opt_state, opt_spec)
            target = jax.device_put(target, table_spec)
            t0 = time.perf_counter()
            with registry.recording() as rec:
                compiled = jit_step.lower(table, opt_state, pool[0],
                                          target).compile()
            self.compile_s = time.perf_counter() - t0
        self.backends = sorted({(k, op, name) for k, op, name, _ in rec})
        want = cell.spec.get("backends")
        if want is not None and sorted(map(tuple, want)) != self.backends:
            raise RuntimeError(f"sketched tables resolved to "
                               f"{self.backends}, the cell expects {want}")
        self.target = target
        self.trainer = Trainer(
            lambda t, s, b: compiled(t, s, b, target), None,
            TrainerConfig(total_steps=0))
        self.state = TrainState(step=0, params=table, opt_state=opt_state)
        self.b1, self.b2 = 0.9, 0.999

    def _check_specs(self, n_rows, dim, hp):
        """The sketches the program builds are the configuration's."""
        from repro.train.steps import sparse_embedding_stores
        m_st, v_st = sparse_embedding_stores(n_rows, dim, hparams=hp)
        want = self.cfg["sketch"]
        for st in (m_st, v_st):
            got = {"depth": st.spec.depth, "width": st.spec.width,
                   "seed": st.spec.seed}
            if got != {k: want[k] for k in got}:
                raise RuntimeError(f"the program's sketch {got} is not the "
                                   f"configuration's {want}")

    def grad_norms(self, state) -> Dict[str, float]:
        """‖g‖ of step 1 from the state after it: the m sketch holds
        (1−β₁)·sketch(g), the v sketch (1−β₂)·sketch(g²)."""
        s = state.opt_state
        return {"m": {"table": float(norm(s["m"])) / (1 - self.b1)},
                "v": {"table": float(norm(s["v"])) / (1 - self.b2)}}

    def change_norms(self, state) -> Dict[str, float]:
        sq = weights.change_sq(state.params, weights.seed_key(self.seed, 0),
                               scale=table_scale(self.cfg))
        return {"table": float(sq) ** 0.5}

    def free(self, state):
        import jax
        for x in jax.tree_util.tree_leaves((state.params, state.opt_state,
                                            self.target)):
            x.delete()
        self.target = None
        self.trainer.step_fn = None

    def cost(self, steps_work: List[Dict[str, int]]) -> dict:
        """Bytes and operations the traced steps needed (``cost/``)."""
        from chipbench.bench import HERE, load_module
        sk = self.cfg["sketch"]
        dim = int(self.cfg["embedding_dim"])
        step = load_module(HERE / "cost" / "sparse_step.py")
        kern = load_module(HERE / "cost" / "cs_adam_tiled.py")
        out = {"step": step.cost(steps_work, dim=dim, depth=sk["depth"]),
               "kernels": {"cs_adam_tiled": kern.cost(
                   steps_work, dim=dim, depth=sk["depth"])}}
        return out


def build(cell, seed, pool, fault=None) -> Session:
    return Session(cell, seed, pool, fault=fault)

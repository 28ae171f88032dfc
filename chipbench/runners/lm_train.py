"""Runner for the language-model training cells.

The step is the one ``repro.launch.train --arch <arch>`` trains:
``make_train_step`` for the configuration's program config (``arch``),
jitted with the launcher's shardings and donation (``jit_train_step``)
and compiled before the loop (``compile_step``); the window drives it
through ``Trainer.fit``.  The benchmark's batches hold ``tokens`` alone:
the labels are the next token, the last position's loss masked, derived
on the device inside the jitted step.  Every parameter is drawn by the
benchmark (``chipbench.weights``), leaf by leaf, as the reference draws
it (``reference/lm_train.py``)."""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import numpy as np

from chipbench.bench import HERE, load_module

RATE_METRIC = "ids_per_s"
EMBED, HEAD = "tok_embed/table", "lm_head/table"


def _reference():
    return load_module(HERE / "reference" / "lm_train.py")


def n_ids(cell) -> int:
    return int(cell.config["vocab_size"])


def step_work(cell, batch) -> Dict[str, int]:
    """What one step's batch asks of the step: token ids (each a lookup in
    the embedding table) and unique ids."""
    ids = batch["tokens"].reshape(-1)
    return {"ids": int(ids.size), "unique": int(np.unique(ids).size)}


# the program config's fields, against the configuration file's keys
FIELDS = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
          "kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim",
          "v_head_dim": "v_head_dim", "d_ff": "moe_intermediate_size",
          "dense_d_ff": "intermediate_size", "n_experts": "n_routed_experts",
          "top_k": "num_experts_per_tok", "experts_held": "n_experts_held",
          "expert_rank": "expert_rank",
          "first_k_dense": "first_k_dense_replace", "n_layers": "n_layers",
          "vocab": "vocab_size", "rope_theta": "rope_theta",
          "norm_eps": "rms_norm_eps",
          "routed_scaling": "routed_scaling_factor"}


def program_config(cell):
    """The program's config for the cell (``arch``, cut for a test cell
    by ``smoke``), checked against the configuration file."""
    from repro import configs
    c = cell.config
    cfg = configs.get(c["arch"])
    if "smoke" in c:
        cfg = cfg.reduced(**c["smoke"])
    want = {f: c[k] for f, k in FIELDS.items()}
    want.update(shared_d_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
                router=c["scoring_func"],
                bias_update_rate=c["router"]["bias_update_rate"],
                aux_loss_alpha=c["router"]["balance_loss_alpha"],
                compute_dtype=c["compute_dtype"])
    got = {f: getattr(cfg, f) for f in want}
    if got != want:
        bad = {f: (got[f], want[f]) for f in want if got[f] != want[f]}
        raise RuntimeError(f"the program's {cfg.name} is not the "
                           f"configuration (program, file): {bad}")
    return cfg


@functools.lru_cache(maxsize=None)
def _norm_fn():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))))


def norm(x) -> float:
    return float(_norm_fn()(x))


def _paths(tree) -> Dict[str, object]:
    import jax
    out = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", k)) for k in kp)] = leaf
    return out


def _unflatten(like, values: Dict[str, object]):
    import jax
    flat, tdef = jax.tree_util.tree_flatten_with_path(like)
    return jax.tree_util.tree_unflatten(tdef, [
        values["/".join(str(getattr(k, "key", k)) for k in kp)]
        for kp, _ in flat])


class Session:
    def __init__(self, cell, seed: int, pool: List[Dict],
                 fault: Optional[str] = None):
        cfg = program_config(cell)       # fails first where the program
        import jax                       # has no such configuration
        import jax.numpy as jnp
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import compile_step, jit_train_step
        from repro.distributed import sharding as shd
        from repro.train.steps import make_train_step
        from repro.train.trainer import Trainer, TrainerConfig, TrainState

        ref = _reference()
        self.cell, self.cfg, self.seed = cell, cfg, seed
        opt = cell.config["optimizer"]
        self.b1, self.b2 = float(opt["b1"]), float(opt["b2"])
        ts = make_train_step(cfg, optimizer="cs_adam",
                             lr=_reference().lr_schedule(opt),
                             grad_clip=None)
        mesh = make_host_mesh(data=1, model=1)
        b, s = pool[0]["tokens"].shape
        self.work_per_step = b * s
        self.shape = (b, s)
        self.rate_metric = RATE_METRIC

        def step(params, opt_state, batch):
            tokens = batch["tokens"]
            mask = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s))
            if fault == "half_batch":
                mask = mask & (jnp.arange(b) < b // 2)[:, None]
            new_p, new_o, metrics = ts.step_fn(params, opt_state, {
                "tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1),
                "loss_mask": mask})
            if fault == "state_unchanged":
                # the forward's readings alone, so the update and the
                # backward are dead code (the state's two copies would not
                # fit the chip beside them)
                new_p, new_o = params, opt_state
                metrics = {k: metrics[k] for k in ("loss", "routed_here")}
            return new_p, new_o, metrics

        shapes = jax.eval_shape(ts.init_fn, jax.random.PRNGKey(0))
        want = {p: tuple(x) for p, x in ref.param_shapes(cell.config).items()}
        got = {p: tuple(x.shape) for p, x in _paths(shapes).items()}
        if got != want:
            raise RuntimeError(f"the program's parameters {got} are not the "
                               f"reference's {want}")
        self._check_sketches(ts, shapes)
        with shd.active_mesh(mesh):
            params = _unflatten(shapes, {
                p: ref.draw(seed, p, x.shape)
                for p, x in _paths(shapes).items()})
            opt_state = jax.jit(ts.optimizer.init)(params)
            tpl = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
            jit_step, (pshard, oshard) = jit_train_step(ts, mesh, tpl,
                                                        step_fn=step)
            params = jax.device_put(params, pshard)
            opt_state = jax.device_put(opt_state, oshard)
            compiled, report = compile_step(jit_step, params, opt_state,
                                            pool[0])
        self.compile_s = report.compile_s
        self.memory = report.memory
        self.backends = sorted({(k, op, name)
                                for k, op, name, _ in report.backends})
        want_b = cell.spec.get("backends")
        if want_b is not None and sorted(map(tuple, want_b)) != self.backends:
            raise RuntimeError(f"sketched tables resolved to "
                               f"{self.backends}, the cell expects {want_b}")
        self.trainer = Trainer(compiled, None, TrainerConfig(total_steps=0))
        self.state = TrainState(step=0, params=params, opt_state=opt_state)

    def _check_sketches(self, ts, shapes):
        """The sketches the program builds are the configuration's."""
        import jax
        state = jax.eval_shape(ts.optimizer.init, shapes)
        from repro.core.optimizers import SketchHParams
        from repro.train.steps import EMBED_PATH, sparse_embedding_stores
        hp = SketchHParams(compression=self.cfg.sketch_compression,
                           depth=self.cfg.sketch_depth)
        m_st, _ = sparse_embedding_stores(self.cfg.vocab, self.cfg.d_model,
                                          hparams=hp, path=EMBED_PATH)
        from repro.core.optimizers import stores_from_policy
        from repro.core.partition import SketchPolicy
        h_st, _ = stores_from_policy(SketchPolicy(min_rows=1024),
                                     hparams=hp).resolve(
            HEAD, (self.cfg.vocab, self.cfg.d_model), "float32")
        got = {EMBED: m_st.spec, HEAD: h_st.spec}
        shapes_got = {EMBED: state["tok_embed"]["m"].shape,
                      HEAD: state["rest"]["m"]["lm_head"]["table"].shape}
        for name, spec in got.items():
            want = self.cell.config["sketch"][name]
            have = {"depth": spec.depth, "width": spec.width,
                    "seed": spec.seed}
            if have != {k: want[k] for k in have} or shapes_got[name] != (
                    spec.depth, spec.width, self.cfg.d_model):
                raise RuntimeError(f"the program's {name} sketch {have} is "
                                   f"not the configuration's {want}")

    def _moments(self, state):
        """{moment: {leaf: array}}: the dense and sketched moments."""
        s = state.opt_state
        out = {}
        for mom in ("m", "v"):
            leaves = _paths(s["rest"][mom])
            leaves[EMBED] = s["tok_embed"][mom]
            out[mom] = leaves
        return out

    def grad_norms(self, state) -> Dict[str, Dict[str, float]]:
        """‖g‖ of step 1 from the state after it, every leaf: m holds
        (1−β₁)·g (or its sketch), v (1−β₂)·g² (or its sketch)."""
        mom = self._moments(state)
        return {"m": {p: norm(x) / (1 - self.b1)
                      for p, x in mom["m"].items()},
                "v": {p: norm(x) / (1 - self.b2)
                      for p, x in mom["v"].items()}}

    def change_norms(self, state) -> Dict[str, float]:
        ref = _reference()
        return {p: ref.change_norm(self.seed, p, x)
                for p, x in _paths(state.params).items()}

    def free(self, state):
        import jax
        for x in jax.tree_util.tree_leaves((state.params, state.opt_state)):
            x.delete()
        self.trainer.step_fn = None

    def cost(self, steps_work: List[Dict[str, int]]) -> dict:
        """Operations and bytes the traced steps needed (``cost/``): the
        model's FLOPs, the embedding's sketch kernel, and the held experts'
        grouped matmuls over the pairs routed here (``routed_here``, from
        the window's steps as ``Trainer.fit`` recorded them)."""
        lm = load_module(HERE / "cost" / "lm_step.py")
        kern = load_module(HERE / "cost" / "cs_adam_tiled.py")
        routed = [h["routed_here"] for h in
                  self.trainer.history[-len(steps_work):]]
        sk = self.cell.config["sketch"][EMBED]
        a = _reference().sizes(self.cell.config)
        b, s = self.shape
        return {"step": lm.cost(steps_work, a, routed, batch=b, seq=s),
                "kernels": {"cs_adam_tiled": kern.cost(
                    steps_work, dim=a["d"], depth=int(sk["depth"])),
                    "experts": lm.experts_cost(a, routed)}}


def build(cell, seed, pool, fault=None) -> Session:
    return Session(cell, seed, pool, fault=fault)

"""Smoke run of the count-sketch training path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the dp x shard sparse step, 4 chips

Everything runs in this one process, through the launcher's entry point
(``repro.launch.train.run`` with an argv list) and the step factories it
calls.  One chip runs four phases:

  * sparse rows, the paper's regime: ``--workload sparse_embedding
    --optimizer cs_adam --store-backend auto`` on a 1,048,576-row table
    (512 MiB of f32 at dim 128), compression 5, 65,536 zipf ids a step,
    at dim 128, at dim 64, and with bfloat16 sketch cells.  Each shows a
    falling loss and is held to the ``ref`` backend on the chip: after
    one step on a collision-free id set the sketches and row updates
    agree to 1e-5, and on the zipf batches (where rows collide) the loss
    trajectory of the resolved backend stays within the estimator-noise
    envelope of DESIGN.md §14 of a per-item ``ref`` run on the same
    deduplicated batches;
  * the LM at published width: qwen2-0.5b (24 layers, d_model 896, the
    151,936 x 896 tied vocab), cs_adam at the planner's 'config' budget
    with the fused store backend, 8 steps; fails on a non-finite loss or
    a last loss not below the first.

``--chips 4`` runs only the dp x shard sparse step (``--dp
--sketch-shards 2`` on a (2, 2) data x model mesh) and the single-chip
step it is compared with: under dyadic betas and integer gradient rows
the first-moment sketch must be bit-identical (tests/test_sharded.py).

Each phase prints the backend every sketched table resolved to, compile
seconds, losses, comparison errors and memory; per-step times are smoke
timings, not benchmark metrics.  The last line is ``{"ok": true,
"device": {...}}``.  Any failed phase makes the exit code non-zero, and
so does a host where JAX finds no TPU (checked before any work).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

SPARSE_ROWS, SPARSE_BATCH, SPARSE_SEQ, SPARSE_STEPS = 1 << 20, 64, 1024, 20
LM_ARGS = ["--arch", "qwen2_0_5b", "--optimizer", "cs_adam",
           "--aux-budget", "config", "--store-backend", "auto",
           "--batch", "8", "--seq", "256", "--steps", "8"]
COLLISION_FREE_IDS = 1024
# DESIGN.md §14: under bucket collisions the tiled step and the per-item
# ref step on the same deduplicated batch differ by estimator noise — the
# applied update within this relative L2 envelope
# (tests/test_backends.py::test_tiled_vs_ref_tolerance_under_collisions)
NOISE_ENVELOPE = 0.6


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def _require_tpu(chips: int):
    """The devices to run on, or exit before any work."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"needs a TPU; JAX found platform {devices[0].platform!r} "
              f"({len(devices)} device(s)) — nothing was run")
    if len(devices) < chips:
        _fail(f"--chips {chips} needs {chips} TPU devices; JAX found "
              f"{len(devices)}")
    return devices[:chips]


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _report_run(phase: str, report) -> None:
    _say(phase, f"compile {report.compile_s:.2f} s")
    for kind, op, name, specs in report.backends:
        _say(phase, f"resolved backend {kind}/{op} -> {name} "
                    f"({', '.join(specs)})")
    losses = [h["loss"] for h in report.history]
    _say(phase, "losses " + " ".join(f"{x:.6g}" for x in losses))
    times = [h["time_s"] for h in report.history[1:]]
    if times:
        _say(phase, f"smoke step time (not a benchmark): median "
                    f"{sorted(times)[len(times) // 2] * 1e3:.1f} ms")
    if report.memory is not None:
        m = report.memory
        _say(phase, f"memory_analysis: arguments "
                    f"{m.argument_size_in_bytes:,} B, outputs "
                    f"{m.output_size_in_bytes:,} B, aliased "
                    f"{m.alias_size_in_bytes:,} B, temporaries "
                    f"{m.temp_size_in_bytes:,} B")


def _check(phase: str, ok: bool, what: str) -> None:
    _say(phase, f"{'PASS' if ok else 'FAIL'} {what}")
    if not ok:
        raise AssertionError(f"{phase}: {what}")


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak:,} B"


# ---------------------------------------------------------------------------
# sparse rows
# ---------------------------------------------------------------------------

def _sparse_argv(dim: int, dtype: str, extra=()):
    return ["--workload", "sparse_embedding", "--optimizer", "cs_adam",
            "--store-backend", "auto", "--sparse-rows", str(SPARSE_ROWS),
            "--sparse-dim", str(dim), "--sparse-compression", "5",
            "--sketch-cell-dtype", dtype, "--batch", str(SPARSE_BATCH),
            "--seq", str(SPARSE_SEQ), "--steps", str(SPARSE_STEPS),
            *extra]


def _collision_free_ids(specs, n_rows: int, want: int, seed: int):
    """``want`` distinct ids no two of which share a bucket in any hash row
    of any of ``specs`` — where batch and per-item semantics coincide."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(seed)
    cand = rng.choice(n_rows, size=16 * want, replace=False)
    buckets = [np.asarray(s.family.bucket(jnp.asarray(cand, jnp.int32)))
               for s in specs]
    used, keep = set(), []
    for i, c in enumerate(cand):
        keys = [(n, j, int(b[j, i])) for n, b in enumerate(buckets)
                for j in range(b.shape[0])]
        if used.isdisjoint(keys):
            used.update(keys)
            keep.append(int(c))
            if len(keep) == want:
                break
    if len(keep) < want:
        raise AssertionError(f"found only {len(keep)} collision-free ids")
    return jnp.asarray(keep, jnp.int32)


def _one_step_vs_ref(phase, spec_m, spec_v, backend, dim, seed):
    """One adam_rows step of ``backend`` and of 'ref' from the same random
    sketch state on a collision-free id set: max abs differences."""
    import jax
    import jax.numpy as jnp
    import repro.kernels as K
    ids = _collision_free_ids((spec_m, spec_v), SPARSE_ROWS,
                              COLLISION_FREE_IDS, seed)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    M = jax.random.normal(k1, spec_m.shape).astype(spec_m.dtype)
    V = jnp.abs(jax.random.normal(k2, spec_v.shape)).astype(spec_v.dtype)
    g = jax.random.normal(k3, (ids.shape[0], dim), jnp.float32)

    @jax.jit
    def step(M, V):
        return {name: K.adam_rows(spec_m, spec_v, M, V, ids, g,
                                  jnp.asarray(2, jnp.int32), lr=1e-2,
                                  backend=name)
                for name in (backend, "ref")}

    out = step(M, V)
    errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            for a, b in zip(out[backend], out["ref"])]
    _say(phase, f"collision-free step, {ids.shape[0]} ids: max |{backend} "
                f"- ref| m {errs[0]:.3g} v {errs[1]:.3g} "
                f"update {errs[2]:.3g}")
    _check(phase, max(errs) <= 1e-5,
           "collision-free sketches and updates agree with ref to 1e-5")


def _ref_run(hp, backend, dim, seed, lr, steps):
    """The sparse step with the per-item ``ref`` backend on the launcher's
    zipf stream, table and target, one update per unique id per step (the
    launcher's backends merge duplicate ids first).  At every step, from
    the same state, the step with the resolved ``backend`` also takes the
    raw batch, and the relative L2 distance of the two applied table
    updates is recorded.

    The unique ids run in sorted order as consecutive calls at the same
    optimizer step, with sizes that are powers of two — per-item
    streaming makes the split invisible, and it bounds the number of
    compiled shapes.  Returns (losses, distances)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import ZipfLM, ZipfLMConfig
    from repro.train.steps import make_sparse_embedding_step

    def factory(name):
        return make_sparse_embedding_step(
            SPARSE_ROWS, dim, lr=lr, hparams=dataclasses.replace(
                hp, backend=name))

    init_fn, ref_step, opt = factory("ref")
    _, res_step, _ = factory(backend)
    table = init_fn(jax.random.PRNGKey(seed))
    target = init_fn(jax.random.PRNGKey(seed + 1))
    data = ZipfLM(ZipfLMConfig(vocab_size=SPARSE_ROWS, seq_len=SPARSE_SEQ,
                               global_batch=SPARSE_BATCH, seed=seed))
    state = opt.init()

    # the target is an argument: closed over, its 512 MiB would be
    # compiled into every program
    @jax.jit
    def resolved(table, state, ids, target):
        rows = table[ids] - target[ids]
        new, _ = res_step(table, state, ids, rows)
        return new - table, jnp.mean(jnp.square(rows))

    @jax.jit
    def part(table, state, uids, counts, target):
        rows = counts[:, None] * (table[uids] - target[uids])
        return ref_step(table, state, uids, rows)

    @jax.jit
    def distance(new, old, upd):
        ref_upd = new - old
        return (jnp.linalg.norm(upd - ref_upd)
                / jnp.maximum(jnp.linalg.norm(ref_upd), 1e-30))

    losses, dists = [], []
    for t in range(steps):
        ids = np.asarray(data.batch(t)["tokens"]).reshape(-1)
        upd, loss = resolved(table, state, jnp.asarray(ids, jnp.int32),
                             target)
        losses.append(float(loss))
        uids, counts = np.unique(ids, return_counts=True)
        old, lo, start = table, 0, state
        while lo < uids.size:
            n = 1 << min(12, (uids.size - lo).bit_length() - 1)
            table, state = part(
                table, dict(state, step=start["step"]),
                jnp.asarray(uids[lo:lo + n], jnp.int32),
                jnp.asarray(counts[lo:lo + n], jnp.float32), target)
            lo += n
        dists.append(float(distance(table, old, upd)))
    return losses, dists


def sparse_phase(name: str, dim: int, dtype: str, seed: int = 0) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.core.optimizers import SketchHParams
    from repro.kernels import registry
    from repro.launch import train
    from repro.train.steps import sparse_embedding_stores
    report = train.run(_sparse_argv(dim, dtype, ["--seed", str(seed)]))
    _report_run(name, report)
    _check(name, report.rc == 0 and len(report.history) == SPARSE_STEPS,
           "launcher exit 0: finite losses, last window below the first")

    hp = SketchHParams(compression=5.0, dtype=jnp.dtype(dtype))
    m_st, v_st = sparse_embedding_stores(SPARSE_ROWS, dim, hparams=hp)
    backend = registry.resolve("pair", "adam_rows", "auto",
                               specs=(m_st.spec, v_st.spec))
    ran = {r[2] for r in report.backends if r[:2] == ("pair", "adam_rows")}
    _check(name, ran == {backend}, f"the step ran {sorted(ran)}, the "
                                   f"backend the registry names ({backend})")
    _one_step_vs_ref(name, m_st.spec, v_st.spec, backend, dim, seed)

    t0 = time.perf_counter()
    ref, dists = _ref_run(hp, backend, dim, seed, 1e-3, SPARSE_STEPS)
    got = [h["loss"] for h in report.history]
    _say(name, f"ref run ({time.perf_counter() - t0:.1f} s incl. compile) "
               f"losses " + " ".join(f"{x:.6g}" for x in ref))
    _say(name, f"per step |update - ref update| / |ref update| from the "
               f"ref state: " + " ".join(f"{d:.3f}" for d in dists))
    w = min(10, max(1, SPARSE_STEPS // 2))
    drop = (np.mean(got[:w]) - np.mean(got[-w:]),
            np.mean(ref[:w]) - np.mean(ref[-w:]))
    _say(name, f"first loss {got[0]:.8g} vs ref {ref[0]:.8g}; window-mean "
               f"loss drop {drop[0]:.6g} vs ref {drop[1]:.6g}")
    _check(name, abs(got[0] - ref[0]) <= 1e-6 * abs(ref[0]),
           "same first loss as ref (same table, target and batch)")
    _check(name, max(dists) <= NOISE_ENVELOPE,
           f"every step's update within the {NOISE_ENVELOPE} "
           f"estimator-noise envelope of ref")
    _check(name, drop[1] > 0, "the ref run's loss falls too")
    _check(name, abs(drop[0] - drop[1]) <= NOISE_ENVELOPE * drop[1],
           f"loss trajectories agree: window-mean drops within "
           f"{NOISE_ENVELOPE} of ref's")


def lm_phase(device) -> None:
    import numpy as np
    from repro.launch import train
    report = train.run(LM_ARGS)
    _report_run("lm", report)
    _say("lm", f"peak bytes in use: {_peak_bytes(device)}")
    losses = np.array([h["loss"] for h in report.history])
    _check("lm", bool(np.isfinite(losses).all())
           and losses[-1] < losses[0] and report.rc == 0,
           "finite losses, last below first")
    _check("lm", any(r[2] == "tiled" for r in report.backends),
           "the vocab table's update_read ran the tiled kernel")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def sharded_phase(devices) -> None:
    """The dp x shard step through the launcher, then the first-moment
    parity of that step against the single-chip step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType
    from repro.core.optimizers import SketchHParams
    from repro.distributed import sharding as shd
    from repro.launch import train
    from repro.train.steps import make_sparse_embedding_step
    name = "dp x shard"
    report = train.run(_sparse_argv(128, "float32",
                                    ["--dp", "--sketch-shards", "2"]))
    _report_run(name, report)
    _check(name, report.rc == 0, "launcher exit 0 on the (2, 2) mesh")

    mesh = jax.make_mesh((2, 2), ("data", "model"), devices=devices,
                         axis_types=(AxisType.Auto,) * 2)
    # dyadic betas + integer gradient rows: every sum is exact, so the
    # psum'd, slab-routed first moment must equal the one-chip step's
    kw = dict(lr=1e-2, b1=0.5, b2=0.5)
    init_fn, sh_step, sh_opt = make_sparse_embedding_step(
        SPARSE_ROWS, 128, dp_axis="data", mesh=mesh, sketch_shards=2,
        hparams=SketchHParams(compression=5.0), **kw)
    # the single-chip step with the batch semantics the sharded body has
    # (one whole-batch tile; 'auto' on a chip streams across tiles)
    _, one_step, one_opt = make_sparse_embedding_step(
        SPARSE_ROWS, 128, hparams=SketchHParams(compression=5.0,
                                                backend="xla"), **kw)
    table = init_fn(jax.random.PRNGKey(0))
    with shd.active_mesh(mesh):
        sh_state = jax.device_put(
            sh_opt.init(), shd.named(mesh, shd.sketch_state_specs(
                jax.eval_shape(sh_opt.init))))
        sh_jit = jax.jit(sh_step)
        t_sh = jax.device_put(table, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))
    one_jit = jax.jit(one_step)
    t_one = jax.device_put(table, devices[0])
    one_state = jax.device_put(one_opt.init(), devices[0])
    rng = np.random.RandomState(0)
    k = SPARSE_BATCH * SPARSE_SEQ
    for step in range(3):
        ids = jnp.asarray(rng.zipf(1.1, size=k) % SPARSE_ROWS, jnp.int32)
        rows = jnp.asarray(rng.randint(-3, 4, size=(k, 128)), jnp.float32)
        with shd.active_mesh(mesh):
            t_sh, sh_state = sh_jit(t_sh, sh_state, ids, rows)
        t_one, one_state = one_jit(t_one, one_state, jax.device_put(
            ids, devices[0]), jax.device_put(rows, devices[0]))
        m_sh = np.asarray(sh_state["m"])
        m_one = np.asarray(one_state["m"])
        diff = int(np.count_nonzero(m_sh != m_one))
        _say(name, f"step {step + 1}: first-moment cells differing from "
                   f"the single-chip step: {diff} of {m_one.size:,}")
        _check(name, diff == 0, f"first moment bit-identical at step "
                                f"{step + 1}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: sparse + LM phases on one chip; 4: only the "
                         "dp x shard sparse step and its comparison")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        _fail(f"no src/repro next to {__file__}: run from a checkout")
    devices = _require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    _say("setup", f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    _say("setup", f"devices: {len(devices)} x {dev.device_kind} "
                  f"({dev.platform})")

    if args.chips == 4:
        phases = [("dp x shard", lambda: sharded_phase(devices))]
    else:
        phases = [("lm", lambda: lm_phase(dev)),
                  ("sparse dim128 f32",
                   lambda: sparse_phase("sparse dim128 f32", 128,
                                        "float32")),
                  ("sparse dim64 f32",
                   lambda: sparse_phase("sparse dim64 f32", 64, "float32")),
                  ("sparse dim128 bf16",
                   lambda: sparse_phase("sparse dim128 bf16", 128,
                                        "bfloat16"))]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # noqa: BLE001 — reported, and the run fails
            traceback.print_exc()
            failed.append(name)
        _say(name, f"phase wall time {time.perf_counter() - t0:.1f} s")
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""The TPU compiler accepts the tiled kernels at the shapes the main path
sends them — compiled here for a described (not attached) TPU v5e.

Interpret mode cannot show this: kernels that passed every interpreter
test were refused by Mosaic for unaligned row slices, for a sort in the
median, and for more SMEM than a chip has.  Each case lowers and compiles
for one chip of a described ``v5e:2x2`` topology; nothing runs.  The
topology is described inside a module fixture (never at import), and the
cases skip where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels as K
from repro.core import sketch as cs
from repro.core.optimizers import SketchHParams
from repro.kernels import registry
from repro.kernels.cs_adam_tiled import cs_adam_tiled
from repro.kernels.cs_ema_tiled import cs_ema_tiled


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels_in(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# (depth, width, dim, rows per batch): real widths, the deduped 65,536-id
# batch, the 151,936-row vocab, depths other than 1 and 3, Moonlight's
# 163,840-row vocab at hidden size 2,048
ADAM_SHAPES = [(3, 16384, 128, 1024), (3, 16384, 128, 16384),
               (1, 65536, 128, 1024), (3, 65536, 128, 32768),
               (4, 16384, 128, 1024), (5, 16384, 128, 1024),
               (3, 16384, 256, 1024), (3, 16384, 512, 1024),
               (3, 16384, 896, 1024), (3, 29952, 896, 151936),
               (3, 11008, 2048, 16384)]


@pytest.mark.parametrize("depth,width,dim,k", ADAM_SHAPES)
def test_cs_adam_tiled_compiles(one_chip, depth, width, dim, k):
    f32, i32 = jnp.float32, jnp.int32

    def step(M, V, bm, sm, bv, g):
        return cs_adam_tiled(M, V, bm, sm, bv, g, lr=1e-3, b1=0.9,
                             b2=0.999, eps=1e-8, bc1=0.1, bc2=0.001,
                             n_valid=k - 3)

    compiled = jax.jit(step).lower(
        _sds((depth, width, dim), f32, one_chip),
        _sds((depth, width, dim), f32, one_chip),
        _sds((depth, k), i32, one_chip), _sds((depth, k), f32, one_chip),
        _sds((depth, k), i32, one_chip),
        _sds((k, dim), f32, one_chip)).compile()
    assert _kernels_in(compiled) >= 1


@pytest.mark.parametrize("k", [32768, 442368])
def test_cs_adam_tiled_compiles_with_live_bound(one_chip, k):
    """``n_valid`` traced and below ``k``, over several calls (7 at
    32,768 rows, 82 at cat_21's 442,368): the kernel's guard around each
    tile's work and its clamped gradient block, inside the loop of calls."""
    f32, i32 = jnp.float32, jnp.int32
    depth, width, dim = 3, 666880, 128

    def step(M, V, bm, sm, bv, g, n_valid):
        return cs_adam_tiled(M, V, bm, sm, bv, g, lr=1e-3, b1=0.9,
                             b2=0.999, eps=1e-8, bc1=0.1, bc2=0.001,
                             n_valid=n_valid)

    compiled = jax.jit(step).lower(
        _sds((depth, width, dim), f32, one_chip),
        _sds((depth, width, dim), f32, one_chip),
        _sds((depth, k), i32, one_chip), _sds((depth, k), f32, one_chip),
        _sds((depth, k), i32, one_chip), _sds((k, dim), f32, one_chip),
        _sds((), i32, one_chip)).compile()
    text = compiled.as_text()
    assert _kernels_in(compiled) >= 1
    assert " while(" in text


EMA_SHAPES = [(3, 16384, 128, 1024, "float32", True),
              (3, 16384, 128, 1024, "float32", False),
              (3, 16384, 128, 1024, "bfloat16", True),
              (3, 16384, 896, 4096, "bfloat16", False),
              (5, 16384, 128, 1024, "float32", True),
              (3, 16384, 512, 32768, "float32", True)]


@pytest.mark.parametrize("depth,width,dim,k,dtype,signed", EMA_SHAPES)
def test_cs_ema_tiled_compiles(one_chip, depth, width, dim, k, dtype,
                               signed):
    f32 = jnp.float32
    seed = jnp.uint32(3) if dtype == "bfloat16" else None

    def step(S, b, s, x, m):
        return cs_ema_tiled(S, b, s if signed else None, x, m, beta=0.9,
                            scale=0.1, sr_seed=seed)

    compiled = jax.jit(step).lower(
        _sds((depth, width, dim), jnp.dtype(dtype), one_chip),
        _sds((depth, k), jnp.int32, one_chip),
        _sds((depth, k), f32, one_chip), _sds((k, dim), f32, one_chip),
        _sds((k, 1), f32, one_chip)).compile()
    assert _kernels_in(compiled) >= 1


@pytest.mark.parametrize("signed", [True, False])
def test_qwen2_vocab_dense_path_compiles(one_chip, signed):
    """The LM dense path's fused update_read over qwen2-0.5b's whole
    151,936 x 896 vocab table (the sketch width the planner picks for it
    at the 'config' budget), as ``--store-backend auto`` resolves it on a
    TPU."""
    n, dim = 151936, 896
    spec = cs.SketchSpec(depth=3, width=29952, dim=dim, signed=signed,
                         seed=1)
    ids = jnp.arange(n, dtype=jnp.int32)

    def step(S, x):
        return K.update_read(spec, S, ids, x, beta=0.9, scale=0.1,
                             backend="tiled")

    compiled = jax.jit(step).lower(
        _sds(spec.shape, jnp.float32, one_chip),
        _sds((n, dim), jnp.float32, one_chip)).compile()
    assert _kernels_in(compiled) >= 1


def _compile_sparse_step(one_chip, k):
    from repro.train.steps import make_sparse_embedding_step
    n, dim = 1 << 20, 128
    _, step_fn, opt = make_sparse_embedding_step(
        n, dim, hparams=SketchHParams(compression=5.0, backend="tiled"))
    state = jax.eval_shape(opt.init)
    state = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), state)
    with registry.recording() as rec:
        lowered = jax.jit(step_fn).lower(
            _sds((n, dim), jnp.float32, one_chip), state,
            _sds((k,), jnp.int32, one_chip),
            _sds((k, dim), jnp.float32, one_chip))
    assert {r[2] for r in rec} == {"tiled"}
    assert _kernels_in(lowered.compile()) >= 1


def test_sparse_step_compiles_with_tiled(one_chip):
    """The jitted sparse-embedding step on a 1,048,576 x 128 table with a
    65,536-id batch.  ``auto`` resolves by the host this test runs on (a
    CPU), so the test names 'tiled' — what ``auto`` picks on a TPU."""
    _compile_sparse_step(one_chip, 65536)


def test_sparse_step_compiles_at_cat21_ids(one_chip):
    """The same step with cat_21's 442,368 ids a step: 82 calls of the
    kernel, a traced number of their tiles live."""
    _compile_sparse_step(one_chip, 442368)


# (dim, cell dtype) -> what 'auto' runs on a TPU for the sparse-rows pair
# op and for the dense-path update_read
RESOLVE_CASES = [(128, "float32", "tiled", "tiled"),
                 (896, "float32", "tiled", "tiled"),
                 (64, "float32", "xla", "xla"),
                 (128, "bfloat16", "xla", "tiled"),
                 (64, "bfloat16", "xla", "xla"),
                 (128, "int8", "xla", "xla")]


@pytest.mark.parametrize("dim,dtype,pair,dense", RESOLVE_CASES)
def test_registry_resolves_by_spec_on_tpu(monkeypatch, dim, dtype, pair,
                                          dense):
    """On a TPU host 'auto' names 'xla' for sketches the kernels refuse
    (dim not a multiple of 128, int8 cells, bf16 in the f32-only pair
    kernel), and an explicit 'tiled' for them raises instead of running
    another backend."""
    monkeypatch.setattr(registry.jax, "default_backend", lambda: "tpu")
    mk = dict(compression=4.0, width_multiple=256, dtype=jnp.dtype(dtype))
    spec_m = cs.for_param((65536, dim), signed=True, seed=1, **mk)
    spec_v = cs.for_param((65536, dim), signed=False, seed=2, **mk)
    assert registry.resolve("pair", "adam_rows", "auto",
                            specs=(spec_m, spec_v)) == pair
    assert registry.resolve("sketch", "update_read", None,
                            specs=(spec_m,)) == dense
    for kind, op, want, specs in (("pair", "adam_rows", pair,
                                   (spec_m, spec_v)),
                                  ("countmin", "update_read", dense,
                                   (spec_v,))):
        if want == "tiled":
            assert registry.resolve(kind, op, "tiled", specs) == "tiled"
        else:
            with pytest.raises(ValueError, match="cannot run"):
                registry.resolve(kind, op, "tiled", specs)


def test_expert_grouped_matmuls_compile_at_moonlight_widths(one_chip,
                                                           monkeypatch):
    """One dispatch chunk of Moonlight's held experts (24,576 (token, slot)
    rows, 8 experts of 2,048 x 1,408) forward and backward through the
    megablox kernel, at the tiles ``moe._gmm_tiling`` picks."""
    from repro import configs
    from repro.models import moe
    monkeypatch.setattr(moe, "grouped_matmul_backend", lambda: "gmm")
    cfg = configs.get("moonlight-16b-a3b-ep8")
    bf, i32 = jnp.bfloat16, jnp.int32
    rows, d, f = 4096 * cfg.top_k, cfg.d_model, cfg.d_ff

    def chunk(xs, wg, wu, wd, sizes):
        h = jax.nn.silu(moe.grouped_matmul(xs, wg, sizes)) * \
            moe.grouped_matmul(xs, wu, sizes)
        return jnp.sum(moe.grouped_matmul(h, wd, sizes).astype(
            jnp.float32))

    compiled = jax.jit(jax.grad(chunk, (0, 1, 2, 3))).lower(
        _sds((rows, d), bf, one_chip), _sds((8, d, f), bf, one_chip),
        _sds((8, d, f), bf, one_chip), _sds((8, f, d), bf, one_chip),
        _sds((8,), i32, one_chip)).compile()
    assert _kernels_in(compiled) >= 6


@pytest.mark.parametrize("b,s,hq,hkv,hd,dv", [(2, 8192, 16, 16, 192, 128),
                                              (1, 1024, 14, 2, 64, 64)])
def test_attention_kernel_compiles(one_chip, b, s, hq, hkv, hd, dv):
    """The splash attention path of ``flash_attention``, forward and
    backward: Moonlight's latent attention at 8,192 (a 192-wide q·k
    contraction against a 128-wide v) and a GQA layer of 64-wide heads."""
    from repro.models import attention as attn
    bf = jnp.bfloat16

    def loss(q, k, v):
        o = attn.kernel_attention(q, k, v, block=attn.kernel_block(s))
        return jnp.sum(o.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        _sds((b, s, hq, hd), bf, one_chip), _sds((b, s, hkv, hd), bf,
                                                 one_chip),
        _sds((b, s, hkv, dv), bf, one_chip)).compile()
    text = compiled.as_text()
    for phase in ("fwd", "dq", "dkv"):
        assert f"splash_mha_{phase}" in text, phase

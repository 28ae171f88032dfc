"""The Pallas attention path of ``flash_attention``: the splash kernel
(under the interpreter) against ``chunked_attention``, forward and
gradients, at the latent-attention widths and under GQA; which core the
selector picks for what it observes; and the scan left as it was wherever
the selector sends a call there."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import sharding
from repro.models import attention as attn


def _bf16_normal(key, shape):
    """Normal values a bf16 cast keeps exactly, as f32."""
    x = jax.random.normal(jax.random.PRNGKey(key), shape)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# -- the kernel against the reference ---------------------------------------

@pytest.mark.parametrize("hq,hkv,hd,dv", [(2, 2, 192, 128), (4, 2, 192, 128)])
def test_kernel_matches_reference(hq, hkv, hd, dv):
    """Causal, s 512 in blocks of 128: 10 of the 16 blocks of a head live.
    The kernel rounds the scaled q and the probabilities to bf16 and
    accumulates in f32, so it sits within bf16's rounding of the f32
    reference (measured 3.3e-3 of the norm), and far from any mask error
    (a non-causal or shifted mask is off by tens of percent)."""
    b, s = 2, 512
    q, k = _bf16_normal(0, (b, s, hq, hd)), _bf16_normal(1, (b, s, hkv, hd))
    v, w = _bf16_normal(2, (b, s, hkv, dv)), _bf16_normal(3, (b, s, hq, dv))

    def kernel(q, k, v):
        return attn.kernel_attention(q, k, v, block=128, interpret=True)

    def plain(q, k, v):
        return attn.chunked_attention(q, k, v, causal=True, chunk=128)

    o, o_ref = kernel(q, k, v), plain(q, k, v)
    assert o.shape == (b, s, hq, dv) and o.dtype == q.dtype
    assert _rel(o, o_ref) < 1e-2
    np.testing.assert_allclose(o, o_ref, atol=5e-2)
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v) * w)
    grads = jax.grad(loss(kernel), (0, 1, 2))(q, k, v)
    grads_ref = jax.grad(loss(plain), (0, 1, 2))(q, k, v)
    for name, g, g_ref in zip("qkv", grads, grads_ref):
        assert g.shape == g_ref.shape, name
        assert _rel(g, g_ref) < 1e-2, name


# -- the selector -------------------------------------------------------------

CELL = dict(platform="tpu", causal=True, sq=8192, skv=8192, q_offset=0,
            hq=16, hkv=16, mesh={"data": 1, "model": 1}, manual=False)


@pytest.mark.parametrize("change,kernel", [
    ({}, True),                                     # the LM cell's layer
    ({"mesh": None}, True),                         # no mesh at all
    ({"hq": 14, "hkv": 2, "sq": 4096, "skv": 4096}, True),     # GQA
    ({"sq": 384, "skv": 384}, True),                # a 128-block length
    ({"platform": "cpu"}, False),
    ({"sq": 8200, "skv": 8200}, False),             # ragged length
    ({"q_offset": 128}, False),                     # prefill continuation
    ({"sq": 512}, False),                           # sq != skv
    ({"causal": False}, False),                     # encoder / cross attn
    ({"hq": 16, "hkv": 3}, False),                  # no whole GQA groups
    ({"mesh": {"data": 1, "model": 2}}, False),     # TP-sharded mesh
    ({"mesh": {"data": 2, "model": 1}}, False),     # GSPMD would split it
    ({"mesh": {"data": 2, "model": 1}, "manual": True}, True),  # shard_map
])
def test_selector(change, kernel):
    assert attn.use_kernel(**dict(CELL, **change)) is kernel


@pytest.mark.parametrize("s,block", [(8192, 1024), (1024, 1024), (1536, 512),
                                     (768, 256), (384, 128), (8200, None),
                                     (64, None)])
def test_kernel_block(s, block):
    assert attn.kernel_block(s) == block


# -- flash_attention's dispatch ------------------------------------------------

def _qkv(s=256, hq=4, hkv=2):
    return (_bf16_normal(0, (2, s, hq, 24)), _bf16_normal(1, (2, s, hkv, 24)),
            _bf16_normal(2, (2, s, hkv, 16)))


def test_off_the_tpu_the_scan_runs_unchanged():
    q, k, v = _qkv()
    for causal in (True, False):
        got = attn.flash_attention(q, k, v, causal, 64)
        want = attn._flash_core(q, k, v, causal, 64, 0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _fake_mesh(**sizes):
    return types.SimpleNamespace(
        axis_names=tuple(sizes),
        devices=types.SimpleNamespace(shape=tuple(sizes.values())))


@pytest.fixture
def on_tpu(monkeypatch):
    """``flash_attention`` told it runs on a TPU, with a spy in place of
    the kernel (the kernel itself runs on the CPU only under the
    interpreter, tested above)."""
    calls = []

    def spy(q, k, v, *, block, interpret=False):
        calls.append(block)
        return jnp.zeros(q.shape[:3] + v.shape[-1:], q.dtype)

    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attn, "kernel_attention", spy)
    return calls


def test_causal_self_attention_on_a_tpu_takes_the_kernel(on_tpu,
                                                         monkeypatch):
    q, k, v = _qkv()
    attn.flash_attention(q, k, v, True, 64)
    monkeypatch.setattr(sharding, "current_mesh",
                        lambda: _fake_mesh(data=1, model=1))
    attn.flash_attention(q, k, v, True, 64)
    assert on_tpu == [256, 256]


@pytest.mark.parametrize("mesh,causal,q_offset", [
    (_fake_mesh(data=1, model=2), True, 0), (None, False, 0),
    (None, True, 64)])
def test_a_call_sent_to_the_scan_is_the_scan(on_tpu, monkeypatch, mesh,
                                             causal, q_offset):
    """On a TPU too, what the selector sends to the scan gives what the
    scan gives, bit for bit."""
    q, k, v = _qkv()
    monkeypatch.setattr(sharding, "current_mesh", lambda: mesh)
    got = attn.flash_attention(q, k, v, causal, 64, q_offset)
    want = attn._flash_core(q, k, v, causal, 64, q_offset)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert on_tpu == []

"""Pallas kernel validation: shape/dtype sweeps in interpret mode against
the pure-jnp oracles in repro/kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.chunk_scan import chunk_scan
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.router_scores import router_scores


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,dh", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA 4:1
    (1, 128, 4, 1, 128),     # MQA, MXU-aligned head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, S, H, KV, dh, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, S, H, dh), dtype)
    k = rand(ks[1], (B, S, KV, dh), dtype)
    v = rand(ks[2], (B, S, KV, dh), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_flash_attention_sliding_window():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, KV, dh, w = 1, 256, 4, 2, 64, 64
    q = rand(ks[0], (B, S, H, dh), jnp.float32)
    k = rand(ks[1], (B, S, KV, dh), jnp.float32)
    v = rand(ks[2], (B, S, KV, dh), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=w, block_q=64,
                          block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,dh", [
    (2, 128, 4, 4, 64),
    (3, 256, 8, 2, 64),
    (1, 512, 4, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, S, H, KV, dh, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = rand(ks[0], (B, H, dh), dtype)
    k = rand(ks[1], (B, S, KV, dh), dtype)
    v = rand(ks[2], (B, S, KV, dh), dtype)
    pos = jax.random.randint(ks[3], (B,), 0, S)
    out = decode_attention(q, k, v, pos, block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_decode_attention_ring_buffer():
    """window > 0: every slot valid once pos ≥ S_cache."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, S, H, KV, dh = 2, 128, 4, 2, 64
    q = rand(ks[0], (B, H, dh), jnp.float32)
    k = rand(ks[1], (B, S, KV, dh), jnp.float32)
    v = rand(ks[2], (B, S, KV, dh), jnp.float32)
    pos = jnp.asarray([40, 4000])          # one pre-wrap, one post-wrap
    out = decode_attention(q, k, v, pos, window=S, block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos, window=S)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pos_val", [126, 127, 128, 129, 255, 256])
def test_decode_attention_ring_wrap_boundary(pos_val):
    """The ``pos >= s_cache`` validity flip in ``_decode_kernel`` at the
    exact wrap boundary: pos = S-1 is the last masked step (slots > pos
    still invalid), pos = S is the first fully-valid step, and every later
    position stays fully valid. Checked against the jnp oracle so a fence
    error on either side of the flip fails loudly."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    B, S, H, KV, dh = 2, 128, 4, 2, 64
    q = rand(ks[0], (B, H, dh), jnp.float32)
    k = rand(ks[1], (B, S, KV, dh), jnp.float32)
    v = rand(ks[2], (B, S, KV, dh), jnp.float32)
    pos = jnp.asarray([pos_val, max(pos_val - 1, 0)])
    out = decode_attention(q, k, v, pos, window=S, block_k=64,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos, window=S)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if pos_val >= S:
        # post-wrap the ring is position-independent: every slot attends
        full = ref.decode_attention_ref(q, k, v,
                                        jnp.full((B,), 10 * S), window=S)
        np.testing.assert_allclose(np.asarray(out)[:1],
                                   np.asarray(full)[:1],
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pos_val", [0, 63, 64, 127])
def test_decode_attention_full_cache_boundary(pos_val):
    """window == 0 (full cache): validity is strictly ``idx <= pos`` — in
    particular the final position S-1 attends over the whole cache and
    block boundaries (block_k=64) introduce no fence error."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, S, H, KV, dh = 2, 128, 4, 2, 64
    q = rand(ks[0], (B, H, dh), jnp.float32)
    k = rand(ks[1], (B, S, KV, dh), jnp.float32)
    v = rand(ks[2], (B, S, KV, dh), jnp.float32)
    pos = jnp.asarray([pos_val, S - 1 - pos_val])
    out = decode_attention(q, k, v, pos, block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# router scores
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,K,D", [(8, 2, 32), (100, 6, 64), (256, 16, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tau", [1.0, 10.0])
def test_router_scores(B, K, D, dtype, tau):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    x = rand(ks[0], (B, D), dtype)
    c = rand(ks[1], (K, D), dtype)
    out = router_scores(x, c, tau, block_b=64, interpret=True)
    want = ref.router_scores_ref(x, c, tau)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(out, np.float32).sum(-1), 1.0,
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


# ---------------------------------------------------------------------------
# chunk scan (mLSTM / SSD intra-chunk)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,NC,L,H,dk,dv", [
    (1, 2, 64, 2, 32, 32),
    (2, 4, 32, 4, 16, 48),   # dk != dv (Mamba2: N != P)
    (1, 1, 128, 2, 64, 65),  # odd dv (mLSTM normalizer channel)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunk_scan(B, NC, L, H, dk, dv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    qc = rand(ks[0], (B, NC, L, H, dk), dtype)
    kc = rand(ks[1], (B, NC, L, H, dk), dtype)
    vc = rand(ks[2], (B, NC, L, H, dv), dtype)
    # realistic decays: cumulative sums of negative log-gates
    logg = -jnp.abs(jax.random.normal(ks[3], (B, NC, L, H))) * 0.1
    cum = jnp.cumsum(logg, axis=2)
    intra, kv = chunk_scan(qc, kc, vc, cum, interpret=True)
    intra_ref, kv_ref = ref.chunk_scan_ref(qc, kc, vc, cum)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(intra), np.asarray(intra_ref), **tol)
    np.testing.assert_allclose(np.asarray(kv), np.asarray(kv_ref), **tol)


# ---------------------------------------------------------------------------
# end-to-end: model forward with kernels == model forward without
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen3_8b", "xlstm_125m", "zamba2_2_7b"])
def test_model_with_kernels_matches_jnp(arch):
    from repro.configs.base import get_smoke_config
    from repro.models import build_model
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 64
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          cfg.vocab),
             "labels": jnp.zeros((B, S), jnp.int32)}
    ref_logits = model.forward(params, batch, use_kernel=False)
    k_logits = model.forward(params, batch, use_kernel=True)
    np.testing.assert_allclose(np.asarray(k_logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_kernel_mode_follows_platform(backend, interpret, monkeypatch):
    """Kernels are interpreted only on the CPU and compiled on a TPU; any
    other platform is refused, never silently interpreted."""
    from repro.kernels import ops
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="neither"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret

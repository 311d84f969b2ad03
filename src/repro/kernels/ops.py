"""Public jit'd wrappers around the Pallas kernels.

The platform picks the mode. On a TPU every kernel compiles to Mosaic. On
the CPU (the test suite runs with ``JAX_PLATFORMS=cpu``) every kernel runs
with ``interpret=True``: the body executes per grid step, validating the
kernel's logic against the ref.py oracles but not the chip's tiling rules
(tests/test_tpu_compile.py compiles them for a TPU). Any other platform is
an error rather than a silent fall back to the interpreter.
"""
from __future__ import annotations

import functools

import jax

from . import chunk_scan as _chunk
from . import decode_attention as _decode
from . import flash_attention as _flash
from . import flash_attention_bwd as _flash_bwd_mod
from . import router_scores as _router


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for a TPU and are interpreted on "
            f"the CPU; backend {backend!r} is neither — serve with "
            f"use_kernel=False there")
    return backend == "cpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_vjp(q, k, v, causal, window, block_q, block_k):
    return _flash.flash_attention(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


def _flash_fwd(q, k, v, causal, window, block_q, block_k):
    out, lse = _flash.flash_attention_with_lse(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=_interpret())
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, block_q, block_k, res, dout):
    """Blocked Pallas backward from the saved LSE (never materializes the
    S² matrix in HBM) — see kernels/flash_attention_bwd.py."""
    q, k, v, out, lse = res
    return _flash_bwd_mod.flash_attention_bwd(
        q, k, v, out, lse, dout, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=_interpret())


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """Differentiable: forward runs the Pallas kernel; backward uses the
    saved-LSE flash gradient (custom_vjp)."""
    return _flash_vjp(q, k, v, causal, window, block_q, block_k)


@functools.partial(jax.jit, static_argnames=("window", "block_k"))
def decode_attention(q, k, v, pos, *, window: int = 0, block_k: int = 256):
    return _decode.decode_attention(q, k, v, pos, window=window,
                                    block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "blocks_per_step"))
def paged_decode_attention(q, k_pool, v_pool, pos, block_tables, *,
                           window: int = 0, blocks_per_step: int = 1):
    return _decode.paged_decode_attention(q, k_pool, v_pool, pos,
                                          block_tables, window=window,
                                          blocks_per_step=blocks_per_step,
                                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("blocks_per_step",))
def paged_verify_attention(q, k_pool, v_pool, pos, block_tables, *,
                           blocks_per_step: int = 1):
    return _decode.paged_verify_attention(q, k_pool, v_pool, pos,
                                          block_tables,
                                          blocks_per_step=blocks_per_step,
                                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("blocks_per_step",))
def chunk_prefill_attention(q, k_pool, v_pool, start, block_table, *,
                            blocks_per_step: int = 1):
    return _decode.chunk_prefill_attention(q, k_pool, v_pool, start,
                                           block_table,
                                           blocks_per_step=blocks_per_step,
                                           interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("temperature", "block_b"))
def router_scores(x, centroids, temperature: float, *, block_b: int = 256):
    return _router.router_scores(x, centroids, temperature, block_b=block_b,
                                 interpret=_interpret())


@jax.jit
def chunk_scan(qc, kc, vc, cum):
    return _chunk.chunk_scan(qc, kc, vc, cum, interpret=_interpret())

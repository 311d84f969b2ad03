"""Kernel microbenchmarks: us/call for each Pallas kernel and for the jnp
reference, plus the derived ratio. The kernels run compiled on a TPU and
interpreted on the CPU; a CPU run's times are structural only and say
nothing about the chip."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def run(_settings=None):
    key = jax.random.PRNGKey(0)
    rows = []
    dev = jax.devices()[0]
    ker = "interpret" if dev.platform == "cpu" else "compiled"
    xla = f"xla_{dev.platform}"

    B, S, H, KV, dh = 1, 256, 4, 2, 64
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, dh), jnp.float32)
    rows.append(("flash_attention_pallas",
                 _time(lambda a, b, c: ops.flash_attention(a, b, c), q, k, v),
                 ker))
    rows.append(("flash_attention_ref",
                 _time(jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c)),
                       q, k, v), xla))

    qd = q[:, 0]
    pos = jnp.asarray([S - 1])
    rows.append(("decode_attention_pallas",
                 _time(lambda a, b, c, p: ops.decode_attention(a, b, c, p),
                       qd, k, v, pos), ker))
    rows.append(("decode_attention_ref",
                 _time(jax.jit(lambda a, b, c, p:
                               ref.decode_attention_ref(a, b, c, p)),
                       qd, k, v, pos), xla))

    x = jax.random.normal(ks[3], (256, 128), jnp.float32)
    c = jax.random.normal(ks[0], (8, 128), jnp.float32)
    rows.append(("router_scores_pallas",
                 _time(lambda a, b: ops.router_scores(a, b, 10.0), x, c),
                 ker))
    rows.append(("router_scores_ref",
                 _time(jax.jit(lambda a, b: ref.router_scores_ref(a, b, 10.0)),
                       x, c), xla))

    qc = jax.random.normal(ks[1], (1, 4, 64, 2, 32), jnp.float32)
    vc = jax.random.normal(ks[2], (1, 4, 64, 2, 32), jnp.float32)
    cum = jnp.cumsum(-jnp.abs(jax.random.normal(ks[3], (1, 4, 64, 2))) * 0.1,
                     axis=2)
    rows.append(("chunk_scan_pallas",
                 _time(lambda a, b, c_, d: ops.chunk_scan(a, b, c_, d),
                       qc, qc, vc, cum), ker))
    rows.append(("chunk_scan_ref",
                 _time(jax.jit(lambda a, b, c_, d:
                               ref.chunk_scan_ref(a, b, c_, d)),
                       qc, qc, vc, cum), xla))

    # paged decode: page-size x blocks-per-step sweep over one 128-position
    # logical span. bps > 1 folds several logical blocks into one grid
    # step (fewer grid steps, same DMA volume — past-horizon sub-tiles
    # clamp to a revisited index and skip their copy); every timed config
    # is first checked against the jnp oracle so the sweep can't quietly
    # drift from the definition.
    B, H, KV, dh, span = 4, 4, 2, 32, 128
    kp = jax.random.split(key, 3)
    qp = jax.random.normal(kp[0], (B, H, dh), jnp.float32)
    ppos = jnp.asarray([span - 1, span // 2, 7, 0][:B])
    # jit the reference once: wrapping a fresh lambda per loop iteration
    # defeats the trace cache and retraces every rep (repro-lint
    # retrace-hazard)
    jit_ref = jax.jit(ref.paged_decode_attention_ref)
    layer = jnp.int32(0)              # a one-layer pool
    for block in (8, 16, 32):
        NB = span // block
        P = B * NB + 2
        kpool = jax.random.normal(kp[1], (1, P, KV, block, dh), jnp.float32)
        vpool = jax.random.normal(kp[2], (1, P, KV, block, dh), jnp.float32)
        bt = jnp.arange(1, B * NB + 1, dtype=jnp.int32).reshape(B, NB)
        oracle = ref.paged_decode_attention_ref(qp, kpool, vpool, layer,
                                                ppos, bt)
        for bps in (1, 2, 4):
            got = ops.paged_decode_attention(qp, kpool, vpool, layer, ppos,
                                             bt, blocks_per_step=bps)
            assert jnp.allclose(got, oracle, atol=1e-5), (block, bps)
            rows.append((f"paged_decode_b{block}_bps{bps}_pallas",
                         _time(lambda a, b_, c_, p, t, n=bps:
                               ops.paged_decode_attention(
                                   a, b_, c_, layer, p, t,
                                   blocks_per_step=n),
                               qp, kpool, vpool, ppos, bt), ker))
        rows.append((f"paged_decode_b{block}_ref",
                     _time(jit_ref, qp, kpool, vpool, layer, ppos, bt),
                     xla))

    print(f"\n== Kernel microbenchmarks ({dev.platform} {dev.device_kind}; "
          f"kernels {ker}) ==")
    print("name,us_per_call,derived")
    for name, us, tag in rows:
        print(f"{name},{us:.1f},{tag}")
    return rows

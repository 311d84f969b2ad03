"""The plain reference of the deployment around the experts, and the
control's rounding. Imports nothing of the program.

* Eq. 28: the router's weights are the softmax over experts of the
  temperature times the cosine of the request's features with each
  centroid, filtered to its top-k and renormalised; top-1 serving takes
  the argmax expert with weight 1.
* Eq. 27: the served distribution is the router-weighted mixture of the
  experts' next-token distributions (for top-1, the chosen expert's own).
* ``quantize(x, "fp8")``: the control's rounding of a matrix product's
  operand to float8 e4m3 with one scale per tensor (W8A8), the step below
  the configurations' bfloat16 that a faster path would take.

Each architecture's own forward pass is in ``models/<architecture>.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0


def quantize(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def route(features, centroids, temperature: float, top_k: int,
          strategy: str) -> np.ndarray:
    """Eq. 28 weights (n, K), in float64 on the host. top-1 serving takes
    the argmax expert with weight 1."""
    x = features / np.linalg.norm(features, axis=-1, keepdims=True)
    c = centroids / np.linalg.norm(centroids, axis=-1, keepdims=True)
    s = temperature * (x @ c.T)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    if strategy == "top1":
        return np.eye(len(c))[np.argmax(p, -1)]
    keep = np.argsort(-p, -1)[:, :top_k]
    w = np.zeros_like(p)
    np.put_along_axis(w, keep, np.take_along_axis(p, keep, -1), -1)
    return w / w.sum(-1, keepdims=True)


@jax.jit
def mix(logps, weights):
    """Eq. 27: log of the weighted mixture of per-expert distributions.
    logps (K, n, V), weights (K,) -> (n, V)."""
    lw = jnp.log(jnp.maximum(weights, 1e-30))[:, None, None]
    return jax.scipy.special.logsumexp(logps + lw, axis=0)


@jax.jit
def gaps(ref, served, valid):
    """Per position: how far the served token's reference log-probability
    lies below the reference's best. ref (n, V), served (n,) int32."""
    g = ref.max(-1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    return jnp.where(valid, g, 0.0)

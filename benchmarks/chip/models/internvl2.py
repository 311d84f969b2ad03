"""InternVL2-2B as the benchmark serves it: its language model
(InternLM2-1.8B) behind the repository's stub image projector.

Everything the harness needs of one architecture, found by the name in a
configuration file's ``architecture``:

* ``model_config``: the program's ``ModelConfig`` from the file's sizes;
* ``layout``: the parameter tree the program serves (checked against the
  program's own ``jax.eval_shape(model.init)``), from which ``weights.py``
  makes the experts;
* ``make_logprobs_fn``: the plain reference, written from the published
  description in float32 ``jax.numpy`` with ``highest`` matmul precision,
  with no cache, batching or kernels, importing nothing of the program;
* ``decode_flops``/``chunk_flops``: model operations per token;
* ``REHEARSAL``: tiny sizes for the CPU rehearsal.

The reference, layer by layer:

    x   = [gelu_tanh(patches @ w1) @ w2 ; embedding[tokens]]
    h   = rms(x) * ln1;  q, k, v = h @ wq, h @ wk, h @ wv
    q,k = rope(q), rope(k)          (rotate-half, theta from the config)
    x  += causal_gqa_softmax(q k^T / sqrt(dh)) v @ wo
    h   = rms(x) * ln2;  x += (silu(h @ w_gate) * (h @ w_up)) @ w_down
    logits = (rms(x) * final_norm) @ unembed

``quant="fp8"`` rounds every matrix product's operands to float8 e4m3
(``reference.quantize``): the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import flops
from reference import quantize

REHEARSAL = {"num_hidden_layers": 2, "hidden_size": 64,
             "intermediate_size": 128, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
             "projector_input_size": 32, "num_image_token": 8}


def model_config(m: dict, name: str):
    """The program's configuration for these sizes."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        arch_id=name, family="vlm", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], d_head=m["head_dim"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=False, vision_dim=m["projector_input_size"],
        n_patches=m["num_image_token"], param_dtype=m["dtype"],
        compute_dtype=m["dtype"])


def layout(m: dict) -> dict:
    """The served parameter tree (layers stacked on a leading axis): name
    path -> (shape, fan_in) of every leaf; fan_in 0 marks a norm scale, -1
    the token embedding."""
    L, D, H, KV, dh = (m["num_hidden_layers"], m["hidden_size"],
                       m["num_attention_heads"], m["num_key_value_heads"],
                       m["head_dim"])
    F, V = m["intermediate_size"], m["vocab_size"]
    Dv = m["projector_input_size"]
    return {
        "embed": {"embedding": ((V, D), -1), "unembed": ((D, V), D)},
        "blocks": {
            "ln1": ((L, D), 0),
            "attn": {"wq": ((L, D, H, dh), D), "wk": ((L, D, KV, dh), D),
                     "wv": ((L, D, KV, dh), D), "wo": ((L, H, dh, D), H * dh)},
            "ln2": ((L, D), 0),
            "ffn": {"w_gate": ((L, D, F), D), "w_up": ((L, D, F), D),
                    "w_down": ((L, F, D), F)},
        },
        "final_norm": ((D,), 0),
        "projector": {"w1": ((Dv, D), Dv), "w2": ((D, D), D)},
    }


def _mm(a, w, quant, spec="sd,de->se"):
    return jnp.einsum(spec, quantize(a, quant),
                      quantize(w.astype(jnp.float32), quant))


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (S, heads, dh), position = row index."""
    S, _, dh = x.shape
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(w, tokens, patches, m, quant=None):
    """Final hidden states (S, D) of one sequence: ``patches`` (Np, Dv)
    float, then ``tokens`` (S - Np,) int32."""
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    H, KV, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    pr = w["projector"]
    img = _mm(jax.nn.gelu(_mm(patches.astype(jnp.float32), pr["w1"], quant),
                          approximate=True), pr["w2"], quant)
    txt = w["embed"]["embedding"][tokens].astype(jnp.float32)
    x = jnp.concatenate([img, txt], 0)
    S = x.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        a = p["attn"]
        q = _rope(_mm(h, a["wq"], quant, "sd,dhk->shk"), theta)
        k = _rope(_mm(h, a["wk"], quant, "sd,dhk->shk"), theta)
        v = _mm(h, a["wv"], quant, "sd,dhk->shk")
        q = q.reshape(S, KV, H // KV, dh)
        s = jnp.einsum("qkgd,skd->kgqs", q, k) * float(dh) ** -0.5
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, -1), v)
        x = x + _mm(o.reshape(S, H, dh), a["wo"], quant, "shk,hkd->sd")
        h = _rms(x, p["ln2"], eps)
        f = p["ffn"]
        g = jax.nn.silu(_mm(h, f["w_gate"], quant, "sd,df->sf"))
        u = _mm(h, f["w_up"], quant, "sd,df->sf")
        return x + _mm(g * u, f["w_down"], quant, "sf,fd->sd"), None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    return _rms(x, w["final_norm"], eps)


def make_logprobs_fn(m: dict, quant=None):
    """Jitted ``(w, tokens, patches, rows) -> (len(rows), V)`` next-token
    log-probabilities of one expert at the positions ``rows``."""
    def f(w, tokens, patches, rows):
        with jax.default_matmul_precision("highest"):
            x = hidden(w, tokens, patches, m, quant)[rows]
            z = _mm(x, w["embed"]["unembed"], quant, "sd,dv->sv")
        return jax.nn.log_softmax(z, -1)
    return jax.jit(f)


def layer_matmul_flops(m: dict) -> int:
    """One token through one decoder layer's weight matrices."""
    D, H, KV, dh, F = (m["hidden_size"], m["num_attention_heads"],
                       m["num_key_value_heads"], m["head_dim"],
                       m["intermediate_size"])
    return 2 * (D * H * dh + 2 * D * KV * dh + H * dh * D + 3 * D * F)


def unembed_flops(m: dict) -> int:
    return 2 * m["hidden_size"] * m["vocab_size"]


def projector_flops(m: dict) -> int:
    """One image patch through the stub projector."""
    D, Dv = m["hidden_size"], m["projector_input_size"]
    return 2 * (Dv * D + D * D)


def chunk_flops(m: dict, start: int, length: int, final: bool) -> int:
    """One prefill chunk: ``length`` positions from ``start``, the image
    patches among them through the projector, and the next-token logits
    once, at the prompt's last position (``final``)."""
    L = m["num_hidden_layers"]
    keys = length * start + length * (length + 1) // 2
    img = max(0, min(start + length, m["num_image_token"]) - start)
    return (L * (length * layer_matmul_flops(m) + flops.attn_flops(m, keys))
            + img * projector_flops(m) + (unembed_flops(m) if final else 0))


def decode_flops(m: dict, pos: int) -> int:
    """One decoded token fed at position ``pos``."""
    L = m["num_hidden_layers"]
    return (L * (layer_matmul_flops(m) + flops.attn_flops(m, pos + 1))
            + unembed_flops(m))

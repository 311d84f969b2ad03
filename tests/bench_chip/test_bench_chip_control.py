"""The float8 control put in the program's place has to come out as not
correct through the benchmark's own comparison (CPU, ``MID`` sizes)."""
from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_bench_chip import ARCH, CELLS  # noqa: E402
import traffic  # noqa: E402


# --- the float8 control ------------------------------------------------------

MID = {"num_hidden_layers": 4, "hidden_size": 512, "intermediate_size": 2048,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
       "vocab_size": 16384, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
       "projector_input_size": 128, "num_image_token": 32}


def served_by_the_reference(seed):
    """A record of four finished requests at ``MID`` sizes whose tokens
    the float32 reference itself chose, greedily, one position at a time;
    with the configuration, mix and expert that made it."""
    import jax.numpy as jnp
    import weights
    V, Np = MID["vocab_size"], MID["num_image_token"]
    config = {"model": dict(MID, dtype="bfloat16"),
              "engine": {"cache_len": 160},
              "deployment": {"router": {"temperature": 10.0, "top_k": 1}}}
    spec_ = traffic.load("caption")
    spec_["prompt_tokens"] = {"dist": "uniform", "min": 40, "max": 60}
    spec_["output_tokens"] = {"dist": "uniform", "min": 40, "max": 50}
    mix = traffic.Mix(spec_, seed, 4, 1, 16, V, Np,
                      MID["projector_input_size"], seed)
    w = weights.make_expert_fn(ARCH.layout(MID), "bfloat16")(
        weights.expert_keys(seed, 1)[0])
    fn = ARCH.make_logprobs_fn(MID)
    T = config["engine"]["cache_len"] - Np
    reqs = {}
    for i in range(4):
        r = mix.request(i, 0)
        tokens, patches, feats = mix.content(r)
        seq = np.zeros(T, np.int32)
        seq[:len(tokens)] = tokens
        toks = []
        for k in range(r.max_new):
            pos = Np + len(tokens) - 1 + k
            lp = fn(w, jnp.asarray(seq), jnp.asarray(patches),
                    jnp.asarray([pos], jnp.int32))
            toks.append(int(jnp.argmax(lp[0])))
            if len(tokens) + k < T:
                seq[len(tokens) + k] = toks[-1]
        reqs[i] = {"idx": r.idx, "due": 0.5, "text_len": r.text_len,
                   "width": Np + r.text_len, "max_new": r.max_new,
                   "features": feats, "toks": toks, "reason": "length"}
    return {"t0": 0.0, "t1": 1.0, "reqs": reqs}, config, mix, w


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_is_not_correct(seed):
    """At a width the CPU holds, tokens the float32 reference chose pass
    the comparison that decides ``correct``, and the reference in float8
    put in the program's place fails it, under every cell's limits."""
    import check
    rec, config, mix, w = served_by_the_reference(seed)
    for cell in CELLS:
        out = check.compare(rec, config, mix, ARCH, lambda k: w, seed, 1,
                            "top1", cell, control=True)
        assert out["correct"] is True, out["lines"]
        assert out["checks"]["max_gap"]["value"] == 0.0
        assert out["control_correct"] is False, out["lines"]
        assert out["control_gap"] > out["checks"]["max_gap"]["limit"]

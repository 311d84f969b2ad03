"""The comparison that decides ``correct``.

Once the window has closed and the engine is freed, a sample of the
requests the window finished — drawn from the seed, always holding the
longest and, where there are several experts, one routed to each — goes
through the plain reference (the architecture's ``make_logprobs_fn``
and ``reference.py``): each prompt with the
tokens the program served, teacher-forced. The reference routes each
request itself (Eq. 28) and mixes the experts (Eq. 27), so the sample
covers the router's assignment, chunked prefill into the paged pool,
paged decode and the fused epilogue, and for the mixture the stacked
K-expert step and its mix.

Numbers compared, each against a limit in ``limits/<cell>.json``:

* ``max_gap``: the widest gap, in nats, by which a served token's
  reference log-probability lies below the reference's best at that
  position. Greedy serving on exact arithmetic reads 0; rounding reads a
  little more; a wrong token, expert, position or mix reads far more.
* ``failed``: requests due in the window that finished any other way than
  their whole requested length (limit 0).
* ``tokens_compared``: served tokens that went through the comparison
  (at least 1; a window that finished nothing cannot be correct).

With ``control=True`` (calibration only) the reference is run a second
time in float8 (``quant="fp8"``), in the program's place: the tokens that
lower precision puts first, at the same positions, go through the same
checks, and ``control_correct`` is their verdict (it has to be false).

Each sequence is padded to the mix's longest prompt and answer, rounded
up to 128 positions: one reference program per cell, and causal
attention leaves the positions before the padding as they are.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def outcome(rec: dict):
    """(attempted, failed): requests due in the window, and those among
    them that finished any other way than their whole requested length."""
    due = [q for q in rec["reqs"].values()
           if rec["t0"] <= q["due"] < rec["t1"]]
    failed = sum(1 for q in due if q["reason"] is not None and
                 (q["reason"] != "length" or len(q["toks"]) != q["max_new"]))
    return len(due), failed


def limits(cell: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "limits", f"{cell}.json")) as f:
        return json.load(f)


def sample(rec: dict, weights_of, seed: int, target: int, K: int):
    """Requests to compare: the longest finished one, one per expert the
    router used, then a seeded draw until ``target`` served tokens."""
    done = [q for q in rec["reqs"].values()
            if q["reason"] == "length" and len(q["toks"]) == q["max_new"]]
    if not done:
        return []
    order = [done[i] for i in
             np.random.default_rng([seed, 7]).permutation(len(done))]
    pick = [max(done, key=lambda q: len(q["toks"]))]
    for k in range(K):
        if not any(weights_of(q)[k] > 0 for q in pick):
            pick += [q for q in order if weights_of(q)[k] > 0][:1]
    n = sum(len(q["toks"]) for q in pick)
    for q in order:
        if n >= target:
            break
        if all(q is not p for p in pick):
            pick.append(q)
            n += len(q["toks"])
    return pick


def compare(rec: dict, config: dict, mix, arch, expert, seed: int, K: int,
            strategy: str, cell: str, control: bool = False) -> dict:
    """``arch`` is the architecture's module (``models/<name>.py``);
    ``expert(k)`` returns expert k's weights on the device."""
    import jax.numpy as jnp
    import reference
    import traffic

    m, dep = config["model"], config["deployment"]
    lim = limits(cell)
    Np = m["num_image_token"]
    (_, text_hi), (_, A) = mix.bounds()
    T = min(config["engine"]["cache_len"] - Np,
            -(-(text_hi + A) // 128) * 128)
    route = dep["router"]

    def weights_of(q):
        return reference.route(np.asarray(q["features"], np.float64)[None],
                               mix.centroids, route["temperature"],
                               route["top_k"], strategy)[0]

    pick = sample(rec, weights_of, seed, lim["sample_tokens"], K)
    inputs = []
    for q in pick:
        r = traffic.Request(q["idx"], q["text_len"], q["max_new"], 0)
        tokens, patches, _ = mix.content(r)
        served = np.asarray(q["toks"], np.int32)
        n = len(served)
        seq = np.zeros(T, np.int32)
        seq[:len(tokens)] = tokens
        seq[len(tokens):len(tokens) + n - 1] = served[:-1]
        rows = np.zeros(A, np.int32)
        rows[:n] = q["width"] - 1 + np.arange(n)
        tok = np.zeros(A, np.int32)
        tok[:n] = served
        inputs.append((jnp.asarray(seq), jnp.asarray(patches),
                       jnp.asarray(rows), jnp.asarray(tok),
                       jnp.asarray(np.arange(A) < n), weights_of(q)))

    fns = {"ref": arch.make_logprobs_fn(m)}
    if control:
        fns["ctrl"] = arch.make_logprobs_fn(m, quant="fp8")
    lps = [{name: {} for name in fns} for _ in inputs]
    for k in range(K):
        if not any(x[5][k] > 0 for x in inputs):
            continue
        w = expert(k)
        for x, lp in zip(inputs, lps):
            if x[5][k] > 0:
                for name, fn in fns.items():
                    lp[name][k] = fn(w, x[0], x[1], x[2])
        del w

    def mixed(parts: dict, wts):
        ks = sorted(parts)
        if len(ks) == 1:
            return parts[ks[0]]
        return reference.mix(jnp.stack([parts[k] for k in ks]),
                             jnp.asarray(wts[ks], jnp.float32))

    gap, ctrl_gap, n_tok = 0.0, 0.0, 0
    for x, lp in zip(inputs, lps):
        ref = mixed(lp["ref"], x[5])
        gap = max(gap, float(reference.gaps(ref, x[3], x[4]).max()))
        n_tok += int(x[4].sum())
        if control:
            pick_c = jnp.argmax(mixed(lp["ctrl"], x[5]), -1).astype(jnp.int32)
            ctrl_gap = max(ctrl_gap,
                           float(reference.gaps(ref, pick_c, x[4]).max()))
    _, failed = outcome(rec)
    checks = verdict(gap, failed, n_tok, lim)
    lines = [f"check {k} {c['value']!r} {c['rule']} limit {c['limit']!r}"
             for k, c in checks.items()]
    lines.append(f"check requests_compared {len(pick)}")
    out = {"correct": passes(checks), "checks": checks, "lines": lines}
    if control:
        ctrl = verdict(ctrl_gap, failed, n_tok, lim)
        out["control_gap"] = ctrl_gap
        out["control_correct"] = passes(ctrl)
        out["lines"][:0] = [
            f"control {k} {c['value']!r} {c['rule']} limit {c['limit']!r}"
            for k, c in ctrl.items()] + [
            f"control correct {out['control_correct']}"]
    return out


def verdict(gap: float, failed: int, n_tok: int, lim: dict) -> dict:
    """The numbers compared, each beside its limit."""
    return {
        "max_gap": {"value": gap, "limit": lim["max_gap"], "rule": "<="},
        "failed": {"value": failed, "limit": 0, "rule": "<="},
        "tokens_compared": {"value": n_tok, "limit": 1, "rule": ">="},
    }


def passes(checks: dict) -> bool:
    return bool(all(c["value"] <= c["limit"] if c["rule"] == "<=" else
                    c["value"] >= c["limit"] for c in checks.values()))

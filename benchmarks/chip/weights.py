"""Expert weights made by the benchmark from the seed.

The benchmark, not the program, owns the weights: both the system under
test and the plain reference read the same arrays, and the reference
takes nothing the program made. The layout is the architecture's
(``models/<architecture>.py``: name path -> (shape, fan-in));
``check_layout`` compares it with the program's own
``jax.eval_shape(model.init)`` so that a change of layout fails loudly
instead of serving garbage.

Each expert is made on the device by one jitted call, in the dtype it is
served in. Matrices are normal with standard deviation 1/sqrt(fan-in);
the token embedding is standard normal; RMSNorm scales are 1 + 0.1·normal
so that a norm whose scale is dropped shows in the comparison.
"""
from __future__ import annotations

import numpy as np

NORM_JITTER = 0.1


def _leaves(tree, prefix=()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def make_expert_fn(layout: dict, dtype: str):
    """A jitted ``key -> params`` that builds one expert on the device."""
    import jax
    import jax.numpy as jnp

    spec = list(_leaves(layout))
    dt = jnp.dtype(dtype)

    def make(key):
        keys = jax.random.split(key, len(spec))
        out: dict = {}
        for k, (path, (shape, fan_in)) in zip(keys, spec):
            z = jax.random.normal(k, shape, jnp.float32)
            if fan_in == 0:
                leaf = 1.0 + NORM_JITTER * z
            elif fan_in < 0:
                leaf = z
            else:
                leaf = z / np.sqrt(fan_in)
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = leaf.astype(dt)
        return out

    return jax.jit(make)


def expert_keys(seed: int, k: int):
    """The K experts' PRNG keys, derived from the run's seed."""
    import jax
    base = int(np.random.default_rng([seed, 1]).integers(0, 2 ** 31))
    return [jax.random.PRNGKey(base + i) for i in range(k)]


def check_layout(model, layout: dict) -> None:
    """Raise unless the program's parameter tree has exactly this layout."""
    import jax
    want = {path: shape for path, (shape, _) in _leaves(layout)}
    got_tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = {tuple(getattr(p, "key", p) for p in path): tuple(x.shape)
           for path, x in jax.tree_util.tree_flatten_with_path(got_tree)[0]}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise RuntimeError(f"the program's parameter layout differs from the "
                           f"benchmark's: {diff[:6]}")


def n_params(layout: dict) -> int:
    return int(sum(np.prod(shape) for _, (shape, _) in _leaves(layout)))

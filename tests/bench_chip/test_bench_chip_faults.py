"""Faults planted under the benchmark's timed path, each of which the
comparison that decides ``correct`` has to catch (CPU rehearsal sizes)."""
from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_bench_chip import spec  # noqa: E402
import run  # noqa: E402


# --- faults under the timed path ---------------------------------------------

def alter_a_token(monkeypatch):
    from repro.serve import scheduler
    orig = scheduler._SlotTable._advance_fused

    def advance(self, dec, nxt, done):
        nxt = np.array(nxt)
        if dec:
            nxt[dec[0]] = (nxt[dec[0]] + 1) % self.model.cfg.vocab
        return orig(self, dec, nxt, done)
    monkeypatch.setattr(scheduler._SlotTable, "_advance_fused", advance)


def drop_the_kv_write(monkeypatch):
    from repro.models import attention
    orig = attention.paged_decode_attention

    def decode(params, x, cfg, pool, *a, **k):
        out, _ = orig(params, x, cfg, pool, *a, **k)
        return out, pool            # the step returns its state unchanged
    monkeypatch.setattr(attention, "paged_decode_attention", decode)


def drop_the_mix(monkeypatch):
    import jax.numpy as jnp
    from repro.core import router
    monkeypatch.setattr(router.CentroidRouter, "route", lambda self, f: (
        jnp.eye(self.K)[jnp.argmax(self.cluster_probs(f), -1)]))


@pytest.mark.parametrize("cell,fault", [
    ("top1-vqa", alter_a_token), ("top1-caption", drop_the_kv_write),
    ("mixture-caption", alter_a_token),
    ("mixture-caption", drop_the_kv_write),
    ("mixture-caption", drop_the_mix)])
def test_a_planted_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result, lines = run.run(spec(cell, seconds=4.0))
    assert result["correct"] is False, lines
    assert result["checks"]["max_gap"]["value"] > \
        result["checks"]["max_gap"]["limit"]

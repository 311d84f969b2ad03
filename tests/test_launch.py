"""Launch-layer unit tests that don't need the 512-device dry-run env.

NOTE: importing repro.launch.dryrun sets XLA_FLAGS, but the jax backend is
already initialized (1 CPU device) by earlier tests, so the flag is inert
here — these tests only exercise pure helpers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# force backend init BEFORE importing dryrun so the 512-device flag is inert
_ = jax.devices()

from repro.configs.base import INPUT_SHAPES, get_config
from repro.launch import dryrun
from repro.launch.roofline import RooflineReport, model_flops


class FakeMesh:
    shape = {"data": 16, "model": 16}


def test_if_divisible():
    m = FakeMesh()
    assert dryrun._if_divisible(m, ("data",), 256) == ("data",)
    assert dryrun._if_divisible(m, ("data",), 1) is None
    assert dryrun._if_divisible(m, "model", 92_553) is None
    assert dryrun._if_divisible(m, "model", 92_672) == "model"
    assert dryrun._if_divisible(m, ("data", "model"), 512) == ("data", "model")
    assert dryrun._if_divisible(m, None, 64) is None


def test_shape_cfg_long_decode_window():
    cfg = get_config("qwen3_8b")
    assert cfg.sliding_window == 0
    long = dryrun.shape_cfg(cfg, INPUT_SHAPES["long_500k"])
    assert long.sliding_window == dryrun.LONG_DECODE_WINDOW
    # recurrent archs keep native state (no window forced)
    x = dryrun.shape_cfg(get_config("xlstm_125m"), INPUT_SHAPES["long_500k"])
    assert x.sliding_window == 0
    # other shapes unchanged
    t = dryrun.shape_cfg(cfg, INPUT_SHAPES["train_4k"])
    assert t.sliding_window == 0


def test_skip_matrix():
    assert dryrun.is_skipped("whisper_small", INPUT_SHAPES["long_500k"])
    assert not dryrun.is_skipped("whisper_small", INPUT_SHAPES["decode_32k"])
    for arch in ("llama3_405b", "xlstm_125m", "zamba2_2_7b"):
        for shape in INPUT_SHAPES.values():
            assert dryrun.is_skipped(arch, shape) is None


@pytest.mark.parametrize("arch,G,expect_layers", [
    ("qwen3_8b", 2, 2),
    ("xlstm_125m", 2, 8),        # slstm_every=4 → 4 layers per group
    ("zamba2_2_7b", 2, 12),      # shared_attn_every=6
    ("whisper_small", 1, 1),
])
def test_probe_cfg_depth_mapping(arch, G, expect_layers):
    cfg = dryrun.probe_cfg(get_config(arch), G)
    assert cfg.unroll
    assert cfg.n_layers == expect_layers
    if arch == "whisper_small":
        assert cfg.n_enc_layers == G
    # group count must equal G so the linear depth fit is valid
    from repro.models import build_model
    assert build_model(cfg).n_groups == G


def test_unrolled_forward_matches_scanned():
    """cfg.unroll must be a pure compile-strategy change."""
    from repro.configs.base import get_smoke_config
    from repro.models import build_model
    cfg = get_smoke_config("zamba2_2_7b")
    m_scan = build_model(cfg)
    m_unroll = build_model(cfg.reduced(unroll=True))
    params = m_scan.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(2 * 16).reshape(2, 16) % cfg.vocab,
             "labels": jnp.zeros((2, 16), jnp.int32)}
    a = m_scan.forward(params, batch)
    b = m_unroll.forward(params, batch)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_model_flops_and_report():
    cfg = get_config("qwen3_8b")
    f = model_flops(cfg, 8_000_000_000, 1_000_000, "train")
    assert f == pytest.approx(6 * 8e9 * 1e6)
    r = RooflineReport(arch="a", shape="s", mesh="m", mode="d",
                       flops_per_device=197e12, bytes_per_device=819e9,
                       collective_bytes=25e9,
                       model_flops_per_device=98.5e12).finalize()
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.collective_s == pytest.approx(0.5)
    assert r.useful_flops_ratio == pytest.approx(0.5)
    assert r.bottleneck in ("compute", "memory")


def test_moe_active_params():
    from repro.launch.roofline import active_params
    from repro.models import build_model
    from repro.models.params import count_params
    cfg = get_config("qwen3_moe_235b_a22b")
    model = build_model(cfg)
    total = count_params(model.param_specs())
    active = active_params(cfg, total, model)
    # 128 experts, top-8 → active well under total, above dense part
    assert active < 0.25 * total
    assert active > 0.02 * total


def test_dryrun_pins_cpu_platform(monkeypatch):
    """The dry-run must never take an attached accelerator: its import
    pins the CPU next to the 512 placeholder devices, whatever platform
    the environment asked for."""
    import importlib
    import os
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    importlib.reload(dryrun)
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "device_count=512" in os.environ["XLA_FLAGS"]


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir(env_dir, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and nothing is
    set in code; otherwise the cache is the fixed in-checkout directory."""
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", tmp_path / "fixed")
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / env_dir))
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert got == str(tmp_path / "fixed")
            assert (tmp_path / "fixed").is_dir()
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == prev
            assert not (tmp_path / "fixed").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

"""End-to-end launcher smoke tests: train with checkpoint/resume wiring and
batched serve (prefill + decode) through the public CLI entry points."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeCell, reduced
from repro.configs.registry import ARCHS, get_arch
from repro.launch.serve import serve
from repro.launch.train import train


def test_train_loss_decreases():
    cfg = reduced(get_arch("tinyllama-1.1b"), n_layers=2)
    cell = ShapeCell("t", 64, 4, "train")
    out = train(cfg, cell, steps=15, log_fn=lambda *_: None)
    assert len(out["losses"]) == 15
    assert out["losses"][-1] < out["losses"][0]
    assert all(np.isfinite(out["losses"]))


@pytest.mark.parametrize("scheme", ["bf16", "int8"])
def test_train_with_grad_compression(scheme):
    """Flag-gated wire compression in the production step tracks the
    uncompressed loss curve (bf16 ~ exactly; int8 via error feedback)."""
    cfg = reduced(get_arch("smollm-135m"), n_layers=2)
    cell = ShapeCell("t", 32, 4, "train")
    base = train(cfg, cell, steps=5, log_fn=lambda *_: None)["losses"]
    comp = train(cfg, cell, steps=5, compress=scheme,
                 log_fn=lambda *_: None)["losses"]
    assert all(np.isfinite(comp))
    # 5 steps is inside the warmup bump — the claim is that compression
    # tracks the uncompressed curve, not that loss already decreased
    np.testing.assert_allclose(base, comp, rtol=5e-2)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """The CLIs' persistent compile cache: JAX_COMPILATION_CACHE_DIR when
    set (JAX reads it itself, so nothing is set in code), else one fixed
    directory at the checkout's root."""
    from repro.launch.mesh import DEFAULT_COMPILE_CACHE, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = enable_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert path == str(DEFAULT_COMPILE_CACHE)
            assert jax.config.jax_compilation_cache_dir == path
            assert (DEFAULT_COMPILE_CACHE.parent / "pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_train_rejects_unknown_compression():
    from repro.launch.steps import make_train_step
    from repro.optim.adamw import AdamW
    cfg = reduced(get_arch("smollm-135m"), n_layers=2)
    with pytest.raises(ValueError, match="compression"):
        make_train_step(cfg, AdamW(total_steps=10), compress="fp4")


def test_train_grad_accumulation_matches():
    """accum=2 on a fixed batch must track accum=1 closely (same data)."""
    cfg = reduced(get_arch("smollm-135m"), n_layers=2)
    cell = ShapeCell("t", 32, 4, "train")
    l1 = train(cfg, cell, steps=5, accum=1, log_fn=lambda *_: None)["losses"]
    l2 = train(cfg, cell, steps=5, accum=2, log_fn=lambda *_: None)["losses"]
    np.testing.assert_allclose(l1, l2, rtol=2e-3)


@pytest.mark.parametrize("arch", ["smollm-135m", "rwkv6-7b",
                                  "recurrentgemma-9b", "whisper-medium"])
def test_serve_generates(arch):
    cfg = reduced(get_arch(arch))
    tokens, stats = serve(cfg, batch=2, prompt_len=16, gen=6,
                          log_fn=lambda *_: None)
    assert tokens.shape == (2, 6)
    assert int(tokens.min()) >= 0 and int(tokens.max()) < cfg.vocab_size
    assert stats["decode_s"] > 0


def test_serve_greedy_deterministic():
    cfg = reduced(get_arch("smollm-135m"))
    t1, _ = serve(cfg, batch=2, prompt_len=16, gen=5, temperature=0.0,
                  log_fn=lambda *_: None)
    t2, _ = serve(cfg, batch=2, prompt_len=16, gen=5, temperature=0.0,
                  log_fn=lambda *_: None)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))

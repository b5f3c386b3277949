"""The serving launcher's entry points: ``serve()`` at reduced widths with
both wire precisions, the command line's defaults, and where the
persistent compilation cache goes."""

import sys
from pathlib import Path

import jax
import pytest

from repro.kernels import ops
from repro.launch import compile_cache, serve as S

ROOT = Path(__file__).resolve().parents[1]


def test_serve_smoke_runs_both_wire_precisions():
    before = ops.PATHS.copy()
    out = S.serve("h2o-danube-3-4b", smoke=True, requests=4,
                  wire_bits=(8, 4), verbose=False)
    assert len(out.stats.pipeline.tasks) == 4
    assert out.runtime.cfg.name == "h2o-danube-3-4b-smoke"
    assert out.init_s > 0 and out.warmup_s > 0 and out.wall_s > 0
    calls = ops.PATHS - before
    kernel = "pallas" if jax.default_backend() == "tpu" else "interpret"
    # one warm-up per precision, then the requests alternate 8, 4, 8, 4
    for bits in (8, 4):
        assert calls["dequantize", bits, kernel] == 3
        assert sum(n for (op, b, _), n in calls.items()
                   if op == "boundary" and b == bits) == 3


def test_main_defaults_to_full_width_danube(monkeypatch):
    seen = {}
    monkeypatch.setattr(S, "serve", lambda arch, **kw: seen.update(
        arch=arch, **kw))
    monkeypatch.setattr(S, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["serve"])
    S.main()
    assert seen["arch"] == "h2o-danube-3-4b" and seen["smoke"] is False
    assert "wire_bits" not in seen  # the serve() default, 8 bits
    monkeypatch.setattr(sys, "argv", ["serve", "--smoke"])
    S.main()
    assert seen["smoke"] is True


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_environment(monkeypatch, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None  # JAX's own


def test_compile_cache_defaults_to_ignored_checkout_dir(monkeypatch,
                                                        restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path  # fixed, not fresh
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_compile_cache_refuses_installed_package(monkeypatch, tmp_path,
                                                 restore_cache_dir):
    """Without the variable, a root that holds no checkout (an installed
    package's site-packages) is refused, not shared between checkouts."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT", tmp_path)
    with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
        compile_cache.enable_compile_cache()

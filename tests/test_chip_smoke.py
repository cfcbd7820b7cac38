"""chip_smoke.py and the entry points' compile cache, rehearsed on the CPU.

``chip_smoke.py`` measures the chip only: run as a script here it must
exit non-zero and print no result.  Its phases run end to end at a tiny
width through ``run_phases`` with the kernel in interpret mode, so a
wrong path, argument or check fails here before it costs chip time.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.configs.base import OneRecConfig, TransformerConfig
from repro.launch import serve

CHECKOUT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", CHECKOUT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _tiny_cfg() -> OneRecConfig:
    # capacity_factor lifted so MoE batch composition cannot move A vs B
    return OneRecConfig(
        name="onerec-chip-smoke-test",
        history_len=8,
        transformer=TransformerConfig(
            name="onerec-chip-smoke-test-backbone",
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab_size=256, moe=True, n_experts=4, top_k=2,
            d_expert=128, capacity_factor=64.0, ep_degree=4,
            max_seq_len=64, remat=False),
        serve_batch=8, beam_width=4)


def test_chip_smoke_phases_run_at_tiny_width():
    """Phases A (fp8, fused), B (unfused reference) and C (bf16) pass,
    with the fused kernel in interpret mode standing in for the chip."""
    smoke = _load_chip_smoke()
    cfg = _tiny_cfg()
    lines = []
    failures = smoke.run_phases(cfg, smoke.smoke_requests(cfg, 12, 0),
                                n_slots=8, fused="interpret",
                                expect_mode="interpret", log=lines.append)
    assert failures == []
    text = "\n".join(lines)
    assert "(0 post-warmup compiles)" in text
    assert "12 completions" in text
    assert "top-8 overlap" in text


def test_chip_smoke_refuses_the_cpu(tmp_path):
    """No TPU: non-zero exit and no result line, never a CPU fallback."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(CHECKOUT / "chip_smoke.py")],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache and
    the helper sets nothing (JAX reads the variable itself)."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve.enable_compile_cache() == str(tmp_path)
    assert updates == []


def test_compile_cache_defaults_to_a_fixed_checkout_path(monkeypatch):
    """Without the variable: <checkout>/.jax_cache on every call and in
    every process, and nothing is set on import."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = str(CHECKOUT / ".jax_cache")
    assert serve.enable_compile_cache() == fixed
    assert serve.enable_compile_cache() == fixed
    assert updates == [("jax_compilation_cache_dir", fixed)] * 2
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(CHECKOUT / "src"))
    code = ("import jax; from repro.launch import serve; "
            "print(jax.config.jax_compilation_cache_dir); "
            "print(serve.enable_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == ["None", fixed, fixed]

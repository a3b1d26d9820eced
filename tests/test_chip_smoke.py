"""``chip_smoke.py`` rehearsed on the CPU at a tiny size: the same phase
code the chip runs, with the paged kernel in interpret mode.  The script
itself must refuse to report a result without a TPU."""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_kernel_parity_phase_interpret():
    errors = chip_smoke.kernel_parity(
        n_kv_heads=4, n_heads=8, head_dim=32, page_size=8, max_len=128, batch=4,
        tokens=(1, 5), interpret=True,
    )
    assert set(errors) == {1, 5}
    for err, scale in errors.values():
        assert scale > 0.1 and err < 1e-4


def test_main_path_phase_through_kernel(tmp_path, monkeypatch):
    """Both serving jobs through the fleet, the kernel path forced on
    (interpret mode) with fresh model caches so no earlier jnp trace is
    reused; outputs agree token for token on the CPU."""
    from repro.launch import serve
    from repro.models import ModelRuntime

    monkeypatch.setattr(ModelRuntime, "resolve_paged_attn", lambda self: "kernel")
    monkeypatch.setattr(serve, "_MODEL_CACHE", {})
    monkeypatch.setattr(serve, "_PARAM_CACHE", {})
    rng = np.random.default_rng(0)
    prompts = chip_smoke.make_prompts(rng, n=6, vocab=256,
                                      lengths=np.linspace(16, 88, 6).astype(int), sys_len=32)
    summary, results, _ = chip_smoke.serve_main_path(
        str(tmp_path), prompts, arch_overrides="reduced", max_batch=4, max_len=128,
        page_size=8, new_tokens=6,
    )
    counts, agree = chip_smoke.check_main_path(summary, results, prompts, 6)
    assert counts["ngram"]["verify"] > 0 and counts["off"]["verify"] == 0
    assert agree == 1.0


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(tmp_path, from_env):
    """The entry points' compile cache lives in ``JAX_COMPILATION_CACHE_DIR``
    when that is set, else in the fixed ``<checkout>/.jax_cache``."""
    from repro.launch.compile_cache import DEFAULT_DIR

    code = (
        "import jax\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = DEFAULT_DIR
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
        code += (
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()\n"
        )
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [want, want]
    assert DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
    if from_env:
        assert os.listdir(want), "nothing was cached in JAX_COMPILATION_CACHE_DIR"


@pytest.mark.parametrize("alone", [False, True])
def test_script_refuses_without_tpu(tmp_path, alone):
    """On the CPU, from the repo or copied alone, the script exits
    non-zero and prints no result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        (tmp_path / "chip_smoke.py").write_text(open(script).read())
        script = str(tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], env=env, cwd=os.path.dirname(script),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout

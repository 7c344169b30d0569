"""chip_smoke.py on the CPU: it refuses to run without a TPU, and each of
its phases passes at a tiny size with the Pallas kernels in interpret mode."""
import importlib.util
from pathlib import Path

import pytest

from repro.configs.registry import get_config

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny():
    return get_config("olmo-1b").reduced()


def test_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "Nothing was run" in err


def test_kernels_match_refs(smoke, tiny):
    smoke.run_kernels(tiny, tokens=64, batch=2, kv_len=48, interpret=True)


def test_serve_matches_forward(smoke, tiny, capsys):
    smoke.run_serve(tiny, slots=2, max_len=64, prompt_lens=(5, 9),
                    n_requests=3, max_new=4, check_steps=2)
    assert "3/3 requests, 12 tokens" in capsys.readouterr().out


def test_train_losses_finite(smoke, tiny):
    losses = smoke.run_train(tiny, steps=2, batch=2, seq=32)
    assert len(losses) == 2


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(env_dir, tmp_path, monkeypatch):
    """The entry points' cache: JAX_COMPILATION_CACHE_DIR when set, else one
    fixed directory of the checkout that git ignores."""
    import jax
    from repro.launch.cache import REPO_ROOT, use_compile_cache
    want = str(tmp_path / env_dir) if env_dir else str(REPO_ROOT / ".jax_cache")
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert ".jax_cache/" in (REPO_ROOT / ".gitignore").read_text().split()

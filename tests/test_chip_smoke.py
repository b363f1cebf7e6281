"""chip_smoke.py off the chip: it refuses to report a result without a TPU,
and its CPU rehearsal drives every one-chip phase end to end on tiny
configs in interpret mode (every engine request ends 'done', flash decode
tokens equal XLA's, each BERT arm matches the dense reference)."""
import importlib.util
import os

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.skipif(jax.default_backend() == "tpu", reason="CPU contract")
def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "no TPU" in out.err


def test_cpu_rehearsal_passes(chip_smoke, capsys):
    assert chip_smoke.main(["--cpu-rehearsal"]) == 0
    out = capsys.readouterr().out
    assert "flash == xla greedy tokens" in out
    assert '"ok"' not in out


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched (JAX reads it itself);
    unset, the cache goes to the fixed <repo>/.jax_cache."""
    from repro.runtime.compile_cache import ENV, use_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(ENV, raising=False)
    else:
        monkeypatch.setenv(ENV, env_dir)
    try:
        got = use_compile_cache()
        want = env_dir or os.path.join(REPO, ".jax_cache")
        assert got == want
        assert jax.config.jax_compilation_cache_dir == (
            prev if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

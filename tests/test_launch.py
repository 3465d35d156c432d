"""Entry-point plumbing: ``repro.launch.serve.serve`` as a callable, the
backend-derived interpret mode, and the persistent compile-cache rule."""
import pytest


def test_serve_returns_engine_prompts_and_outputs():
    from repro.launch.serve import serve

    run = serve(["--arch", "gpt2-smoke", "--chunked-prefill", "--requests",
                 "3", "--slots", "2", "--min-prompt", "5", "--max-prompt",
                 "20", "--gen", "3"])
    assert sorted(run.outputs) == [0, 1, 2]
    assert all(len(t) == 3 for t in run.outputs.values())
    assert all(5 <= len(p) <= 20 for p in run.prompts)
    eng = run.engine
    assert eng.kernel == "fused" and eng._step_fused._cache_size() <= 2
    # off-TPU the paged kernel is interpreted: no Mosaic custom call
    assert "tpu_custom_call" not in eng.lower_step(1).as_text()


def test_min_prompt_above_max_prompt_is_refused():
    from repro.launch.serve import parse_args

    with pytest.raises(SystemExit):
        parse_args(["--min-prompt", "30", "--max-prompt", "20"])


def test_interpret_follows_the_backend_unless_forced():
    import jax
    from repro.kernels.efta_attention import resolve_interpret

    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    import jax
    from repro.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_ignored_dir_in_checkout(monkeypatch):
    from pathlib import Path

    import jax
    from repro.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = Path(compile_cache.enable_compile_cache())
        assert jax.config.jax_compilation_cache_dir == str(path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = Path(__file__).resolve().parents[1]
    assert path == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()

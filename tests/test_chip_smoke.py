"""The chip smoke's refusals and the compile-cache rule, on the CPU.

`chip_smoke.py` itself only passes on a TPU; here it must refuse, loudly
and before printing any result, because JAX finds no accelerator.
"""
import os
import runpy
import sys

import pytest

from repro.launch import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_chip_smoke_refuses_without_tpu(monkeypatch, capsys, argv):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", *argv])
    with pytest.raises(SystemExit) as exc:
        runpy.run_path(os.path.join(REPO, "chip_smoke.py"),
                       run_name="__main__")
    assert isinstance(exc.value.code, str)          # exit status 1
    assert "needs a TPU" in exc.value.code and "'cpu'" in exc.value.code
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_follows_env_var(monkeypatch):
    calls = []
    monkeypatch.setattr("jax.config.update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []                              # JAX reads the env var


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    calls = []
    monkeypatch.setattr("jax.config.update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = os.path.join(REPO, ".jax_cache")
    assert cache.enable_compile_cache() == path
    assert calls == [("jax_compilation_cache_dir", path)]
    with open(os.path.join(REPO, ".gitignore")) as fh:
        ignored = {ln.strip() for ln in fh}
    assert ".jax_cache/" in ignored

"""Smoke tests for the human-facing CLIs and the docs tree.

The docs PR contract: ``benchmarks/run.py`` and ``python -m
repro.analysis`` must have accurate, working ``--help`` (no import
crashes, the documented flags present), and every markdown link in
README/docs/ROADMAP must resolve (tools/linkcheck.py, the CI ``docs``
job).  ``chip_smoke.py`` must refuse to run without a TPU or without the
package beside it, and the entry points' compilation cache must land
where the environment says, else at one fixed in-checkout path.  These
run the real entry points in subprocesses.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, **env_extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(env_extra)
    return subprocess.run(argv, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=120)


def test_bench_run_help():
    p = _run([sys.executable, "benchmarks/run.py", "--help"])
    assert p.returncode == 0, p.stderr
    for flag in ("--list", "--only"):
        assert flag in p.stdout
    assert "BENCH_*.json" in p.stdout          # the docstring is the epilog


def test_bench_run_list_names_every_group():
    p = _run([sys.executable, "benchmarks/run.py", "--list"])
    assert p.returncode == 0, p.stderr
    names = set(p.stdout.split())
    assert {"conv_fused", "fc_batch", "pipeline_serve", "zoo_serve",
            "chaos_serve", "fleet_serve"} <= names


def test_bench_run_rejects_unknown_group():
    p = _run([sys.executable, "benchmarks/run.py", "--only", "nope"])
    assert p.returncode != 0
    assert "nope" in p.stderr


def test_analysis_help():
    p = _run([sys.executable, "-m", "repro.analysis", "--help"])
    assert p.returncode == 0, p.stderr
    for flag in ("--net", "--all-zoo-variants"):
        assert flag in p.stdout


def test_linkcheck_clean_on_repo_docs():
    p = _run([sys.executable, "tools/linkcheck.py"])
    assert p.returncode == 0, p.stderr + p.stdout
    assert "0 broken links" in p.stdout


def test_linkcheck_flags_breakage(tmp_path):
    bad = tmp_path / "bad.md"
    bad.write_text("# T\n\n[gone](no_such_file.md) "
                   "[badanchor](bad.md#not-a-heading)\n")
    p = _run([sys.executable, "tools/linkcheck.py", str(bad)])
    assert p.returncode == 2
    assert "missing file" in p.stderr and "missing anchor" in p.stderr


def test_linkcheck_rejects_relative_root_badge(tmp_path):
    bad = tmp_path / "badge.md"
    bad.write_text("[![ci](../../actions/workflows/ci.yml/badge.svg)]"
                   "(../../actions/workflows/ci.yml)\n")
    p = _run([sys.executable, "tools/linkcheck.py", str(bad)])
    assert p.returncode != 0
    assert "relative-root" in p.stderr


@pytest.mark.parametrize("doc", ["architecture.md", "dataflows.md",
                                 "serving.md", "benchmarks.md"])
def test_docs_tree_exists_and_linked_from_readme(doc):
    assert os.path.exists(os.path.join(REPO, "docs", doc))
    readme = open(os.path.join(REPO, "README.md"), encoding="utf-8").read()
    assert f"docs/{doc}" in readme


def _run_bare(argv, cwd, **env_extra):
    """Run ``argv`` in ``cwd`` without the repo's ``src`` on the path and
    without a compilation-cache directory from the caller's environment."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    return subprocess.run(argv, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


def _ok_line(stdout: str) -> bool:
    return '"ok": true' in (stdout.strip().splitlines() or [""])[-1]


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    """On a CPU-only machine the smoke run exits non-zero and prints no
    result line: it never falls back to timing the CPU."""
    p = _run_bare([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                  REPO, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert p.returncode != 0
    assert not _ok_line(p.stdout)
    assert "no TPU attached" in p.stderr
    assert f"compile_cache {tmp_path}" in p.stdout


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    """The script alone, without the package beside it, fails too."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(os.path.join(REPO, "chip_smoke.py"),
                          encoding="utf-8").read())
    p = _run_bare([sys.executable, str(alone)], tmp_path)
    assert p.returncode != 0
    assert not _ok_line(p.stdout)
    assert "not beside this script" in p.stderr


_CACHE_PROBE = (
    "import jax, repro.serve.zoo, repro.serve.fleet\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def test_compile_cache_defaults_to_one_ignored_in_checkout_path():
    """Importing the library leaves the cache off; an entry point turns it
    on at ``<checkout>/.jax_cache``, which git ignores."""
    p = _run_bare([sys.executable, "-c", _CACHE_PROBE], REPO,
                  PYTHONPATH=os.path.join(REPO, "src"))
    assert p.returncode == 0, p.stderr
    before, chosen, after = p.stdout.split()
    want = os.path.join(REPO, ".jax_cache")
    assert before == "None"
    assert chosen == after == want
    ignored = open(os.path.join(REPO, ".gitignore"), encoding="utf-8").read()
    assert ".jax_cache/" in ignored.split()


def test_compile_cache_uses_the_environment_directory(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is the cache, and nothing in the
    program names another directory."""
    p = _run_bare([sys.executable, "-c", _CACHE_PROBE], REPO,
                  PYTHONPATH=os.path.join(REPO, "src"),
                  JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [str(tmp_path)] * 3

"""Packaging / install story (reference ``setup.py:70-197``): the package
must be pip-installable with working console entry points and its native
kernel sources shipped as package data, so the CLI tools work with the
repo nowhere on ``sys.path``."""
import os
import shutil
import subprocess
import sys

import pytest

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


@pytest.fixture(scope="module")
def installed_tree(tmp_path_factory):
    """pip-install the repo into an isolated --target tree (builds the
    wheel via setuptools, no network: --no-deps --no-build-isolation).
    The build runs on a COPY of the files the wheel needs: setuptools
    builds in-tree, and a ``build/`` + ``*.egg-info`` left in the
    checkout holds a stale copy of the package that later runs copy
    around with the repo."""
    src = tmp_path_factory.mktemp("src")
    for name in ("pyproject.toml", "README.md"):
        shutil.copy(os.path.join(REPO_ROOT, name), src / name)
    shutil.copytree(os.path.join(REPO_ROOT, "deepspeed_tpu"),
                    src / "deepspeed_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    target = tmp_path_factory.mktemp("site")
    proc = subprocess.run(
        [sys.executable, "-m", "pip", "install", "--quiet", "--no-deps",
         "--no-build-isolation", "--target", str(target), str(src)],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    return target


def test_install_ships_package_and_native_sources(installed_tree):
    pkg = installed_tree / "deepspeed_tpu"
    assert (pkg / "__init__.py").is_file()
    # the JIT-built host Adam kernel source must ride along (op_builder
    # resolves sources relative to the installed package dir)
    assert (pkg / "csrc" / "adam" / "cpu_adam.cpp").is_file()


@pytest.mark.parametrize("script", ["deepspeed", "ds", "ds_report",
                                    "ds_ssh", "ds_elastic", "dslint"])
def test_console_scripts_run_off_tree(installed_tree, script, tmp_path):
    """Each console script must import and print help using ONLY the
    installed tree — cwd is outside the repo and sys.path excludes it."""
    env = dict(os.environ,
               PYTHONPATH=str(installed_tree),
               JAX_PLATFORMS="cpu",
               # don't let the user site or repo leak in
               PYTHONNOUSERSITE="1")
    exe = installed_tree / "bin" / script
    assert exe.is_file(), f"pip --target did not create bin/{script}"
    proc = subprocess.run([sys.executable, str(exe), "--help"],
                          capture_output=True, text=True, timeout=120,
                          cwd=tmp_path, env=env)
    assert proc.returncode == 0, proc.stderr
    out = (proc.stdout + proc.stderr).lower()
    # ds_report has no arg parser — it just prints the report
    assert "usage" in out or "environment report" in out

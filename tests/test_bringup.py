"""Bring-up plumbing: where the compile cache lives, and when the native
library is rebuilt.

* The persistent compile cache is configured in ONE place
  (jylis_tpu/__init__.py): JAX_COMPILATION_CACHE_DIR decides the location
  when set; unset, a fixed directory beside the package.
* The native .so is stale when the CONTENT of native/ changed, whatever
  the mtimes say (a copied tree keeps no meaningful mtimes).
* What the build, the CI configs and the README name is in the checkout,
  and `make ci` still expands: a deletion that leaves a reference behind
  fails here, not in a CI nobody ran.
"""

import os
import re
import shutil
import subprocess
import sys

import pytest

from jylis_tpu import native
from procutil import REPO, connect_client, free_port, stop_node

FIXED_CACHE = os.path.join(REPO, ".jax_cache")


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.pop("XLA_FLAGS", None)  # one CPU device: the cheapest boot
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _cache_dir_of_a_process(env):
    """[directory, number of entries] as a process that compiled one
    program under `env` reports them."""
    code = (
        "import jax, jylis_tpu, os\n"
        "jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7)).block_until_ready()\n"
        "d = jax.config.jax_compilation_cache_dir\n"
        "print(d, len(os.listdir(d)))\n"
    )
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env, check=True,
        capture_output=True, text=True,
    ).stdout.split()


def test_env_var_alone_places_the_compile_cache(tmp_path):
    cache = tmp_path / "cache"
    port = free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "jylis_tpu", "--port", str(port), "--addr",
         f"127.0.0.1:{free_port()}:cache-node", "--log-level", "warn"],
        cwd=REPO, env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
    )
    try:
        connect_client(port, proc=proc).close()  # serving: warmup compiled
    finally:
        stop_node(proc)
    booted = len(os.listdir(cache))
    assert booted > 0  # the boot's kernels landed there
    # ... and the package put no directory of its own over the variable's
    # (asked of a process: other workers write to the in-checkout directory)
    where, entries = _cache_dir_of_a_process(_env(JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert where == str(cache) and int(entries) > booted


def test_unset_the_cache_is_the_fixed_in_checkout_directory():
    out = _cache_dir_of_a_process(_env())
    assert out[0] == FIXED_CACHE and int(out[1]) > 0


def test_one_place_sets_a_cache_dir():
    hits = []
    for top in ("jylis_tpu", "scripts", "chip_smoke.py", "__graft_entry__.py"):
        paths = [os.path.join(REPO, top)]
        if os.path.isdir(paths[0]):
            paths = [
                os.path.join(d, f) for d, _s, fs in os.walk(paths[0])
                for f in fs if f.endswith(".py")
            ]
        for path in paths:
            with open(path, encoding="utf-8") as f:
                if re.search(r"compilation_cache_dir|set_cache_dir|initialize_cache", f.read()):
                    hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("jylis_tpu", "__init__.py")], hits


def test_native_staleness_is_decided_by_source_content(tmp_path, monkeypatch):
    src = tmp_path / "native"
    src.mkdir()
    unit = src / "unit.cpp"
    unit.write_text('#include "unit.h"\nextern "C" int jy_one() { return ONE; }\n')
    (src / "unit.h").write_text("#define ONE 1\n")
    so = str(src / "libjylis_native.so")
    monkeypatch.setattr(native, "_SRC_DIR", str(src))
    monkeypatch.setattr(native, "_BUILT_SO", so)
    monkeypatch.setattr(native, "_SO_PATH", so)

    assert native.build()
    assert native.built_hash() == native.source_hash() and not native._stale()
    # mtimes say "sources newer than the binary": irrelevant
    os.utime(unit, (2e9, 2e9))
    assert not native._stale()
    # content changed (a header counts), binary "newer" by mtime: stale
    (src / "unit.h").write_text("#define ONE 2\n")
    os.utime(so, (3e9, 3e9))
    assert native._stale()
    assert native.build() and not native._stale()
    # a binary that arrived without its stamp (a copied tree) is not trusted
    os.unlink(so + native._HASH_SUFFIX)
    assert native._stale()
    # an operator-provided binary (JYLIS_NATIVE_SO, a wheel) is taken as given
    given = str(tmp_path / "given.so")
    shutil.copy(so, given)
    monkeypatch.setattr(native, "_SO_PATH", given)
    assert not native._stale()


_NAMED = re.compile(r"[\w./*-]+\.(?:py|json|md)\b")


def _named_paths():
    """(where, path) for every *.py / *.json / *.md the Makefile, the two CI
    configs and the backticked spans of README.md name."""
    for src in ("Makefile", ".github/workflows/ci.yml", ".circleci/config.yml", "README.md"):
        with open(os.path.join(REPO, src), encoding="utf-8") as f:
            text = f.read()
        spans = re.findall(r"`([^`\n]+)`", text) if src.endswith(".md") else [text]
        for span in spans:
            for path in _NAMED.findall(span):
                if "*" not in path:  # a pattern names no one file
                    yield src, path


def test_every_path_the_build_names_exists():
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        ignored = {line.strip() for line in f}
    have = set()
    for d, subs, files in os.walk(REPO):
        # what building and running leave behind (an unpacked parent commit
        # under .scratch/ among it) is not the checkout
        subs[:] = [s for s in subs if s != ".git" and s + "/" not in ignored]
        have.update("/" + os.path.relpath(os.path.join(d, f), REPO) for f in files)
    # prose names a module by the end of its path (`ops/planes.py`): a name
    # counts when some file's path ends with it; a build output when ignored
    missing = sorted({
        (src, path) for src, path in _named_paths()
        if path not in ignored and not any(h.endswith("/" + path) for h in have)
    })
    assert not missing, missing


@pytest.mark.parametrize("target", ["ci", "test", "lint"])
def test_make_target_expands(target):
    done = subprocess.run(
        ["make", "-n", target], cwd=REPO, capture_output=True, text=True
    )
    assert done.returncode == 0, done.stderr

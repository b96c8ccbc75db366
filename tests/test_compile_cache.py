"""The compile cache rule (trino_tpu/__init__.py): JAX_COMPILATION_CACHE_DIR
places the cache from outside; unset, it is <checkout>/.jax_cache_tpu; and no
other code sets a directory."""

import os
import re
import subprocess
import sys

import jax

import trino_tpu  # noqa: F401  (applies the rule to this process)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PRINT_DIR = "import trino_tpu, jax; print(jax.config.jax_compilation_cache_dir)"


def _cache_dir_of_a_fresh_import(env_value):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    out = subprocess.run(
        [sys.executable, "-c", _PRINT_DIR], env=env, cwd=REPO, check=True,
        capture_output=True, text=True, timeout=120,
    )
    return out.stdout.strip().splitlines()[-1]


def test_environment_places_the_cache(tmp_path):
    assert _cache_dir_of_a_fresh_import(str(tmp_path)) == str(tmp_path)
    assert os.listdir(tmp_path) == []  # placing it writes nothing


def test_unset_the_cache_is_in_the_checkout():
    want = os.path.join(REPO, ".jax_cache_tpu")
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        assert _cache_dir_of_a_fresh_import(None) == want
    else:  # this process imported trino_tpu that way already
        assert jax.config.jax_compilation_cache_dir == want


def test_the_directory_is_set_at_one_site():
    name = "jax_compilation_cache" + "_dir"
    write = re.compile(
        r"""update\(\s*["']%s["']|\b%s\s*=[^=]|environ(\[|\.setdefault\()\s*["']%s["']\s*[\],]"""
        % (name, name, name.upper())
    )
    sites = []
    skip = {".git", ".jax_cache_tpu", "chiprun_out", "chip_stage", "__pycache__"}
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path, encoding="utf-8") as fh:
                    sites += [os.path.relpath(path, REPO)] * len(write.findall(fh.read()))
    assert sites == ["trino_tpu/__init__.py"], sites

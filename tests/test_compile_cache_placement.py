"""Where the persistent XLA compile cache goes
(``device.place_compile_cache``).

The setting is process-global, so every case is its own subprocess.  With
``JAX_COMPILATION_CACHE_DIR`` set, nothing in the package may override or
clear it — not the import, not ``FLAGS_xla_compile_cache_dir`` (set or
emptied), not ``chip_smoke.py``'s setup.  Unset, the cache is
``<checkout>/.cache/xla_compile`` whatever the working directory.  The
probe also checks that ``import paddle_tpu`` places the cache without
initialising a backend (a parent may import the package and still start a
child that owns the chip).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT = os.path.join(REPO, ".cache", "xla_compile")

_PROBE = """
import sys
sys.path.insert(0, {repo!r})
import jax
seen = []
def look():
    seen.append(jax.config.jax_compilation_cache_dir)
import paddle_tpu as pt
from jax._src import xla_bridge
assert not xla_bridge._backends, "import paddle_tpu initialised a backend"
look()
pt.set_flags({{"FLAGS_xla_compile_cache_dir": {flag_dir!r}}})
look()
pt.set_flags({{"FLAGS_xla_compile_cache_dir": ""}})
look()
import chip_smoke
chip_smoke.device_identity()
look()
print("SEEN", "|".join(str(s) for s in seen))
"""


def _probe(tmp_path, env_dir, cwd, flag_dir="/flag/dir"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("FLAGS_xla_compile_cache_dir", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run(
        [sys.executable, "-c", _PROBE.format(repo=REPO, flag_dir=flag_dir)],
        cwd=str(cwd), env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    line = next(l for l in r.stdout.splitlines() if l.startswith("SEEN "))
    return line[5:].split("|")


def test_env_var_is_never_overridden_or_cleared(tmp_path):
    """after import / flag set / flag emptied / chip_smoke's setup."""
    assert _probe(tmp_path, "/x", tmp_path) == ["/x"] * 4


@pytest.mark.parametrize("cwd", ["repo", "elsewhere"])
def test_default_is_the_checkout_whatever_the_cwd(tmp_path, cwd):
    seen = _probe(tmp_path, None, REPO if cwd == "repo" else tmp_path)
    # import -> checkout default; flag -> the flag's dir; emptied -> back
    # to the default (never None); chip_smoke's setup leaves it alone
    assert seen == [DEFAULT, "/flag/dir", DEFAULT, DEFAULT]


def test_no_code_path_derives_a_cache_dir_from_tempfile_pid_or_time():
    """Outside tests/, only paddle_tpu/device.py names the jax cache
    option, and it computes the path from the package location."""
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d not in ("tests", "build", "dist", "chiprun_out")
                   and not d.startswith(".")]
        for fn in files:
            if not fn.endswith((".py", ".sh")):
                continue
            path = os.path.join(root, fn)
            with open(path, errors="replace") as f:
                text = f.read()
            if "jax_compilation_cache_dir\", " in text or \
                    "JAX_COMPILATION_CACHE_DIR=" in text:
                offenders.append(os.path.relpath(path, REPO))
    assert offenders == [os.path.join("paddle_tpu", "device.py")]

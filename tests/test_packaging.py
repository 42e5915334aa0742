"""Packaging (VERDICT r3 missing #3; ref pyzoo/setup.py, make-dist.sh):
pip-install the package into a CLEAN venv — native .so compiled by the
build hook, label resources as package data — and run the lenet-style
quickstart from the INSTALLED copy (repo not on the path)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

QUICKSTART = r"""
import os, sys
# prove we're running the INSTALLED copy, not the source tree
import analytics_zoo_tpu as zoo
assert analytics_zoo_tpu_site in zoo.__file__, zoo.__file__

import numpy as np
zoo.init_nncontext()

# packaged data: bundled label maps
from analytics_zoo_tpu.models.image.labels import LabelReader
assert LabelReader.read_imagenet()[0].startswith("tench")

# packaged native runtime: the .so compiled by the wheel build hook
from analytics_zoo_tpu import native
assert native.available(), "packaged native runtime failed to load"
from analytics_zoo_tpu.inference.serving_export import ensure_serving_lib
assert os.path.exists(ensure_serving_lib())

# the quickstart: a small model through compile/fit/evaluate
from analytics_zoo_tpu.keras.engine.topology import Sequential
from analytics_zoo_tpu.keras.layers import Dense
from analytics_zoo_tpu.keras.optimizers import Adam
rng = np.random.default_rng(0)
x = rng.normal(size=(256, 8)).astype(np.float32)
y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.int32)
m = Sequential()
m.add(Dense(16, activation="relu", input_shape=(8,)))
m.add(Dense(2, activation="softmax"))
m.compile(optimizer=Adam(lr=0.02), loss="sparse_categorical_crossentropy",
          metrics=["accuracy"])
m.fit(x, y, batch_size=32, nb_epoch=6)
acc = m.evaluate(x, y, batch_size=32)["accuracy"]
assert acc > 0.8, acc
print("QUICKSTART_OK", acc)
"""


@pytest.mark.slow
def test_pip_install_clean_venv_runs_quickstart(tmp_path):
    venv_dir = tmp_path / "venv"
    subprocess.run([sys.executable, "-m", "venv", "--system-site-packages",
                    str(venv_dir)], check=True)
    vpy = str(venv_dir / "bin" / "python")

    # A venv created from a venv python chains to the ORIGINAL base
    # interpreter, so --system-site-packages does not expose the running
    # environment's packages (jax, setuptools, ...). Link them in with a
    # .pth — the test's subject is OUR package's install, not jax's.
    import sysconfig

    base_purelib = sysconfig.get_paths()["purelib"]
    vsite = subprocess.run(
        [vpy, "-c",
         "import sysconfig; print(sysconfig.get_paths()['purelib'])"],
        check=True, capture_output=True, text=True).stdout.strip()
    with open(os.path.join(vsite, "zz_base_env.pth"), "w") as f:
        f.write(base_purelib + "\n")

    env = dict(os.environ)
    env["PYTHONPATH"] = ""  # the venv must import the INSTALLED package
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    # offline install: no index, no deps (baked into the base env),
    # no build isolation (system setuptools compiles the native libs)
    subprocess.run(
        [vpy, "-m", "pip", "install", "--no-build-isolation", "--no-index",
         "--no-deps", "--quiet", REPO],
        check=True, env=env, timeout=600)

    site = subprocess.run(
        [vpy, "-c",
         "import sysconfig; print(sysconfig.get_paths()['purelib'])"],
        check=True, env=env, capture_output=True, text=True).stdout.strip()
    script = (f"analytics_zoo_tpu_site = {site!r}\n"
              "import os\n"
              "os.environ['XLA_FLAGS'] = "
              "'--xla_force_host_platform_device_count=8'\n" + QUICKSTART)
    out = subprocess.run([vpy, "-c", script], env=env, cwd=str(tmp_path),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    assert "QUICKSTART_OK" in out.stdout

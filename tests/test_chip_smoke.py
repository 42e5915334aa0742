"""The bring-up contract, as far as a CPU can check it.

``chip_smoke.py`` must fail without a TPU and name what it found; the
compile cache is placed from outside or at one fixed path; nothing on the
way to constructing a ``FrontDoor`` initialises a JAX backend (a parent that
holds the chip starves its workers); a kernel failure propagates instead of
turning into the XLA reference; an unknown device has no peaks.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_tpu_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr, proc.stderr[-2000:]
    # no result line: nothing JSON-shaped on stdout
    assert "{" not in proc.stdout, proc.stdout[-2000:]


def test_last_stdout_line_is_the_verdict_and_nothing_else(monkeypatch,
                                                          capsys):
    """The accelerator check reads the last stdout line and refuses any key
    beyond ``ok`` and ``device{platform, kind, count}``; the report with the
    phases is the line before it. Phases are stubbed: this is the shape of
    the output, not a run."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from analytics_zoo_tpu.common.runtime import device_info

    def devices(run):
        run.device = device_info()

    monkeypatch.setattr(smoke, "phase_devices", devices)
    for name in ("train", "kernels", "serve", "profile", "several_chips"):
        monkeypatch.setattr(smoke, f"phase_{name}", lambda run: {})
    assert smoke.main(["--rehearse"]) == 0
    report, verdict = map(json.loads, capsys.readouterr().out.splitlines())
    assert verdict == {"ok": True, "device": device_info()}
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert isinstance(verdict["device"]["count"], int)
    assert report["rehearsal"] is True and report["ok"] is True
    assert set(report["phases"]) >= {"devices", "train", "kernels", "serve",
                                     "profile"}
    assert list(report)[-1] == "claim" and report["claim"] is None


_CACHE_PROBE = (
    "import jax, analytics_zoo_tpu\n"
    "from analytics_zoo_tpu.common import runtime\n"
    "from jax._src import xla_bridge\n"
    "assert not xla_bridge.backends_are_initialized()\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(runtime.CHECKOUT_CACHE_DIR)\n")


def _cache_probe(env_dir):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.split()


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    configured, _checkout = _cache_probe(str(tmp_path))
    assert configured == str(tmp_path)


def test_compile_cache_defaults_to_the_checkout():
    configured, checkout = _cache_probe(None)
    assert checkout == os.path.join(REPO, ".jax_cache")
    assert configured == checkout


def test_frontdoor_and_build_info_initialise_no_backend():
    """In a fresh interpreter (this one has long since initialised the CPU
    backend): construct a FrontDoor, register build_info, look at what JAX
    has brought up."""
    code = (
        "from jax._src import xla_bridge\n"
        "from analytics_zoo_tpu.common.observability import ("
        "MetricsRegistry, build_info)\n"
        "from analytics_zoo_tpu.serving.frontdoor import ("
        "FrontDoor, FrontDoorConfig)\n"
        "reg = MetricsRegistry()\n"
        "build_info(reg)\n"
        "fd = FrontDoor(FrontDoorConfig(spec='nowhere:build', workers=2))\n"
        "assert xla_bridge._backends == {}, xla_bridge._backends\n"
        "assert 'backend=\"uninitialized\"' in reg.render(), reg.render()\n"
        "import jax; jax.devices()\n"
        "build_info(reg)\n"
        "text = reg.render()\n"
        "assert 'backend=\"cpu\"' in text, text\n"
        "assert text.count('zoo_build_info{') == 1, text\n")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, timeout=120,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"), check=True)


def test_attention_propagates_a_kernel_runtime_error(monkeypatch):
    """``XlaRuntimeError`` is a ``RuntimeError``: a kernel that fails to
    compile must surface, not be answered by the O(S^2) reference."""
    from analytics_zoo_tpu.ops import attention, flash_attention

    def boom(*_a, **_kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(flash_attention, "flash_attention", boom)
    q = jnp.zeros((1, 1, 128, 64), jnp.float32)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        attention.scaled_dot_product_attention(q, q, q, use_flash=True)


def test_peaks_lookup_raises_on_an_unknown_device_kind():
    from analytics_zoo_tpu.common.runtime import PEAKS, device_peaks

    assert device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="cpu"):
        device_peaks("cpu")
    assert "cpu" not in PEAKS

"""``chip_smoke.py`` refuses any platform but a TPU, and never reports
success off the chip."""
import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("devices", [
    [types.SimpleNamespace(platform="cpu", device_kind="cpu")],
    [types.SimpleNamespace(platform="gpu", device_kind="NVIDIA H100")],
    [],
], ids=["cpu", "gpu", "none"])
def test_device_check_refuses_non_tpu(devices):
    with pytest.raises(SystemExit) as exc:
        _smoke().device_info(devices)
    # a message exits with status 1
    assert isinstance(exc.value.code, str) and "needs a TPU" in exc.value.code


def test_device_check_reports_tpu_record():
    devs = [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
    assert _smoke().device_info(devs) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_smoke_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout
    assert "needs a TPU" in run.stderr
    for line in run.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)

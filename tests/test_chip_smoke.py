"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script's own device check wants a TPU; these tests point it at the CPU
and shrink its sizes from here, so every phase, check and the last-line
contract run on each change without a chip.  The persistent compile cache
stays off: tests write no cache.
"""
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

TINY = dict(PLATFORM="cpu", LOG_N=10, BATCH_N=1024, SERVICE_REQUESTS=8,
            SERVICE_SIZE=256, SHARDED_LOG_N=11)


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(monkeypatch):
    import repro.matching
    monkeypatch.setattr(repro.matching, "enable_persistent_compile_cache",
                        lambda: "off")
    return _load()


def test_chip_smoke_one_chip_phases_on_cpu(smoke, monkeypatch, capsys):
    for name, value in TINY.items():
        monkeypatch.setattr(smoke, name, value)
    assert smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    for phase in ("single", "batch", "service"):
        assert f"[{phase}] ok" in "\n".join(lines), phase
    # three single-graph runs, the batch and the service each name the
    # variant and warm start they ran
    assert sum(bool(re.search(r" apfb-gpubfs_wr\+(karp_sipser|cheap) |"
                              r" apsb-gpubfs_wr-exact\+karp_sipser ", ln))
               for ln in lines) == 5


def test_chip_smoke_refuses_a_host_without_the_chip(smoke, capsys):
    assert smoke.main([]) == 1
    assert capsys.readouterr().out == ""


def test_chip_smoke_four_chip_path_on_four_cpu_devices():
    code = (
        "import sys, chip_smoke, repro.matching\n"
        "repro.matching.enable_persistent_compile_cache = lambda: 'off'\n"
        f"for k, v in {TINY!r}.items(): setattr(chip_smoke, k, v)\n"
        "sys.exit(chip_smoke.main(['--chips', '4']))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{REPO}{os.pathsep}{REPO}/src")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1])["device"]["count"] == 4
    runs = [ln for ln in lines if "over 4 devices warm_start=" in ln]
    assert len(runs) == 3
    assert all("single_chip=" in ln for ln in runs)
    assert sum("identical_to_single_chip=True" in ln for ln in lines) == 1


def test_chip_smoke_alone_fails_without_printing_a_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout == ""

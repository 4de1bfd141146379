"""A whole run of each cell on the CPU at a tiny size, with the look for a
chip skipped: sound, and with the timed path broken underneath, where
``correct`` has to come out false."""
import argparse
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import run
from traffic import generate

# each mix cut to a size the CPU runs in seconds; nothing else changes
TINY = {"kron18": {"scale": 9}, "er18": {"log2_n": 10},
        "mixed-overload": {"size_hint": 64, "rate_per_s": 40.0}}
SPEC = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]
SERVICE = [w["name"] for w in SPEC["workloads"]
           if w["config"] == "assign-service"]


@pytest.fixture
def tiny(monkeypatch):
    real = generate.load
    monkeypatch.setattr(generate, "load",
                        lambda name: dict(real(name), **TINY[name]))


def run_tiny(capsys, name, trace=0, seconds=1.5, seed=2**33 + 5):
    cell = next(w for w in SPEC["workloads"] if w["name"] == name)
    config = run.load_json(os.path.join(run.BENCH, "configs",
                                        f"{cell['config']}.json"))
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=trace)
    assert run.run_cell(SPEC, cell, config, args, jax.devices()[:1]) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny, capsys, name):
    result, err = run_tiny(capsys, name)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    want = {m["name"] for m in run.cell_metrics(SPEC, name, False)}
    assert set(result["metrics"]) == want and "setup_s" in want
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check unanswered 0 limit")
    assert result["device"]["count"] == 1


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_per_layer_metrics(tiny, capsys, name):
    result, _ = run_tiny(capsys, name, trace=1)
    assert result["correct"] is True
    allowed = {m["name"] for m in run.cell_metrics(SPEC, name, True)}
    assert result["metrics"] and set(result["metrics"]) <= allowed
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def _wrap_program(monkeypatch, alter):
    """Break ``Matcher``'s compiled program underneath every entry point:
    ``alter(out_state, in_state)`` is applied to what it returns."""
    from repro.matching import Matcher
    real = Matcher.program

    def program(self, graph, cold=True):
        fn = real(self, graph, cold)
        return lambda g, s: alter(fn(g, s), s)

    monkeypatch.setattr(Matcher, "program", program)


def unchanged(out, state):
    """A step that returns its state unchanged."""
    return state


def half_batch(out, state):
    """Half of the batch left out: its lanes come back unsolved."""
    if not out.cmatch.ndim == 2:
        return out
    keep = out.cmatch.shape[0] // 2
    return jax.tree.map(lambda o, s: jnp.concatenate([o[:keep], s[keep:]]),
                        out, state)


def altered(out, state):
    """An answer altered where it is produced: column 0 moved one row on."""
    nr = out.rmatch.shape[-1] - 1
    cm = out.cmatch.at[..., 0].set((out.cmatch[..., 0] + 1) % nr)
    return out.__class__(cmatch=cm, rmatch=out.rmatch, phases=out.phases,
                         fallbacks=out.fallbacks, certified=out.certified)


FAULTS = [(f, c) for f in (unchanged, altered) for c in CELLS]
FAULTS += [(half_batch, c) for c in SERVICE]


@pytest.mark.parametrize("fault,name", FAULTS,
                         ids=[f"{f.__name__}-{c}" for f, c in FAULTS])
def test_broken_timed_path_is_not_correct(tiny, capsys, monkeypatch, fault,
                                          name):
    _wrap_program(monkeypatch, fault)
    result, err = run_tiny(capsys, name)
    assert result["correct"] is False and result["failed"] > 0
    checks = result["checks"]
    assert any(c["value"] > c["limit"] for c in checks.values())
    assert "limit" in err.strip().splitlines()[-1]


def _run_script(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run_script(["--workload", "transversal.kron18", "--seed", "3",
                     "--seconds", "1", "--trace", "0"], run.ROOT)
    assert p.returncode == 1
    assert "{" not in p.stdout
    assert "needs 1 tpu" in p.stderr


def test_checkout_of_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    p = _run_script(["--workload", "transversal.kron18", "--seed", "3",
                     "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_traffic_sets_the_configured_size():
    """A configuration states its cut once; each graph mix draws exactly
    that many vertices per side."""
    for w in SPEC["workloads"]:
        config = run.load_json(os.path.join(run.BENCH, "configs",
                                            f"{w['config']}.json"))
        mix = generate.load(w["traffic"])
        if "vertices_per_side" in config:
            log2_n = mix["scale"] if mix["family"] == "kron" else mix["log2_n"]
            assert 1 << int(log2_n) == config["vertices_per_side"], w["name"]

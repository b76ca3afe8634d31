"""Rehearsal of ``chip_smoke.py`` without the chip: its phases at a tiny
size on the CPU, with the Pallas kernels interpreted, and the script's
refusal to run (no result line, non-zero exit) where JAX finds no TPU."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def watch(smoke):
    return smoke.CompileWatch()


def tiny(smoke, name):
    # both of the smoke's modes: at this budget high-perf finds feasible
    # designs to re-evaluate and serve, low-power finds none
    return smoke.paper_spec(name, nodes=[7, 28], episodes=64, lanes=4)


def test_one_chip_phases_at_tiny_size(smoke, watch, tmp_path):
    phases = smoke.run_one_chip(tiny(smoke, "tiny"), str(tmp_path), watch,
                                interpret=True)
    assert list(phases) == ["campaign", "determinism", "reference",
                            "recommend"]


def test_multichip_phase_on_one_device(smoke, watch, tmp_path):
    smoke.multichip_phase(tiny(smoke, "tiny-mc"), 1, str(tmp_path), watch)


def test_recommend_phase_raises_what_stops_the_server(smoke, monkeypatch):
    """A server that cannot start fails the phase at once, with its own
    error, instead of a timeout."""
    from types import SimpleNamespace

    from repro.launch import serve

    def refuse(roots, **kw):
        raise ValueError("archive index holds no frontier points")

    monkeypatch.setattr(serve, "recommend_server", refuse)
    with pytest.raises(ValueError, match="no frontier points"):
        smoke.recommend_phase(SimpleNamespace(root="unused"))


def test_no_result_line_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)

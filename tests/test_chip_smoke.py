"""``chip_smoke.py`` on the CPU: the tiny rehearsal passes end to end, and
without ``--rehearse`` the script refuses a backend that is not a TPU."""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # its dataclasses resolve through it
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def test_rehearsal_passes_on_cpu(chip_smoke, capsys):
    assert chip_smoke.main(["--rehearse"]) == 0
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
    for phase in ("join[pallas] vs float64", "pallas vs xla",
                  "byte_identical=True", "serving vs float64"):
        assert phase in out


def test_refuses_without_a_chip(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "no TPU" in out

"""``chip_smoke.py`` fails loudly: no TPU, or a forced engine failure,
means a non-zero exit and no result line — never a fallback answer.

The platform check is steered here, in the test; the script has no
option to skip it."""
from __future__ import annotations

import importlib.util
import pathlib

import jax
import pytest

from repro.resilience import default_policy, faultinject, set_default_policy

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    prev = default_policy()
    yield mod
    # the smoke session installs its one-attempt policy process-wide
    set_default_policy(prev)


def test_smoke_refuses_a_host_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "needs a TPU" in err


def test_forced_engine_failure_fails_the_smoke(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    with faultinject.scoped("crash@chunk:0"):
        rc = smoke.main([])
    assert rc != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "FAILED" in err and "injected crash at chunk:0" in err

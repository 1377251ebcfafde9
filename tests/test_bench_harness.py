"""The benchmark driver's contract: a failed suite fails the run, suites
that emulate devices on the host CPU refuse any other platform, and the
peak table never lends one chip's numbers to another."""

import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import common  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402


@pytest.mark.parametrize("suite", ["table5", "table6"])
def test_emulating_suite_refuses_off_cpu_and_run_exits_nonzero(
    monkeypatch, capsys, suite
):
    import repro.core.plancache as plancache

    monkeypatch.setattr(plancache, "enable_persistent_cache", lambda root: None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sys, "argv", ["run", "--only", suite])
    with pytest.raises(SystemExit) as exc:
        bench_run.main()
    assert exc.value.code == 1
    out, err = capsys.readouterr()
    assert f"# SUITE {suite} FAILED" in out
    assert "emulates devices on the host CPU" in err
    assert f"FAILED suites: {suite}" in err


def test_require_cpu_emulation_passes_on_cpu():
    assert jax.default_backend() == "cpu"
    common.require_cpu_emulation("table5")


def test_device_peaks_keyed_by_device_kind():
    assert common.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        common.device_peaks("TPU v4")

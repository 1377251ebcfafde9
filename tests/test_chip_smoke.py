"""``chip_smoke.py`` at a tiny size on the CPU.

The script runs only on a TPU; here its phases run with the Pallas
interpreter and small chunks (so the merge ladder runs too), and every
check inside them compares against the script's NumPy reference.  The
references themselves are cross-checked against the system's own
D-bitmap and extraction code.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_scenario_matches_reference(capsys):
    smoke = _smoke()
    smoke.run_one_chip(
        8192, 3, smoke.Phases(), interpret=True,
        chunk_size=2048, chunk_threshold=4096,
    )
    out = capsys.readouterr().out
    assert "interpret=True" in out
    assert "incremental=True" in out
    assert out.count(": pass") == 6


def test_smoke_references_agree_with_system(rng):
    """The script's NumPy D-bitmap and extraction equal the system's."""
    import jax.numpy as jnp

    from repro.core.compress import extract_bits, make_plan
    from repro.core.dbits import compute_dbitmap

    smoke = _smoke()
    words = smoke.make_table(3000, 1).words
    order = smoke.ref_order(words)
    bitmap = smoke.ref_dbitmap(words[order])
    np.testing.assert_array_equal(bitmap, np.asarray(compute_dbitmap(jnp.asarray(words))))
    np.testing.assert_array_equal(
        smoke.ref_extract(words, bitmap),
        np.asarray(extract_bits(jnp.asarray(words), make_plan(bitmap, words.shape[1]))),
    )


def test_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr

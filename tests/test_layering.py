"""Imports point one way: cluster -> providers -> featcache and carbon ->
policy -> engine. Each layer below the engine imports without loading
``repro.core.api`` (checked in a fresh interpreter, so no other test's
imports leak in)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


@pytest.mark.parametrize("module", ["providers", "featcache", "carbon",
                                    "policy"])
def test_layer_imports_without_the_engine(module):
    code = (f"import sys, repro.core.{module}; "
            "print('repro.core.api' in sys.modules)")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "False"

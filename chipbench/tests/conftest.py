"""Tests of the benchmark's yardstick: CPU only, tiny sizes.

Nothing here touches the chip or the TPU's library: the harness's look
for a chip is skipped by calling ``run.run_cell`` directly, and the
solver entry points run with 64-bit mode on, as the program's own tests
do.
"""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

jax.config.update("jax_enable_x64", True)

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def data_dir():
    return DATA

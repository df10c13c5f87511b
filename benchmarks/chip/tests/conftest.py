"""Tests of the chip benchmark's own code, run on the CPU at small
sizes.  They import no accelerator library: jax is held to the CPU."""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def cpu_as_chip(monkeypatch):
    """A run's device rule pointed at the CPU: ``chip_devices`` takes the
    CPU device in place of the TPU chips, and the roofline readers get a
    v5e's published peaks."""
    import jax

    from chipbench import cell, manifest
    monkeypatch.setattr(cell, "chip_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(cell, "peaks",
                        lambda kind: manifest.peaks("TPU v5 lite"))

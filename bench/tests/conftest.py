"""Tests of the benchmark itself, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They put ``bench/`` and the program's ``src/`` on the path."""
import dataclasses
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE = {
    "dense": dict(num_layers=4, d_model=128, num_heads=4, num_kv_heads=4,
                  head_dim=32, d_ff=256, vocab_size=512),
}


def smoke_cell(name: str, seq: int = 64):
    """The cell ``name`` at a size a CPU test holds: every width cut,
    the traffic's sequence shortened; its limits unchanged."""
    from harness import spec
    cell = spec.resolve(spec.load_spec(), name)
    c = dict(cell.config)
    c.update(SMOKE[c["family"]])
    t = dict(cell.traffic)
    t.update(seq=seq)
    return dataclasses.replace(cell, config=c, traffic=t)


@pytest.fixture
def cpu_peaks(monkeypatch):
    """A peak table entry for the CPU, which `peaks.json` rightly lacks."""
    from harness import device
    monkeypatch.setattr(device, "peaks", lambda kind, path=None: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})

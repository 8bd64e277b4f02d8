"""``chip_smoke.py`` rehearsed on the CPU at smoke size.

The script's phases run here on the smoke config, with the Pallas
kernels in their interpreter where the chip would compile them, so its
checks (completions, token counts, leaked pages, the kernel and logits
comparisons, the remesh's offsets, losses and shardings) are exercised
without a chip.  The remesh runs in a subprocess: it needs four fake
devices.
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_backend_other_than_tpu(capsys):
    assert _load().main([]) != 0
    out = capsys.readouterr()
    assert "platform 'cpu'" in out.err
    assert '"ok"' not in out.out


def test_serve_phase_at_smoke_size(monkeypatch, capsys):
    """Every check of the serving phase passes at smoke size; the last,
    that the decode step holds a compiled kernel, rightly fails, since
    the Pallas interpreter lowers to plain XLA ops."""
    from repro.kernels import platform
    from repro.kernels.decode_attention import ops

    smoke = _load()
    monkeypatch.setattr(platform, "compiled_kernels", lambda: True)
    monkeypatch.setattr(ops, "resolve_interpret", lambda interpret: True)
    with pytest.raises(smoke.SmokeFailure, match="no Pallas kernel"):
        smoke.serve_phase(0, full_size=False, slots=4, max_len=64, page=16,
                          prompt_lens=(8, 24), new_tokens=4)
    out = capsys.readouterr().out
    assert '"phase": "serve"' in out
    assert '"completed": 4' in out
    assert '"leaked_pages": 0' in out


def test_remesh_phase_on_four_cpu_devices():
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); import chip_smoke; "
        "chip_smoke.remesh_phase(0, layers=2, full_width=False, batch=8, "
        "seq_len=32); print('REMESH OK')"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code, str(ROOT)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().endswith("REMESH OK")
    assert '"offsets_equal": true' in proc.stdout

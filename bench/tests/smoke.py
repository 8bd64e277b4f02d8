"""Small cells for the CPU tests: the manifest's cells with the
program's smoke-size MiniCPM and short traffic, run without a chip."""

from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH / "drivers", BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import common  # noqa: E402

SMOKE_SIZES = {
    "arch": "minicpm-2b", "smoke": True, "hidden_size": 72, "intermediate_size": 144,
    "num_attention_heads": 6, "num_key_value_heads": 6, "num_hidden_layers": 3,
    "vocab_size": 512, "rope_theta": 500000.0, "published": {"num_hidden_layers": 3},
}


# program's readings on the CPU at smoke size over 11 seeds (loss, grad,
# delta, each leaf against its own norm; served gap): <= 0.0044, 0.0061,
# 0.040; 0.021.  Control on 4 seeds: >= 0.0034 (loss), 0.019, 0.016;
# 0.34.  Half the batch left out: >= 0.016, 0.063, 0.060.  A state left
# unchanged reads 1.  The loss gap has no upper reading (the control
# reads under three times, the fault under ten times the program), so it
# is not compared, as on the chip.
SMOKE_LIMITS = {"train": {"grad_norm_gap": 0.012, "delta_norm_gap": 0.25},
                "serve": {"served_logit_gap": 0.1}}


def cell(name: str) -> dict:
    """The manifest's cell ``name`` at smoke size."""
    c = copy.deepcopy(common.cell(name))
    c["config"].update(copy.deepcopy(SMOKE_SIZES))
    if c["config"]["kind"] == "serve":
        c["config"]["serving"].update(slots=4, max_len=128, pages=33)
        lengths = [8, 16, 24, 40]
        c["traffic"].update(rate_per_s=40.0, prompt_lengths=lengths,
                            prompt_median=16, output_median=8, output_min=4,
                            output_max=24, max_total=100, check_tokens=120,
                            check_requests=8)
    else:
        c["config"]["training"].update(seq_len=32, rows_per_chip=2)
        c["traffic"].update(doc_median=20, doc_max=200)
    c["config"]["num_hidden_layers"] = 3
    # the smoke model's rounding reads differently from the chip's at the
    # published widths, so its limits are set from its own readings on the
    # CPU, between the program's and the control's as the chip's are
    c["limits"] = dict(SMOKE_LIMITS[c["config"]["kind"]])
    return c


def cpu_device(c: dict) -> dict:
    return {"platform": "cpu", "kind": "cpu", "count": c["workload"]["chips"]}

"""A configuration file's sizes, for the reference and for the program.

``sizes`` reads only the file.  ``program_arch`` builds the program's
own architecture config for it and refuses one whose sizes differ, so a
later change to the program's presets cannot silently change a cell."""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

from common import BenchError


def sizes(conf: dict) -> Dict:
    dep = conf["program_departures"]
    published_layers = conf["published"]["num_hidden_layers"]
    return {
        "d": conf["hidden_size"],
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["hidden_size"] // conf["num_attention_heads"],
        "ff": conf["intermediate_size"],
        "vocab": conf["vocab_size"],
        "layers": conf["num_hidden_layers"],
        "residual_scale": conf["scale_depth"] / math.sqrt(published_layers),
        "eps": dep["rms_norm_eps"],
        "rope_theta": conf["rope_theta"],
    }


def program_arch(conf: dict):
    from repro.config import get_arch

    s = sizes(conf)
    arch = dataclasses.replace(get_arch(conf["arch"], smoke=conf.get("smoke", False)),
                               num_layers=s["layers"])
    have = {"d": arch.d_model, "heads": arch.num_heads, "kv_heads": arch.num_kv_heads,
            "head_dim": arch.resolved_head_dim, "ff": arch.d_ff,
            "vocab": arch.vocab_size, "layers": arch.num_layers,
            "eps": arch.norm_eps, "rope_theta": arch.rope_theta}
    wrong = {k: (v, s[k]) for k, v in have.items() if v != s[k]}
    if not math.isclose(arch.residual_scale, s["residual_scale"], rel_tol=1e-9):
        wrong["residual_scale"] = (arch.residual_scale, s["residual_scale"])
    if not arch.tie_embeddings or arch.logit_softcap or arch.parallel_block:
        wrong["layout"] = "not a tied, plain pre-norm decoder"
    if wrong:
        raise BenchError(f"the program's {conf['arch']!r} differs from "
                         f"{conf['name']}: {wrong} (program, file)")
    return arch

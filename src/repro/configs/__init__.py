"""Assigned architecture configs. Importing this package registers all of
them with the config registry (``repro.config.get_arch``)."""

from repro.configs import (  # noqa: F401
    gemma3_4b,
    minicpm_2b,
    llama3_2_1b,
    command_r_plus_104b,
    mixtral_8x7b,
    llama4_maverick_400b_a17b,
    internvl2_1b,
    jamba_v0_1_52b,
    whisper_tiny,
    mamba2_370m,
    deepseek_v2_lite,
)
from repro.configs.tcmm import TCMMConfig  # noqa: F401

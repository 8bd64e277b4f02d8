"""The architecture module reproduces, exactly, the numbers that the
harness gave before MiniCPM-2B's code moved into ``bench/archs``:
``parity.json`` was written by the harness as it stood then, at smoke
size on the CPU, from the seed and the inputs it holds."""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import smoke
import common
import reference
import serve
import weights as W

WANT = json.loads((Path(__file__).parent / "parity.json").read_text())
SEED = WANT["seed"]
FULL = ("minicpm-2b-serve.chat", "minicpm-2b-train.dp1")


@pytest.fixture(scope="module")
def arch():
    c = smoke.cell("minicpm-2b-train.dp1")
    A = common.arch(c["config"])
    return A, A.sizes(c["config"]), c


def norms(tree, A):
    return {k: float(v) for k, v in W.leaf_norms(tree, A.leaf_name).items()}


def test_sizes(arch):
    A, s, _ = arch
    assert s == WANT["sizes_smoke"]
    for name in FULL:
        assert A.sizes(common.cell(name)["config"]) == WANT["sizes_full"][name]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_program_weights(arch, dtype):
    A, s, _ = arch
    got = norms(W.program_params(A, SEED, s, jnp.dtype(dtype)), A)
    assert got == WANT["program_norms"][dtype]


def test_reference_weights(arch):
    A, s, _ = arch
    assert norms(A.train_params(SEED, s), A) == WANT["reference_norms"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("control", [False, True])
def test_served_gaps(arch, dtype, control):
    A, s, _ = arch
    inp = WANT["gaps_input"]
    got = reference.served_gaps(A, SEED, s, inp["seqs"], inp["prompt_lens"],
                                jnp.dtype(dtype), control=control)
    assert got == WANT["gaps"][f"{dtype}.{'control' if control else 'program'}"]


@pytest.mark.parametrize("kind", ["plain", "lowp", "half"])
def test_train_steps(arch, kind):
    A, s, c = arch
    batches = [np.asarray(b, np.int32) for b in WANT["train_input"]]
    kw = {"plain": {}, "lowp": {"lowp": True}, "half": {"half": True}}[kind]
    got = reference.train_steps(A, SEED, s, c["config"]["training"], batches, **kw)
    assert got == WANT["train"][kind]


@pytest.mark.parametrize("label", ("smoke",) + FULL)
def test_costs_and_counters(arch, label):
    A, s, c = arch
    conf = c["config"] if label == "smoke" else common.cell(label)["config"]
    ss = A.sizes(conf)
    w = {"queue_wait_s": [0.1], "decode_steps": 7, "decode_rows": 50,
         "kv_tokens": 12345, "prefill_tokens": 999}
    want = WANT["costs"][label]
    assert serve.counters(w, {"config": conf}) == want["counters"]
    assert A.decode_flops(ss, 8, 4000) == want["decode_flops"]
    assert A.train_flops_per_token(ss, 1024) == want["train_flops_per_token"]

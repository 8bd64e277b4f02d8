"""The plain reference against the program at smoke size on the CPU.

Both run in float32 here (the program with float32 compute), so they
differ by summation order alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import smoke  # noqa: F401  (puts bench/ and src/ on the path)
import common
import reference
import weights as W

SEED = 2**31 + 3


@pytest.fixture(scope="module")
def setup():
    from repro.models.zoo import build_model

    c = smoke.cell("minicpm-2b-train.dp1")
    A = common.arch(c["config"])
    s = A.sizes(c["config"])
    prog = build_model(A.program_arch(c["config"]), compute_dtype=jnp.float32,
                       param_dtype=jnp.float32)
    params = W.program_params(A, SEED, s, jnp.float32)
    tokens = np.random.default_rng(0).integers(3, s["vocab"], (2, 40)).astype(np.int32)
    return A, s, prog, params, tokens


def test_layout_matches_program(setup):
    _, s, prog, params, _ = setup
    want = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(want)] == [a.shape for a in jax.tree.leaves(params)]


def test_forward_logits_match_program(setup):
    A, s, prog, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        want, _ = prog.train_logits(params, {"tokens": jnp.asarray(tokens)})
    h, tok = A.hidden(SEED, s, tokens, jnp.float32)
    got = np.asarray(jnp.einsum("ntd,vd->ntv", h, tok, precision="highest"))
    want = np.asarray(want)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_served_gap_is_zero_for_reference_argmax(setup):
    A, s, _, _, tokens = setup
    h, tok = A.hidden(SEED, s, tokens[:1, :20], jnp.float32)
    greedy = list(tokens[0, :20])
    lg = np.asarray(jnp.einsum("d,vd->v", h[0, -1], tok, precision="highest"))
    greedy.append(int(lg.argmax()))
    gaps = reference.served_gaps(A, SEED, s, [greedy], [20], jnp.float32)
    assert gaps[0] == pytest.approx(0.0, abs=1e-6)
    wrong = greedy[:-1] + [int(lg.argmin())]
    got = reference.served_gaps(A, SEED, s, [wrong], [20], jnp.float32)[0]
    assert got == pytest.approx(float((lg.max() - lg.min()) / lg.std()), rel=1e-4)


def test_loss_and_gradients_match_program(setup):
    A, s, prog, params, tokens = setup
    batch = {"tokens": jnp.asarray(tokens[:, :-1]), "labels": jnp.asarray(tokens[:, 1:])}
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(prog.loss_fn, has_aux=True)(params, batch)
    rp = A.train_params(SEED, s)
    n = tokens[:, 1:].size
    with jax.default_matmul_precision("highest"):
        rloss, rgrads = jax.value_and_grad(A.nll_sum)(
            rp, batch["tokens"], batch["labels"], reference.sizes_key(s), False)
    assert float(rloss) / n == pytest.approx(float(loss), rel=1e-5)
    got = {k: float(v) for k, v in W.leaf_norms(jax.tree.map(lambda g: g / n, rgrads),
                                                A.leaf_name).items()}
    want = {k: float(v) for k, v in W.leaf_norms(grads, A.leaf_name).items()}
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-4), k


def test_control_rounds_to_float8():
    x = jnp.linspace(-3.0, 3.0, 101)
    q = reference._fp8(x)
    assert 0 < float(jnp.abs(q - x).max()) <= 3.0 / 16
    assert float(jnp.abs(q - x).max()) > 3.0 / 256

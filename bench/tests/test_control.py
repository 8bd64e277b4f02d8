"""The control, at smoke size on the CPU: the float32 reference put in
the program's place with its matrix products in float8 must fail one of
the cell's numbers against the cell's own limits."""

import pytest

import smoke
import control

SEED = 2**31 + 21


@pytest.mark.parametrize("name", ["minicpm-2b-serve.chat"])
def test_serving_control_fails(name):
    c = smoke.cell(name)
    out = control.serve_seed(c, SEED, 2.0, control=True)
    limit = c["limits"]["served_logit_gap"]
    assert out["program"] <= limit < out["control"], out


def test_training_control_fails():
    c = smoke.cell("minicpm-2b-train.dp1")
    out = control.train_seed(c, SEED, control=True)
    lim = c["limits"]
    keys = [k for k in ("loss_gap", "grad_norm_gap", "delta_norm_gap") if k in lim]
    assert all(out["program"][k] <= lim[k] for k in keys), out
    assert any(out["control"][k] > lim[k] for k in keys), out
    assert any(out["fault_half_batch"][k] > lim[k] for k in keys), out

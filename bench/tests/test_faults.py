"""A run with its timed path broken underneath must come out not
correct: for each fault a cell can have, the harness is driven at smoke
size on the CPU (its look for a chip skipped) with the program's step
replaced by a broken one."""

import pytest

import smoke
import run

SEED = 2**31 + 5


def broken_decode(kind):
    from repro.serving import serve_step

    orig = serve_step.make_decode_step

    def make(model, temperature=0.0):
        step = orig(model, temperature)

        def bad(params, tokens, cache, positions, rng, frontend=None):
            nt, new_cache, rng = step(params, tokens, cache, positions, rng)
            if kind == "token":
                nt = (nt + 1) % model.cfg.vocab_size
            elif kind == "state":
                new_cache = cache
            elif kind == "half":
                # half the rows left out: they keep the token they had
                half = nt.shape[0] // 2
                nt = nt.at[:half].set(tokens[:half, 0])
            return nt, new_cache, rng

        return bad

    return make


@pytest.mark.parametrize("kind", ["token", "state", "half"])
@pytest.mark.parametrize("name", ["minicpm-2b-serve.chat"])
def test_serving_fault_is_not_correct(name, kind, monkeypatch):
    from repro.serving import elastic

    monkeypatch.setattr(elastic, "make_decode_step", broken_decode(kind))
    c = smoke.cell(name)
    r = run.run(c, SEED, 2.0, False, smoke.cpu_device(c))
    assert not r["correct"], r["checks"]


def broken_train_step(kind):
    from repro.training import train_step

    orig = train_step.make_train_step

    def make(model, tcfg):
        step = orig(model, tcfg)

        def bad(state, batch):
            if kind == "state":
                _, m = step(state, batch)
                return state, m
            half = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:half] for k, v in batch.items()})

        return bad

    return make


@pytest.mark.parametrize("kind", ["state", "half"])
def test_training_fault_is_not_correct(kind, monkeypatch):
    from repro.training import job

    monkeypatch.setattr(job, "make_train_step", broken_train_step(kind))
    c = smoke.cell("minicpm-2b-train.dp1")
    r = run.run(c, SEED, 1.0, False, smoke.cpu_device(c))
    assert not r["correct"], r["checks"]

"""A run whose timed path is broken underneath comes out not correct:
one case for each fault the cell can have.  The rest of the run is the
real harness, on the CPU at a small size, against the cell's own limits.

Serving: a token altered where the engine produces it, and a CF answer
altered where the head produces it.  Training on one chip: a step that
returns its state unchanged, and a step that leaves out half of its
batch and takes the mean over the rest.  (The exchange between chips is
a fault only a four-chip cell can have.)"""
import json

import jax
import jax.numpy as jnp
import pytest

import tiny
from benchlib import harness

SEED = 7_000_000_001


def run(kind, capsys):
    cell = tiny.cell(kind)
    harness.main(["--workload", cell.name, "--seed", str(SEED),
                  "--seconds", "2", "--trace", "0"],
                 devices=jax.devices(), cell=cell)
    return json.loads(capsys.readouterr()[0].strip().splitlines()[-1])


def _token_altered(monkeypatch):
    from repro.serving import engine
    greedy = engine._greedy_tokens
    monkeypatch.setattr(engine, "_greedy_tokens",
                        lambda lg: (greedy(lg) + 1) % lg.shape[-1])


def _cf_answer_altered(monkeypatch):
    from repro.serving import cf_head
    score = cf_head.CFHead.score

    def bad(self, *a, **kw):
        out = score(self, *a, **kw)
        out["cf"] = out["cf"].copy()
        out["cf"][0] += 1.0
        out["fused"] = out["fused"].copy()
        out["fused"][0] += 1.0
        return out
    monkeypatch.setattr(cf_head.CFHead, "score", bad)


def _state_unchanged(monkeypatch):
    from repro.optimizer import adamw
    monkeypatch.setattr(adamw, "adamw_apply",
                        lambda params, grads, opt, *a, **kw: (params, opt))


def _half_batch(monkeypatch):
    from repro.models import transformer as tf
    loss_fn = tf.loss_fn

    def half(cfg, params, batch, *a, **kw):
        n = batch["tokens"].shape[0] // 2
        return loss_fn(cfg, params, jax.tree.map(lambda x: x[:n], batch),
                       *a, **kw)
    monkeypatch.setattr(tf, "loss_fn", half)


@pytest.mark.parametrize("kind,fault", [
    ("serve", _token_altered), ("serve", _cf_answer_altered),
    ("train", _state_unchanged), ("train", _half_batch)])
def test_fault_is_not_correct(kind, fault, monkeypatch, capsys):
    fault(monkeypatch)
    res = run(kind, capsys)
    assert res["correct"] is False, res["checks"]


def test_sound_run_is_correct(capsys):
    assert run("train", capsys)["correct"] is True

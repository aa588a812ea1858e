"""A run whose timed path is broken underneath comes out not correct:
one case for each fault the cell can have.  The rest of the run is the
real harness, on the CPU at a small size, against the cell's own limits.

Serving: a token altered where the engine produces it, and a CF answer
altered where the head produces it.  Training on one chip: a step that
returns its state unchanged, and a step that leaves out half of its
batch and takes the mean over the rest (``bench/tools/faults.py``).  The
exchange between chips is a fault only a four-chip cell can have:
``test_multichip.py``."""
import importlib.util
import json

import jax
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


def tool(name: str):
    """A module of ``bench/tools``, loaded from its file."""
    path = tiny.spec.BENCH_DIR / "tools" / f"{name}.py"
    sp = importlib.util.spec_from_file_location(f"bench_tool_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


FAULTS = tool("faults")


@pytest.mark.parametrize("kind,fault", [
    ("serve", _token_altered), ("serve", _cf_answer_altered),
    ("train", FAULTS.state_unchanged), ("train", FAULTS.half_batch)],
    ids=lambda v: v if isinstance(v, str) else "_" + v.__name__.lstrip("_"))
def test_fault_is_not_correct(kind, fault, monkeypatch, capsys):
    fault(monkeypatch)
    res = run(kind, capsys)
    assert res["correct"] is False, res["checks"]


def test_sound_run_is_correct(capsys):
    assert run("train", capsys)["correct"] is True

"""A run with the timed serving path broken underneath comes out not
correct, for each fault a one-chip serving cell can have: a decode step
that returns its KV pages unchanged, half of the batch's lanes left
out, and a token altered where it is sampled. (The exchange between
chips does not exist on one chip.)"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells import run, tiny_serving_cell
from repro.serving import compile as serving_compile
from repro.serving import scheduler


def _pages_unchanged(monkeypatch):
    orig = serving_compile.CompiledDecodeStep.__call__

    def step(self, kwargs):
        kept = {k: jnp.copy(v) for k, v in kwargs.items()
                if k.startswith(("kp", "vp"))}
        out = dict(orig(self, kwargs))
        out.update(kept)
        return out

    monkeypatch.setattr(serving_compile.CompiledDecodeStep, "__call__", step)


def _half_batch(monkeypatch):
    orig = serving_compile.CompiledDecodeStep.__call__

    def step(self, kwargs):
        out = dict(orig(self, kwargs))
        lg = out["logits"]
        half = lg.shape[0] // 2
        if half:
            out["logits"] = lg.at[half:].set(lg[:1])
        return out

    monkeypatch.setattr(serving_compile.CompiledDecodeStep, "__call__", step)


def _token_altered(monkeypatch):
    orig = scheduler.Scheduler._sample

    def sample(self, row):
        return (orig(self, row) + 1) % int(np.asarray(row).shape[-1])

    monkeypatch.setattr(scheduler.Scheduler, "_sample", sample)


@pytest.mark.parametrize("fault", [_pages_unchanged, _half_batch,
                                   _token_altered])
def test_fault_makes_run_incorrect(monkeypatch, fault):
    fault(monkeypatch)
    # more offered than two slots serve keeps both full in every step,
    # whatever the timing, and answers longer than their prompts make the
    # decode step's own K/V most of what later tokens attend to
    cell = tiny_serving_cell()
    cell.traffic.update(rate_per_s=4.0,
                        prompt={"dist": "cycle", "values": [16]},
                        output={"dist": "cycle", "values": [32, 40]})
    out = run(cell, seed=2**32 + 9)
    assert not out.correct, out.checks

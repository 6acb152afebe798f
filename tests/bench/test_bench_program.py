"""The AXPYDOT cell's driver on the CPU, with the harness's chip checks
skipped: a sound run is correct; the bfloat16 control, a dot over half
of the elements scaled up, and an altered answer are not."""
import jax.numpy as jnp
import pytest

from bench_cells import run, tiny_program_cell
from bench import harness, program
from repro.kernels.axpydot import ops


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    # on the CPU the kernel runs in the Pallas interpreter
    monkeypatch.setattr(program, "require_compiled", lambda r, c: None)


def test_sound_run_is_correct():
    out = run(tiny_program_cell(1 << 16), seed=2**33 + 1, seconds=0.5)
    assert out.correct, out.checks
    assert out.attempted > 0 and out.end_to_end["program_ms"] > 0


@pytest.mark.parametrize("seed", [1, 2, 2**32 + 3])
def test_bfloat16_control_fails_the_limit(seed):
    cell = tiny_program_cell(1 << 16)
    inputs = program.make_inputs(cell.config, seed)
    ref_mod = harness.reference(cell.config)
    host = {k: jnp.asarray(v) for k, v in inputs.items()}
    import numpy as np
    ref = ref_mod.axpydot(*(np.asarray(host[k]) for k in "axyw"))
    low = ref_mod.axpydot_low(*(host[k] for k in "axyw"), jnp.bfloat16)
    assert abs(low - ref) / abs(ref) > cell.config["check"]["max_rel_err"]


def test_window_reads_back_every_call_sent(monkeypatch):
    sent = []

    def counting(compiled, inputs):
        sent.append(1)
        return dispatch(compiled, inputs)

    dispatch = program.dispatch
    monkeypatch.setattr(program, "dispatch", counting)
    out = run(tiny_program_cell(), seed=3, seconds=0.5)
    assert out.correct, out.checks
    # two calls warm up before the window
    assert out.attempted == len(sent) - 2 > 0


def test_mix_sets_client_options_before_the_client(monkeypatch):
    import jax
    cell = tiny_program_cell()
    set_to = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_to.append((k, v)))
    harness.configure_client(cell)
    assert set_to == [("jax_pjrt_client_create_options",
                       cell.traffic["client_options"])]


def _half(orig):
    def f(a, x, y, w, **kw):
        n = x.shape[0] // 2
        return orig(a, x[:n], y[:n], w[:n], **kw) * 2.0
    return f


def _altered(orig):
    def f(a, x, y, w, **kw):
        return orig(a, x, y, w, **kw) * (1.0 + 1e-3)
    return f


@pytest.mark.parametrize("fault,n", [(_half, (1 << 16) + 1024),
                                     (_altered, (1 << 16) + 2048)])
def test_fault_makes_run_incorrect(monkeypatch, fault, n):
    monkeypatch.setattr(ops, "axpydot", fault(ops.axpydot))
    out = run(tiny_program_cell(n), seed=5, seconds=0.3)
    assert not out.correct, out.checks

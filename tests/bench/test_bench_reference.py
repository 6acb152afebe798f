"""The served path against the plain reference, at reduced widths on the
CPU: chunked prefill, the page scatter and the compiled paged decode
step agree with the float32 reference on logits, and the float8
control does not.

Readings at these sizes (120 served tokens): the program's mean logit
gap 0.0 on seeds 11 and 12, the control's 0.0055 and 0.0080; the limit
``TINY_MEAN_GAP`` lies between."""
import jax.numpy as jnp
import pytest

from bench_cells import TINY_MEAN_GAP, run, tiny_serving_cell
from bench import harness, serving


@pytest.mark.parametrize("arrivals", ["poisson", "on_off"])
def test_served_tokens_agree_with_reference(arrivals):
    out = run(tiny_serving_cell(arrivals), seed=2**33 + 17)
    assert out.correct, out.checks
    assert out.checks["mean_logit_gap"]["value"] <= TINY_MEAN_GAP
    assert out.checks["checked_tokens"]["value"] >= 40
    assert out.attempted > 0 and out.failed == 0
    assert set(out.end_to_end) >= {"output_tps"}


@pytest.mark.parametrize("seed", [11, 2**31 + 3])
def test_float8_control_fails_the_limit(seed):
    cell = tiny_serving_cell()
    sess = serving.setup(cell, seed, 2.0, lambda m: None)
    serving.measure(sess, cell.name, 2.0, False, harness.CompileCounter())
    checks = serving.check(sess, seed, lambda m: None,
                           control_dtype=jnp.float8_e4m3fn)
    assert checks["mean_logit_gap"]["value"] <= TINY_MEAN_GAP
    assert checks["control_mean_logit_gap"]["value"] > TINY_MEAN_GAP
    # the harness's own comparison rules the control not correct
    assert serving.passed(checks)
    assert not serving.passed(serving.as_control(checks))

"""What decides ``correct``, at a size a test run holds: the harness's own
run, with the device check skipped, comes out correct; the cell's control
(the reference at the precision below the configuration's, in the
program's place) comes out not correct; and so does a run whose timed
step is broken underneath, or whose engine serves a token other than the
one its step put first.

Readings at this size, over seeds 1-4 (program; control):
fp32 under bf16x6_pallas: logit_err ~4e-7; bf16x3 1.1e-5, gap 0 both.
bf16 on the default policies: logit_err 6.4e-3..9.0e-3; int8
2.4e-2..3.6e-2, gap 0..7.8e-3; int8 gap 9.6e-3..4.8e-2.
"""
import jax
import jax.numpy as jnp
import pytest

import repro.serving.engine as engine_module

import bench_tiny
from bench import check
from bench import run as R
from bench.drive import Clock


def _run(cell, seed, control=None):
    res, numbers, limits, _run = R.run_cell(cell, seed, 2.0, False, Clock(),
                                            None, control=control,
                                            log=lambda s: None)
    return res, numbers, limits


@pytest.mark.parametrize("kind", ["fp32", "bf16"])
def test_program_correct_and_control_not(kind):
    cell = bench_tiny.cell(kind, "batch")
    res, numbers, limits = _run(cell, 2**31 + 5, cell.config["control"])
    assert res["correct"], numbers
    assert numbers["served_tokens"] > 0
    # the control serves nothing: it is judged on the numbers it reads
    read = {k: v for k, v in limits.items() if f"control.{k}" in numbers}
    assert set(read) == {"gap", "logit_err"}
    control = {k: numbers[f"control.{k}"] for k in read}
    assert not check.judge(control, read), control


def _stale_state(engine):
    """The decode step returns its cache state unchanged."""
    fn = engine._decode_fn

    def step(params, tokens, caches, *rest):
        kept = jax.tree.map(jnp.copy, caches)
        logits, _new = fn(params, tokens, caches, *rest)
        return logits, (kept if tokens.shape[1] == 1 else _new)
    engine._decode_fn = step


def _altered_token(engine):
    """Every fifth decode step emits token 5 in every slot."""
    fn = engine._decode_fn
    calls = [0]

    def step(params, tokens, caches, *rest):
        logits, new = fn(params, tokens, caches, *rest)
        if tokens.shape[1] == 1:
            calls[0] += 1
            if calls[0] % 5 == 0:
                logits = logits.at[:, 5].set(1e4)
        return logits, new
    engine._decode_fn = step


class _ArgmaxOffByOne:
    """``jax.numpy`` for the engine, whose every fifth ``argmax`` picks the
    next token id: a token altered in the engine's host loop, served and
    fed back, while the step's logits stay as computed."""

    def __init__(self):
        self.calls = 0

    def __getattr__(self, name):
        return getattr(jnp, name)

    def argmax(self, x, axis=None):
        out = jnp.argmax(x, axis=axis)
        self.calls += 1
        return (out + 1) % x.shape[-1] if self.calls % 5 == 0 else out


def _altered_served_token(engine):
    engine_module.jnp = _ArgmaxOffByOne()


@pytest.mark.parametrize("fault,kind", [
    pytest.param(_stale_state, "bf16", id="_stale_state"),
    pytest.param(_altered_token, "bf16", id="_altered_token"),
    pytest.param(_altered_token, "fp32", id="_altered_token-fp32"),
    pytest.param(_altered_served_token, "bf16", id="_altered_served_token"),
    pytest.param(_altered_served_token, "fp32",
                 id="_altered_served_token-fp32"),
])
def test_broken_timed_step_is_not_correct(monkeypatch, fault, kind):
    build = R.build

    def faulty(cell, seed):
        arch, params, engine = build(cell, seed)
        fault(engine)
        return arch, params, engine
    monkeypatch.setattr(R, "build", faulty)
    monkeypatch.setattr(engine_module, "jnp", jnp)
    res, numbers, _limits = _run(bench_tiny.cell(kind, "batch"), 7)
    assert not res["correct"], numbers
    if fault is _altered_served_token:
        # the logits and the reference follow the served tokens alike
        assert numbers["argmax_mismatch"] > 0, numbers

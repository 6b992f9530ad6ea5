"""The scope join: each compiled instruction's ``op_name``, a fusion's from
its fused computation, the share of a program's device time under a scope,
and the reader of ``fake_quant_share.train`` on the tiny train step."""

import re

import pytest

from bench import run_cell
from bench.harness import scopes

from . import tiny

TEXT = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %round.1 = f32[4]{0} round-nearest-even(%param_0.1), metadata={op_name="jit(step)/quant/acts/round"}
  ROOT %multiply.2 = f32[4]{0} multiply(%round.1, %round.1), metadata={op_name="jit(step)/transpose(jvp(quant/acts))/mul"}
}

%fused_computation.2 (param_0.2: f32[4]) -> (f32[4], f32[4]) {
  %param_0.2 = f32[4]{0} parameter(0)
  %exponential.1 = f32[4]{0} exponential(%param_0.2), metadata={op_name="jit(step)/attn/exp"}
  %convert.1 = f32[4]{0} convert(%exponential.1)
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%convert.1, %exponential.1)
}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = (f32[4]{0}, f32[4]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %copy.3 = f32[4]{0:T(256)} copy(%fusion.1)
  %dequant.4 = f32[4]{0} add(%p, %p), metadata={op_name="jit(step)/dequant/add"}
  ROOT %dot.5 = f32[4]{0} multiply(%copy.3, %p), metadata={op_name="jit(step)/ffn/dot"}
}
"""


def test_op_scopes_fall_back_to_the_fused_computation():
    got = scopes.op_scopes(TEXT)
    assert got["fusion.1"] == "jit(step)/transpose(jvp(quant/acts))/mul"
    assert got["fusion.2"] == "jit(step)/attn/exp"  # ROOT tuple: last name
    assert got["copy.3"] == "" and got["dot.5"] == "jit(step)/ffn/dot"


def test_share_counts_unscoped_ops_in_the_denominator():
    ops = {"fusion.1": [3, 0.3], "fusion.2": [1, 0.2], "copy.3": [1, 0.1],
           "dequant.4": [1, 0.15], "dot.5": [2, 0.25]}
    inside, bare = scopes.share(ops, scopes.op_scopes(TEXT), "quant/")
    assert inside == pytest.approx(30.0)  # dequant/ is not quant/
    assert bare == pytest.approx(10.0)
    assert scopes.share({}, scopes.op_scopes(TEXT), "quant/") is None
    assert scopes.share(ops, scopes.op_scopes(TEXT), "norm/") is None


@pytest.fixture(scope="module")
def step_text():
    return scopes.train_step_text(tiny.config("pquant-700m"),
                                  tiny.train_mix())


def test_fake_quant_rounding_carries_its_scope(step_text):
    """Every rounding or sign of the tiny QAT step (activation and int8
    weight fake-quant, forward and rematerialized) is traced under
    ``quant/``, and both scopes appear, backward ops included."""
    got = scopes.op_scopes(step_text)
    rounds = re.findall(r"%([^\s=]+) = \S+ (?:round-nearest-even|sign)\(",
                        step_text)
    assert rounds and all(scopes.in_scope(got[r], "quant/") for r in rounds)
    names = set(got.values())
    assert any("quant/weights" in n for n in names)
    assert any("quant/acts" in n for n in names)
    assert any(n.startswith("jit(train_step)/train/grads/transpose(jvp(")
               and scopes.in_scope(n, "quant/") for n in names)


def test_the_reader_joins_the_windows_program(step_text):
    """The reader compiles the step again at the window's shapes: the same
    instruction names as the loop's own program, joined to device times."""
    import jax

    from bench.harness import program, traffic
    from bench.loops import train

    cfg, mix = tiny.config("pquant-700m"), tiny.train_mix()
    state = program.train_state(tiny.SEED, cfg)
    batch = jax.device_put(next(traffic.train_batches(
        mix, tiny.SEED, cfg["vocab_size"])))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), (state, batch))
    own = train.make_step(cfg).lower(*shapes).compile().as_text()
    got = scopes.op_scopes(step_text)
    assert got.keys() == scopes.op_scopes(own).keys()
    ops = {name: [1, 1e-3] for name in got}
    rec = {"kind": "train", "cfg": cfg, "mix": mix,
           "trace": {"ops_by_kind": {"train": ops}}}
    value = run_cell.load_reader("fake_quant_share.train")(rec)
    assert value == pytest.approx(scopes.share(ops, got, "quant/")[0])
    assert 0 < value < 100

"""The control, the plain reference at the precision below the one a
configuration states, fails the limit that the program meets."""
import os

import numpy as np
import pytest

from bench import model, reference
from bench.registry import ROOT, _json
from bench.tests import tiny


def _number(name, got, want):
    if name == "max_rel_l2":
        return reference.rel_l2_per_sample(got, want).max()
    return int(np.sum(np.any(got != want, axis=1)))


@pytest.mark.parametrize("extra", [tiny.FLOAT_CARD_EXTRA, tiny.INT_CARD_EXTRA],
                         ids=["fp8-control", "int4-control"])
def test_control_fails_the_limit(extra):
    """The reference at the precision below the stated one fails the
    configuration's limit on every seed tried."""
    card = tiny.tiny_card("ctl", extra)
    (number, limit), = extra["limits"].items()
    for seed in (1, 2, 3):
        params = model.make_weights(card, extra["weight_fill"], seed)
        xs = model.make_inputs(card, extra["input_fill"], 32, seed)
        want = reference.forward(card, params, xs)
        got = reference.forward(card, params, xs, control=extra["control"])
        value = _number(number, got, want)
        assert value > limit, (seed, value)


def test_float_reference_matches_program_on_cpu():
    """On the CPU the program's float32 answers and the reference's
    agree to float32 rounding."""
    from repro import compile_graph, frontends

    card = tiny.tiny_card("agree", tiny.FLOAT_CARD_EXTRA)
    params = model.make_weights(card, "he_normal", 9)
    xs = model.make_inputs(card, "standard_normal", 4, 9)
    art = compile_graph(frontends.import_card(card).dfg)
    got = np.asarray(art.run(xs, params)).reshape(4, -1)
    err = reference.rel_l2_per_sample(got, reference.forward(card, params, xs))
    assert err.max() < 1e-5


def test_lenet5_int4_control_at_its_size():
    """LeNet-5 at its published size: every answer of the int4 control
    differs from the int8 reference, and the program on the CPU, through
    the batched path the server drives, matches it exactly."""
    from repro import CompileOptions, compile_graph, frontends

    card = _json(os.path.join(ROOT, "bench", "configs", "lenet5.json"))
    params = model.make_weights(card, card["weight_fill"], 2**33 + 1)
    xs = model.make_inputs(card, card["input_fill"], 64, 2**33 + 1)
    want = reference.forward(card, params, xs)
    got = reference.forward(card, params, xs, control=card["control"])
    assert _number("mismatched_answers", got, want) > len(xs) // 2
    art = compile_graph(frontends.import_model(
        os.path.join(ROOT, "bench", "configs", "lenet5.json")).dfg,
        CompileOptions(**card["compile_options"]))
    prog = np.asarray(art.run(xs[:32], params)).reshape(32, -1)
    assert _number("mismatched_answers", prog, want[:32]) == 0

"""The output check on the CPU at a tiny size: a sound run is correct,
and a run with a token altered where the engine produces it, or with
the program's int8 weights switched on (the control), is not."""

import pytest

from bench import cell
from bench.control import CONTROLS
from bench.tests.tiny import make_root

SEED = 2**31 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # f32 tiny model: the program and the reference agree to rounding,
    # far inside 1e-3, and a wrong token in ten reads a mean gap of
    # hundredths.
    return make_root(tmp_path_factory.mktemp("bench"), limit=1e-3)


def run(root, workload="tiny.chat", **kw):
    return cell.run_cell(root, workload, SEED, 1.5, False,
                         require_chip=False, **kw)


def altered(engine):
    """A token altered where it is produced: each request's second."""
    emit, vocab = engine._emit, engine.cfg.vocab_size

    def bad(slot, tok, events):
        if slot.generated == 1:
            tok = (tok + 1) % vocab
        return emit(slot, tok, events)

    engine._emit = bad


def pool_unchanged(name):
    """A step that returns its state unchanged: the engine's jitted
    ``name`` (the decode step or the chunk insert) hands back the page
    pool it was given, so the KV rows it wrote are lost."""
    def fault(engine):
        fn = getattr(engine, name)

        def stale(*args):
            out = fn(*args)
            if name == "_decode":
                return out[0], args[-1]
            return args[0]

        stale.lower = fn.lower          # the engine reads its cost
        setattr(engine, name, stale)
    return fault


@pytest.mark.parametrize("workload", ["tiny.chat", "tiny.backlog"])
def test_sound_run_is_correct(root, workload):
    res = run(root, workload)
    assert res["correct"], res["check"]
    assert res["check"]["mean_gap"]["value"] <= 1e-3
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"


def test_altered_token_is_caught(root):
    res = run(root, fault=altered)
    assert not res["correct"]
    assert res["check"]["mean_gap"]["value"] > 1e-3


@pytest.mark.parametrize("step", ["_decode", "_insert_chunk"])
def test_state_left_unchanged_is_caught(root, step):
    res = run(root, fault=pool_unchanged(step))
    assert not res["correct"], res["check"]


def test_control_is_not_correct(root):
    res = run(root, **CONTROLS["quantize"])
    assert not res["correct"], res["check"]


def test_reference_control_is_not_correct(root):
    # The other control: the reference at int8 weights, put in the
    # served tokens' place on the same prompts.
    res = run(root, **CONTROLS["reference_int8"])
    assert not res["correct"], res["check"]
    assert res["check"]["mean_gap"]["value"] > \
        res["check"]["mean_gap"]["limit"]

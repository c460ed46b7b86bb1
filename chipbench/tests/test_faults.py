"""The whole measuring path on the CPU, with the timed path broken
underneath, has to come out as not correct; unbroken, as correct.  The
serving cells can have two of the faults: a decode step that returns its
cache unchanged, and a token altered where the decode step produces it.
(Training's half batch and the exchange between chips do not occur on
one-chip serving cells.)"""
import jax
import pytest

from rehearse import rehearse

CELLS = ["phi3-chat", "stablelm-rag", "phi3-chat-sat"]


def state_unchanged(eng):
    step = jax.jit(eng._decode_impl)
    eng._decode = lambda c, p, t, pos: (step(c, p, t, pos)[0], c)


def token_altered(eng):
    """Each decode step alters the token of one slot, a different slot on
    each step, so that every request in flight is hit."""
    step, vocab = eng._decode, eng.cfg.vocab_size

    def altered(c, p, t, pos):
        tokens, cache = step(c, p, t, pos)
        k = eng.decode_steps % eng.max_batch
        return tokens.at[k].set((tokens[k] + 1) % vocab), cache
    eng._decode = altered


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = rehearse(cell, rate=2.0)
    assert out["finished"] >= 2 and out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [state_unchanged, token_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    out = rehearse(cell, rate=2.0, fault=fault)
    assert out["finished"] >= 2 and not out["correct"], out["checks"]

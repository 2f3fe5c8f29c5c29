"""The comparison that decides ``correct`` fails what it has to fail.

Each test drives the whole harness (traffic, warm-up, open-loop window,
reference check) at a size the CPU holds, with the chip look skipped. The
sound program passes; the control (the program's own int8 fake-quant path,
the nearest precision below the configuration's) and each fault the cells
can have, planted under the served path, come out not correct. The
exchange between chips has no fault to plant: every cell runs on one chip.
"""

import jax
import jax.numpy as jnp
import pytest

from chipbench.tests import tiny


def _decode_step(eng):
    from repro.serving.paged import make_paged_decode_step
    return make_paged_decode_step(eng.cfg, eng.max_len, greedy=True,
                                  fused=True)


def unchanged_state(eng):
    """The decode step returns the KV pools it was given."""
    step = _decode_step(eng)

    def broken(params, token, pos, pools, tables, key):
        nxt, _ = step(params, token, pos, pools, tables, key)
        return nxt, pools
    eng._paged_decode = jax.jit(broken)


def half_batch(eng):
    """Only the first half of the slots is decoded; the rest get token 0."""
    step = _decode_step(eng)

    def broken(params, token, pos, pools, tables, key):
        nxt, pools = step(params, token, pos, pools, tables, key)
        return nxt.at[nxt.shape[0] // 2:].set(0), pools
    eng._paged_decode = jax.jit(broken)


def altered_token(eng):
    """Every decoded token is replaced by its neighbour in the vocabulary."""
    step = _decode_step(eng)
    vocab = eng.cfg.vocab_size

    def broken(params, token, pos, pools, tables, key):
        nxt, pools = step(params, token, pos, pools, tables, key)
        return jnp.mod(nxt + 1, vocab).astype(nxt.dtype), pools
    eng._paged_decode = jax.jit(broken)


@pytest.mark.parametrize("cell", ["tiny.decode", "tiny.chat"])
def test_sound_program_is_correct(cell):
    out = tiny.run(cell)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("cell", ["tiny.decode", "tiny.chat"])
def test_control_is_not_correct(cell):
    sound = tiny.run(cell)["check"]["max_logit_gap"]["value"]
    out = tiny.run(cell, control=True)
    gap = out["check"]["max_logit_gap"]["value"]
    assert not out["correct"], out["check"]
    assert gap >= 3 * max(sound, tiny.LIMITS["max_logit_gap"] / 3)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_token])
@pytest.mark.parametrize("cell", ["tiny.decode", "tiny.chat"])
def test_fault_is_not_correct(cell, fault):
    out = tiny.run(cell, tamper=fault)
    assert not out["correct"], (fault.__name__, out["check"])

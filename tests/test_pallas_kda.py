"""``parallel/pallas_kda.py``: the single-token step of the delta rule as
one kernel over the live rows, interpreted on the CPU at toy sizes,
against ``models/kda.py:delta_step``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models import kda
from bluefog_tpu.parallel import pallas_kda

ROWS, HEADS, DIM = 6, 4, 128

PATTERNS = {
    "every row live": [1, 1, 1, 1, 1, 1],
    "mixed": [0, 1, 0, 1, 1, 0],
    "the first row live": [1, 0, 0, 0, 1, 1],
    "no row live": [0, 0, 0, 0, 0, 0],
}
STARTS = {
    "no row at index 0": [0, 0, 0, 0, 0, 0],
    "some rows at index 0": [1, 1, 0, 1, 0, 1],
}


def draw(seed, heads=HEADS):
    rng = np.random.default_rng(seed)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(ROWS, heads, DIM))) * DIM ** -0.5
    k = unit(rng.normal(size=(ROWS, heads, DIM)))
    v = rng.normal(size=(ROWS, heads, DIM))
    g = -5.0 * rng.uniform(size=(ROWS, heads, DIM))
    beta = rng.uniform(size=(ROWS, heads))
    # what a slot's last request left: large
    state = 50.0 * rng.normal(size=(ROWS, heads, DIM, DIM))
    return tuple(map(f32, (q, k, v, g, beta))), f32(state)


@pytest.mark.parametrize("mapped", [False, True],
                         ids=["a batch", "vmap over slots"])
@pytest.mark.parametrize("starts", list(STARTS))
@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_the_kernel_is_delta_step_over_the_live_rows(pattern, starts,
                                                     mapped):
    """``o`` and the new state of a live row to float32's tolerance
    (the sums over a state's rows run in another order), a row at index
    0 from zero state whatever garbage its leaf holds, and the leaf of
    every row that is not live BIT FOR BIT what went in (``o`` zeros)."""
    live = np.array(PATTERNS[pattern], bool)
    fresh = np.array(STARTS[starts], bool)
    token, state = draw(len(pattern) + 7 * len(starts))
    # garbage under the index-0 rule, also where no select may read it
    state = state.at[fresh].set(jnp.nan)
    went_in = np.asarray(state)
    if mapped:
        # as serving/engine.py:_decode_step_prog maps it: a batch of
        # one sequence a slot, its flags scalars of the slot
        def one(live, fresh, state, *token):
            return pallas_kda.delta_step(
                *(x[None] for x in token), state[None], live=live[None],
                fresh=fresh)

        o, new = jax.vmap(one)(jnp.asarray(live), jnp.asarray(fresh), state,
                               *token)
        o, new = o[:, 0], new[:, 0]
    else:
        o, new = pallas_kda.delta_step(*token, state, live=jnp.asarray(live),
                                       fresh=jnp.asarray(fresh))
    want_o, want_new = kda.delta_step(
        *token, jnp.where(fresh[:, None, None, None], 0.0, state))
    o, new = np.asarray(o), np.asarray(new)
    np.testing.assert_allclose(o[live], np.asarray(want_o)[live],
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(new[live], np.asarray(want_new)[live],
                               rtol=1e-5, atol=1e-3)
    assert new[~live].tobytes() == went_in[~live].tobytes()
    assert not o[~live].any()


def test_one_flag_serves_every_row_and_heads_split_into_blocks():
    """Scalars for ``live`` and ``fresh`` (``generate``'s call: a batch,
    every row live), and 16 heads in two blocks of 8."""
    token, state = draw(3, heads=16)
    o, new = pallas_kda.delta_step(*token, state, block_h=8)
    want_o, want_new = kda.delta_step(*token, state)
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(new, want_new, rtol=1e-5, atol=1e-3)
    o, new = pallas_kda.delta_step(*token, state, fresh=True)
    want_o, want_new = kda.delta_step(*token, jnp.zeros_like(state))
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new, want_new, rtol=1e-5, atol=1e-5)
    assert pallas_kda.steppable(128) and not pallas_kda.steppable(8)

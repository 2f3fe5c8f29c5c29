"""The paged decode-1q kernel (``attn_template:paged_decode``) against its
oracle: gather each row's blocks to a contiguous view
(``ref.paged_kv_gather``), then ``ref.decode_attention`` over it.

Pallas interpret mode on the CPU; the compile for a described v5e is in
``tests/test_tpu_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import attn_template as T
from repro.kernels import ops, ref
from repro.models.attention import fit_head

BS, NB, N = 16, 4, 24            # block size, table width, pool blocks
MAX_LEN = BS * NB

#: (name, Hq, Hkv, head dim)
HEADS = {"mha_hd80": (4, 4, 80), "gqa4_hd128": (8, 2, 128)}

#: per-row lengths: block edges, the whole table, a mix, a dead slot
LENGTHS = {
    "len1": (1, 1),
    "len15": (15, 15),
    "len16": (16, 16),
    "len17": (17, 17),
    "max_len": (MAX_LEN, MAX_LEN),
    "mix": (1, 15, 16, 17, 33, MAX_LEN),
}


def _tables(lengths, rng):
    """Distinct live blocks per row (never block 0); entries past a row's
    live blocks stay 0, the scratch block, as the engine leaves them."""
    tables = np.zeros((len(lengths), NB), np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for b, n in enumerate(lengths):
        for j in range(-(-n // BS)):
            tables[b, j] = free.pop()
    return jnp.asarray(tables)


def _case(heads, lengths, layers=None, seed=0):
    hq, hkv, d = HEADS[heads]
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    lead = () if layers is None else (layers,)
    kp = jax.random.normal(k[0], lead + (N, BS, hkv, d), jnp.float32)
    vp = jax.random.normal(k[1], lead + (N, BS, hkv, d), jnp.float32)
    q = jax.random.normal(k[2], (len(lengths), 1, hq, d), jnp.float32)
    tables = _tables(lengths, np.random.RandomState(seed))
    return q, kp, vp, tables, jnp.asarray(lengths, jnp.int32)


def _oracle(q, kp, vp, tables, lengths):
    return ref.decode_attention(q, ref.paged_kv_gather(kp, tables, MAX_LEN),
                                ref.paged_kv_gather(vp, tables, MAX_LEN),
                                lengths)


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_paged_kernel_matches_gather_then_decode(heads, lengths):
    q, kp, vp, tables, lens = _case(heads, LENGTHS[lengths])
    got = ops.attn_decode_paged_template(q, kp, vp, tables, lens,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_oracle(q, kp, vp, tables, lens)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pages", [1, 3, NB])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_paged_kernel_layer_stacked_pool(heads, pages):
    """The layer-stacked pool with a layer index reads only that layer,
    whatever the pages per grid step (3 does not divide the table)."""
    q, kp, vp, tables, lens = _case(heads, LENGTHS["mix"], layers=3)
    for layer in (0, 2):
        got = T.paged_decode_core(q, kp, vp, tables, lens, jnp.int32(layer),
                                  pages_per_step=pages, interpret=True)
        want = _oracle(q, kp[layer], vp[layer], tables, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_paged_kernel_reads_lane_padded_pool():
    """A pool whose head dim is zero-padded to the lane width (as the
    engine keeps it on the paged path), read with q padded alike
    (``attention.fit_head``) and the model's scale, gives what the
    unpadded pool gives in its first 80 lanes and zeros past them."""
    q, kp, vp, tables, lens = _case("mha_hd80", LENGTHS["mix"])
    got = ops.attn_decode_paged_template(
        fit_head(q, 128), fit_head(kp, 128), fit_head(vp, 128), tables,
        lens, scale=80 ** -0.5, interpret=True)
    assert got.shape == q.shape[:-1] + (128,)
    np.testing.assert_allclose(np.asarray(got[..., :80]),
                               np.asarray(_oracle(q, kp, vp, tables, lens)),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[..., 80:]), 0.0)


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_paged_kernel_scratch_entries_and_dead_slot(heads):
    """A dead slot (position 0, an all-scratch table row) reads row 0 of
    block 0; a live row's table tail on block 0 is never read; a row of
    length 0 gives exact zeros, as in the oracle."""
    q, kp, vp, tables, _ = _case(heads, (17, 1, 0))
    tables = tables.at[1].set(0)                   # dead slot: all scratch
    lens = jnp.asarray([17, 1, 0], jnp.int32)      # pos + 1; 0: no key
    assert int(tables[0, 2]) == 0                  # live row's scratch tail
    got = ops.attn_decode_paged_template(q, kp, vp, tables, lens,
                                         interpret=True)
    want = _oracle(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[2]), 0.0)
    # block 0's other rows reach no output
    poisoned = kp.at[0, 1:].set(1e4), vp.at[0, 1:].set(1e4)
    again = ops.attn_decode_paged_template(q, *poisoned, tables, lens,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_paged_spec_registered_and_refuses_other_masks():
    spec = next(s for s in T.BUILTIN_SPECS if s.paged)
    assert spec.mask == "decode"
    assert T.kernel_key(spec) in ops.KERNEL_SPECS
    with pytest.raises(ValueError, match="paged"):
        T.AttnSpec(name="bad_paged", mask="causal", paged=True)

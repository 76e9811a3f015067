"""Packed parameter arena: layout invariants and round-trips."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import arena


def _tree(key):
    return {"w": jax.random.normal(key, (65, 7), jnp.float32),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (129,), jnp.bfloat16),
            "i": jax.random.randint(jax.random.fold_in(key, 2), (40,), 0, 100, jnp.int32),
            "s": jax.random.normal(jax.random.fold_in(key, 3), (1,), jnp.bfloat16)}


def test_pack_unpack_roundtrip(key):
    params = _tree(key)
    buf, spec = arena.pack(params)
    assert buf.dtype == jnp.uint32 and buf.shape[0] == spec.n_words
    back = arena.unpack(buf, spec)
    for k in params:
        assert back[k].dtype == params[k].dtype
        assert np.array_equal(np.asarray(back[k], np.float32),
                              np.asarray(params[k], np.float32)), k


def test_leaves_block_aligned(key):
    _, spec = arena.pack(_tree(key))
    for l in spec.leaves:
        assert l.offset % arena.BLOCK == 0
        assert (l.n_words + l.pad_words) % arena.BLOCK == 0
    assert spec.n_words % arena.BLOCK == 0
    ends = [l.offset + l.n_words + l.pad_words for l in spec.leaves]
    assert ends == sorted(ends) and ends[-1] == spec.n_words


def test_padding_is_zero(key):
    buf, spec = arena.pack(_tree(key))
    buf = np.asarray(buf)
    for l in spec.leaves:
        pad = buf[l.offset + l.n_words:l.offset + l.n_words + l.pad_words]
        assert (pad == 0).all()


def test_leaf_of_block_attribution(key):
    buf, spec = arena.pack(_tree(key))
    for i, l in enumerate(spec.leaves):
        first = l.offset // arena.BLOCK
        assert spec.leaf_of_block(first) == i
        assert spec.leaf_of_block(first + l.n_blocks - 1) == i


def test_pack_is_jittable(key):
    params = _tree(key)
    _, spec = arena.pack(params)

    @jax.jit
    def roundtrip(p):
        buf, s = arena.pack(p)
        return arena.unpack(buf, s)

    back = roundtrip(params)
    for k in params:
        assert np.array_equal(np.asarray(back[k], np.float32),
                              np.asarray(params[k], np.float32)), k


def test_unsupported_dtype_raises():
    with pytest.raises(TypeError):
        arena.pack({"x": jnp.zeros((4,), jnp.int8)})


def test_empty_pytree_protect_scrub():
    """Regression: a 0-word arena must not crash the kernel dispatch."""
    from repro.core.reliability import ReliableStore
    store = ReliableStore.protect({})
    assert store.parity.shape == (0, 3)
    fixed, rep = store.scrub()
    assert int(rep.corrected) == 0 and int(rep.uncorrectable) == 0
    assert fixed.params == {}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("slab_words", [256, 1 << 24])
def test_map_leaf_words_slabs_match_whole_leaf(key, dtype, slab_words):
    """Walking a leaf in block-aligned slabs (one fori_loop, leaf rewritten
    in place) gives the words and summed aux of one whole-leaf call, and
    keeps every bit — NaN payloads included."""
    from repro.faults import TransientBitFlips
    x = jax.random.normal(key, (6, 16, 64)).astype(dtype)
    model = TransientBitFlips(0.05)        # dense enough to make NaNs

    def fn(words, offset):
        bad = model.corrupt_words(words, key, offset=offset)
        return bad, (bad != words).sum(dtype=jnp.int32)

    got, flipped = jax.jit(lambda x: arena.map_leaf_words(
        x, fn, slab_words=slab_words))(x)
    words = arena.leaf_to_words(x)
    want = model.corrupt_words(words, key)
    np.testing.assert_array_equal(np.asarray(arena.leaf_to_words(got)),
                                  np.asarray(want))
    assert int(flipped) == int((want != words).sum()) > 0

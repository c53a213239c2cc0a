"""What the attention tests of the three token families ask of a walk's
`interior` (ops/block_attention.py) and of the kernel body it selects."""

import jax
import jax.numpy as jnp
import numpy as np


def interior_pairs(mask: np.ndarray, walk, tile: int, every: bool):
    """Walks every query tile's key tiles and every key tile's query tiles
    (the same pairs), holding `walk.interior` against the dense `mask` of
    positions x positions: never true where the pair's block of the mask
    hides an entry and, with `every`, true wherever it hides none.
    -> (pairs it is true for, pairs visited)."""
    n = walk.tiles
    whole = mask.reshape(n, tile, n, tile).all(axis=(1, 3))
    i32 = jnp.int32
    from_queries = [(qt, int(walk.fwd_key_tile(i32(qt), i32(s)))) for qt in range(n) for s in range(int(walk.fwd_steps(i32(qt))))]
    from_keys = [(int(walk.bwd_query_tile(i32(kt), i32(u))), kt) for kt in range(n) for u in range(int(walk.bwd_steps(i32(kt))))]
    assert sorted(from_queries) == sorted(from_keys) and len(set(from_queries)) == len(from_queries)
    said = {(qt, kt): bool(walk.interior(i32(qt), i32(kt))) for qt, kt in from_queries}
    for (qt, kt), interior in said.items():
        assert not interior or whole[qt, kt], (qt, kt)
        assert interior or not (every and whole[qt, kt]), (qt, kt)
    return sum(said.values()), len(said)


def never_interior_keeps_the_bits(monkeypatch, walk_class, attend, heads, positions, head_dim):
    """Output and all three gradients of `attend(q, k, v)`, q of `heads[0]`
    and k, v of `heads[1]` heads in two rows, with the walk's `interior` as
    it is, and answering "never" so that every pair of dq and dk/dv takes
    the masked body: equal bit for bit."""
    keys = jax.random.split(jax.random.PRNGKey(positions + head_dim), 4)
    q, weight = (jax.random.normal(key, (2, heads[0], positions, head_dim)) for key in keys[:2])
    k, v = (jax.random.normal(key, (2, heads[1], positions, head_dim)) for key in keys[2:])

    def run():
        def value_and_gradients(q, k, v):
            o, pull = jax.vjp(attend, q, k, v)
            return o, pull(weight)

        return jax.jit(value_and_gradients)(q, k, v)

    asked = []
    as_it_is = walk_class.interior
    monkeypatch.setattr(walk_class, "interior", lambda self, qt, kt: asked.append(as_it_is(self, qt, kt)) or asked[-1])
    two_bodies = run()
    assert len(asked) == 2 and not any(answer is False for answer in asked)  # dq and dk/dv, both bodies in each
    monkeypatch.setattr(walk_class, "interior", lambda self, qt, kt: asked.append(False) or False)
    masked_only = run()
    assert asked[2:] == [False] * 2
    for got, want in zip(jax.tree.leaves(two_bodies), jax.tree.leaves(masked_only)):
        assert np.array_equal(np.asarray(got), np.asarray(want))

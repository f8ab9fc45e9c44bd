"""The learned index over a latent cache: which cached rows a query attends to.

A layer with an index (`index_topk > 0`) caches, beside each position's
latent row, one index key of `index_head_dim` columns. A query at position
t scores every cached row s <= t,

    I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s)),    j over the index heads,

and attends to the `index_topk` rows of largest score alone (all of them
while t + 1 <= index_topk). Scores are f32 from products in the operands'
type with f32 accumulation; equal scores go to the lower row, as
`lax.top_k` breaks ties, so the set is always exactly min(topk, t + 1) rows.

Both functions are plain XLA. `index_scores` runs a chunk's queries in
blocks, so that the `[block, heads, rows]` products before the sum over
heads stay tens of megabytes. `select_rows` gives the set as a mask over
the rows and moves no row: the attention that follows masks what is left
out (`models/transformer._attention_latent`). It finds each query's
`topk`-th largest score by a search over the bits of the f32 scores, a
compare-and-count pass a bit, and then the last row taken among those that
tie with it: no sort, and nothing gathered. On the v5e the search takes
0.26-0.29 ms for a chunk's 512 queries over 8192 rows where `lax.top_k`
takes 1.36, and 0.19 ms for a decode step's four where it takes 0.39
(PERF.md section 6, PR 39).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# bytes of the `[block, heads, rows]` f32 products of one block of queries
_SCORE_BLOCK_BYTES = 64 << 20


def index_scores(
    qi: jnp.ndarray,  # [T, J, dI]: a lane's index queries, rope applied
    w: jnp.ndarray,  # [T, J] f32: a weight an index head, its constants folded in
    keys: jnp.ndarray,  # [S, dI]: the lane's cached index keys of one layer
) -> jnp.ndarray:
    """I(t, s) for every query of a lane against its first S cached rows,
    f32 [T, S]; which rows a query may see is `select_rows`' to say."""
    t, j, _ = qi.shape
    s = keys.shape[0]

    def block(args):
        q, ww = args
        dots = jnp.einsum("tjd,sd->tjs", q, keys, preferred_element_type=jnp.float32)
        return jnp.einsum("tjs,tj->ts", jnp.maximum(dots, 0.0), ww)

    tb = max(1, _SCORE_BLOCK_BYTES // (4 * j * s))
    if t <= tb or t % tb:
        return block((qi, w))
    out = lax.map(block, (qi.reshape(t // tb, tb, j, -1), w.reshape(t // tb, tb, j)))
    return out.reshape(t, s)


def _sort_keys(scores: jnp.ndarray) -> jnp.ndarray:
    """uint32 keys in the order of the f32 scores; every finite score's key
    is above 0."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select_rows(
    scores: jnp.ndarray,  # [N, S] f32
    q_pos: jnp.ndarray,  # [N] int32: each query's position; negative = sees nothing
    topk: int,
) -> jnp.ndarray:
    """bool [N, S]: row s is among the min(topk, q_pos + 1) rows s <= q_pos
    of largest score, ties to the lower row."""
    n, s = scores.shape
    rows = jnp.arange(s, dtype=jnp.int32)[None, :]
    seen = rows <= q_pos[:, None]
    key = jnp.where(seen, _sort_keys(scores), jnp.uint32(0))

    def value_bit(i, v):
        cand = v | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= cand[:, None], axis=-1, dtype=jnp.int32) >= topk
        return jnp.where(enough, cand, v)

    # the largest v that at least `topk` keys reach: the topk-th largest key
    # (0, an unseen row's, where a query sees fewer rows than that)
    v = lax.fori_loop(0, 32, value_bit, jnp.zeros((n,), jnp.uint32))
    above = key > v[:, None]
    tie = jnp.logical_and(key == v[:, None], seen)
    need = topk - jnp.sum(above, axis=-1, dtype=jnp.int32)
    n_bits = max(1, (s - 1).bit_length())

    def row_bit(i, r):
        cand = r | (jnp.int32(1 << (n_bits - 1)) >> i)
        before = jnp.sum(
            jnp.logical_and(tie, rows < cand[:, None]), axis=-1, dtype=jnp.int32)
        return jnp.where(before < need, cand, r)

    # the largest r with fewer than `need` tying rows before it: the last
    # tying row that is taken
    last = lax.fori_loop(0, n_bits, row_bit, jnp.zeros((n,), jnp.int32))
    return jnp.logical_or(above, jnp.logical_and(tie, rows <= last[:, None]))

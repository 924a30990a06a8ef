"""What every model's paged step shares: where a step's fresh cache
rows go in the block pool, and the in-place loop that puts them there.

A paged pool is a tuple of stacked leaves ``(L, H, num_blocks *
block_size, d)`` — ``(k, v)`` for ``transformer_lm``, one latent leaf
``(L, 1, R, d)`` for ``deepseek_v3`` — addressed through per-sequence
block tables; the write is the same for every leaf and every layer.
"""


def write_plan(tables, positions, valid, Lq, block_size):
    """Where a step's fresh cache rows go in a layer of the pool: the
    same for every layer, so computed once a program.

    ``Lq == 1`` (decode) is one row a sequence.  A chunk is written per
    (sequence, affected block) — at most ``A = (Lq + bs - 2) // bs + 1``
    blocks, whatever its start.  Only the writes that land in a block
    some table owns are live: pad rows, a block of the bound past the
    last valid row and the rows of a slot outside the dispatch (an
    all-zero table) would only reach the trash block 0, which nothing
    reads unmasked, and are written nowhere.  Returns ``(live count,
    columns)``, the columns sorted live first: the sequence and the
    pool row the write starts at and, for a chunk, the chunk rows
    ``[lo, hi)`` that land in the block, whose row 0 is chunk row
    ``lo`` (negative where the chunk starts inside the block)."""
    import jax.numpy as jnp

    B, T = tables.shape
    bs = int(block_size)
    rows = jnp.arange(B, dtype=jnp.int32)
    if Lq == 1:
        phys = tables[rows, jnp.minimum(positions // bs, T - 1)]
        live = phys != 0
        cols = (rows, phys * bs + positions % bs)
    else:
        A = (Lq + bs - 2) // bs + 1
        seq = jnp.repeat(rows, A)                           # (B*A,)
        log = (positions // bs)[seq] + jnp.tile(
            jnp.arange(A, dtype=jnp.int32), B)              # logical block
        phys = tables[seq, jnp.minimum(log, T - 1)]
        live = (log <= ((positions + valid - 1) // bs)[seq]) \
            & (log < T) & (phys != 0)
        cols = (seq, phys * bs, log * bs - positions[seq], valid[seq])
    order = jnp.argsort(~live, stable=True)                 # live first
    return (jnp.sum(live, dtype=jnp.int32),
            tuple(col[order] for col in cols))


def pool_write(pools, layer, fresh, plan, block_size):
    """Write a step's fresh rows — ``fresh[i]`` ``(B, H_i, Lq, d_i)``
    — into layer ``layer`` of each stacked pool leaf ``pools[i]`` by
    :func:`write_plan`'s ``plan``, IN PLACE on the donated arrays:
    ``dynamic_update_slice``s in one loop over the live writes, never a
    ``scatter``.  The TPU compiler gives a scatter on the pool a layout
    of its own (``{3,1,2,0}``) and copies the whole pool into it and
    back around every program (docs/architecture/decode_engine.md, "The
    pool stays where it is").

    Decode writes one ``(1, H, 1, d)`` row a sequence; a chunk reads
    each affected block, overlays the chunk's valid rows and writes it
    back.  Blocks are taken in order, so a block two tables share holds
    exactly what a row-by-row write would leave."""
    import jax
    import jax.numpy as jnp

    Lq = fresh[0].shape[2]
    bs = int(block_size)
    count, cols = plan
    fresh = tuple(new.astype(pool.dtype)
                  for pool, new in zip(pools, fresh))

    def write(n, pools):
        at = (layer, 0, cols[1][n], 0)
        out = []
        for pool, new in zip(pools, fresh):
            H, dh = new.shape[1], new.shape[3]
            new = jax.lax.dynamic_slice_in_dim(new, cols[0][n], 1, 0)
            if Lq > 1:
                # the block's rows are a window of bs consecutive chunk
                # rows starting anywhere in (-bs, Lq): bs rows of margin
                # either side make it one dynamic_slice
                lo, hi = cols[2][n], cols[3][n]
                new = jax.lax.dynamic_slice(
                    jnp.pad(new, ((0, 0), (0, 0), (bs, bs), (0, 0))),
                    (0, 0, lo + bs, 0), (1, H, bs, dh))
                r = lo + jnp.arange(bs, dtype=jnp.int32)
                new = jnp.where(
                    ((r >= 0) & (r < hi))[None, None, :, None], new,
                    jax.lax.dynamic_slice(pool, at, (1, H, bs, dh)))
            out.append(jax.lax.dynamic_update_slice(pool, new, at))
        return tuple(out)

    return jax.lax.fori_loop(0, count, write, tuple(pools))

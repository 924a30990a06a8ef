"""What every model's paged step shares: where a step's fresh cache
rows go in the block pool, and the in-place loop that puts them there.

A paged pool is a tuple of stacked leaves ``(L, H, num_blocks *
block_size, d)`` — ``(k, v)`` for ``transformer_lm``, one latent leaf
``(L, 1, R, d)`` for ``deepseek_v3`` — addressed through per-sequence
block tables; the write is the same for every leaf and every layer.

A STATE leaf ``(L, 1, num_blocks, d)`` holds ONE ROW A BLOCK: what a
layer keeps per sequence and not per token (``lfm2_moe``'s short
convolution: its last two inputs), as it stood after the block's last
written token.  It rides the same tables: the state a sequence brings
to position ``p`` is the row of the block that holds ``p - 1``
(:func:`state_read`), a step leaves the state after each block's last
token it wrote in that block's row (:func:`state_write`), and so a
prefix-cache hit at ``j`` whole blocks finds the state after token ``j
* block_size - 1`` in the last adopted block's row, and a
copy-on-write fork carries a block's row with its tokens
(docs/architecture/decode_engine.md, "State beside the pool").
"""


class RowGroup:
    """One ROW GROUP of a paged step: ``B`` sequences of ``Lq`` rows
    each, with their block tables ``(B, T)``, first positions and valid
    counts ``(B,)``.  A step may take several (a tick's decode rows,
    ``Lq = 1``, and its prompt chunk's): what a layer does to a TOKEN
    runs over all the groups' rows laid end to end, and ``span`` says
    where this group's ``B * Lq`` lie among them (``at``: the rows of
    the groups before it); what it does to a SEQUENCE runs a group.
    ``live``: the rows that are real tokens, ``(B * Lq,)`` (valid rows
    of sequences whose table owns a block)."""

    def __init__(self, tables, shape, positions, valid, block_size, at=0):
        import jax.numpy as jnp
        self.B, self.Lq = B, Lq = shape
        self.bs = int(block_size)
        self.span = slice(at, at + B * Lq)
        self.tables = jnp.asarray(tables, jnp.int32)
        self.positions = jnp.asarray(positions, jnp.int32)
        self.valid = jnp.asarray(valid, jnp.int32)
        self.rows = jnp.arange(Lq, dtype=jnp.int32)
        self.live = ((self.tables[:, :1] != 0)
                     & (self.rows[None] < self.valid[:, None])) \
            .reshape(B * Lq)

    def write_plan(self, tables=None):
        """:func:`write_plan` of the group's rows through ``tables``
        (the group's own; a model with classes of block: a class's)."""
        return write_plan(self.tables if tables is None else tables,
                          self.positions, self.valid, self.Lq, self.bs)

    def angles(self, freqs):
        """The rotary angles of the group's rows, ``(B, Lq, len(freqs))``
        float32: row ``r`` of a sequence sits at ``positions + r``."""
        import jax.numpy as jnp
        at = (self.positions[:, None] + self.rows[None]).astype(jnp.float32)
        return at[..., None] * jnp.asarray(freqs, jnp.float32)

    def last(self, h):
        """Each sequence's last valid row of the group's span of ``h``
        ``(N, D)``: ``(B, D)``."""
        import jax.numpy as jnp
        return h[self.span].reshape(self.B, self.Lq, -1)[
            jnp.arange(self.B), self.valid - 1]


def row_groups(groups, block_size, cls=RowGroup, **more):
    """A step's ``groups`` ``(tables, tokens, positions, valid)`` as
    :class:`RowGroup`s (``cls``: a model's own, ``more`` its further
    arguments) laid end to end, and their tokens that way: ``(N,)``."""
    import jax.numpy as jnp
    out, at = [], 0
    for tables, tokens, positions, valid in groups:
        out.append(cls(tables, tuple(tokens.shape), positions, valid,
                       block_size=block_size, at=at, **more))
        at = out[-1].span.stop
    return out, cat([jnp.asarray(g[1]).reshape(-1) for g in groups])


def cat(parts, axis=0):
    """The groups' ``parts`` laid end to end; ONE group's as it is (a
    one-group step traces what it always traced)."""
    import jax.numpy as jnp
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)


def last_logits(hN, groups, head):
    """``head(the groups' last valid rows of hN, laid end to end)``
    ONCE, and handed back a group: a tuple of ``(B, vocab)``."""
    every = head(cat([g.last(hN) for g in groups]))
    out, at = [], 0
    for g in groups:
        out.append(every[at:at + g.B])
        at += g.B
    return tuple(out)


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


def state_read(state, layer, tables, positions, block_size):
    """The state each sequence brings to its first position of this
    step: row ``tables[b, (positions[b] - 1) // bs]`` of layer
    ``layer`` of the state leaf ``(L, 1, num_blocks, d)``, zeros for a
    sequence at position 0 — ``(B, d)`` in the leaf's dtype.  A gather
    of ``B`` rows; the leaf is not copied."""
    import jax
    import jax.numpy as jnp

    B, T = tables.shape
    before = jnp.maximum(positions - 1, 0) // int(block_size)
    phys = tables[jnp.arange(B), jnp.minimum(before, T - 1)]
    # one gather over the whole leaf (a ``state[layer]`` first would be
    # a slice of the leaf's shape for the compiler to think about)
    rows = jax.vmap(lambda b: jax.lax.dynamic_slice(
        state, (layer, 0, b, 0), (1, 1, 1, state.shape[3])))(phys)
    return jnp.where((positions > 0)[:, None], rows.reshape(B, -1), 0)


def state_write(state, layer, trail, plan, Lq, block_size):
    """Leave the state after each written block's last token in that
    block's row of layer ``layer`` of the state leaf, IN PLACE like
    :func:`pool_write` (one ``dynamic_update_slice`` a live write of
    :func:`write_plan`'s ``plan``).  ``trail`` ``(B, n + Lq, w)`` is
    the sequence of what the state is made of, the ``n`` entries a
    sequence brought first and then the step's own: the state after
    chunk row ``r`` is entries ``r + 1 .. r + n`` flattened, ``d = n *
    w`` values."""
    import jax
    import jax.numpy as jnp

    bs = int(block_size)
    count, cols = plan
    n = trail.shape[1] - Lq
    trail = trail.astype(state.dtype)

    def write(i, state):
        if Lq == 1:
            last = 0
        else:       # the last valid chunk row that lands in the block
            last = jnp.minimum(cols[2][i] + bs, cols[3][i]) - 1
        new = jax.lax.dynamic_slice(
            trail, (cols[0][i], last + 1, 0), (1, n, trail.shape[2]))
        return jax.lax.dynamic_update_slice(
            state, new.reshape(1, 1, 1, -1),
            (layer, 0, cols[1][i] // bs, 0))

    return jax.lax.fori_loop(0, count, write, state)

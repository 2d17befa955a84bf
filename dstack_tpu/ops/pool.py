"""Writes into a paged pool leaf [L, NB, BS, X] (layers, blocks, rows of a
block, lanes): shared by every program that stores rows there, whatever the
rows are (K and V heads folded into the lanes, or MLA's latent rows)."""


def scatter_rows(leaf, idx, rows):
    """Write ``rows`` [..., X] at flat row indices ``idx`` (layer, block,
    offset folded: (l*NB + blk)*BS + off) of a paged pool leaf
    [L, NB, BS, X].  A row scatter over the pool's merged leading dims
    updates it in place under donation; indexing the layer as a window
    dim (``.at[:, blk, off]``) makes XLA change the WHOLE pool's layout on
    the way in and out (tests/compute/test_tpu_compile.py holds this)."""
    x = leaf.shape[-1]
    return leaf.reshape(-1, x).at[idx.reshape(-1)].set(
        rows.reshape(-1, x)).reshape(leaf.shape)

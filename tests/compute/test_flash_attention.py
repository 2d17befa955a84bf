"""Flash-attention kernel vs the XLA reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.ops.attention import causal_attention
from dstack_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_sharded,
    paged_decode_attention,
    supports,
)
from dstack_tpu.ops.loss import chunked_cross_entropy


def _qkv(b=2, s=256, hq=4, hkv=2, d=32, dtype=jnp.float32):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(jax.random.fold_in(key, 0), (b, s, hq, d), dtype=dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d), dtype=dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d), dtype=dtype)
    return q, k, v


def test_flash_forward_matches_reference():
    q, k, v = _qkv()
    ref = causal_attention(q, k, v)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        atol=2e-3,
    )


def test_flash_grads_match_reference():
    q, k, v = _qkv()

    def loss(att):
        def f(q, k, v):
            return jnp.sum(att(q, k, v).astype(jnp.float32) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    gf = loss(flash_attention)
    gr = loss(lambda q, k, v: causal_attention(q, k, v))
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32),
            np.asarray(b, dtype=np.float32),
            atol=5e-3, rtol=5e-3,
        )


def test_flash_sharded_matches_local(cpu_devices):
    from dstack_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=2, fsdp=2, tensor=2), cpu_devices)
    q, k, v = _qkv(b=4, s=128, hq=4, hkv=2, d=32)
    local = flash_attention(q, k, v)
    sharded = flash_attention_sharded(mesh, q, k, v)
    np.testing.assert_allclose(
        np.asarray(sharded, dtype=np.float32),
        np.asarray(local, dtype=np.float32),
        atol=2e-3,
    )


@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 4), (8, 2)])
@pytest.mark.slow
def test_flash_packed_d64_matches_reference(hq, hkv):
    # d=64 routes through the head-packed kernels (GQA even-group and MHA
    # kv-pairing variants); verify fwd + grads against the XLA path
    from dstack_tpu.ops.flash_attention import _use_packed

    assert _use_packed(64, hq, hkv)
    q, k, v = _qkv(b=2, s=256, hq=hq, hkv=hkv, d=64)
    ref = causal_attention(q, k, v)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        atol=2e-3,
    )

    def grads(att):
        def f(q, k, v):
            return jnp.sum(att(q, k, v).astype(jnp.float32) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(grads(flash_attention),
                    grads(lambda q, k, v: causal_attention(q, k, v))):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32),
            np.asarray(b, dtype=np.float32),
            atol=5e-3, rtol=5e-3,
        )


def test_flash_packed_matches_unpacked(monkeypatch):
    q, k, v = _qkv(b=1, s=256, hq=4, hkv=2, d=64, dtype=jnp.bfloat16)
    packed = flash_attention(q, k, v)
    monkeypatch.setenv("DSTACK_TPU_FLASH_PACK", "0")
    unpacked = flash_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(packed, dtype=np.float32),
        np.asarray(unpacked, dtype=np.float32),
        atol=2e-2,
    )


def test_supports_shapes():
    assert supports(1024, 64, jnp.bfloat16)
    assert not supports(100, 64, jnp.bfloat16)   # not 128-aligned
    assert not supports(65536, 256, jnp.bfloat16)  # KV exceeds VMEM budget


def test_chunked_cross_entropy_matches_dense():
    key = jax.random.PRNGKey(1)
    b, s, d, vocab = 2, 48, 16, 37
    x = jax.random.normal(jax.random.fold_in(key, 0), (b, s, d))
    head = jax.random.normal(jax.random.fold_in(key, 1), (d, vocab))
    targets = jax.random.randint(jax.random.fold_in(key, 2), (b, s), 0, vocab)
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (b, s)) > 0.3)

    logits = x @ head
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    want = jnp.sum(nll * mask) / jnp.sum(mask)

    got = chunked_cross_entropy(x, head, targets, mask, chunk=16)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    # Gradients flow through the rematerialized chunks.
    g_chunk = jax.grad(
        lambda x: chunked_cross_entropy(x, head, targets, mask, chunk=16))(x)
    g_dense = jax.grad(
        lambda x: jnp.sum(
            -jnp.take_along_axis(
                jax.nn.log_softmax(x @ head, axis=-1), targets[..., None], axis=-1
            )[..., 0] * mask
        ) / jnp.sum(mask))(x)
    np.testing.assert_allclose(
        np.asarray(g_chunk), np.asarray(g_dense), atol=1e-5, rtol=1e-4)


# -- paged decode kernel -----------------------------------------------------


#: layers of the stacked pool in the paged cases: every test reads EACH of
#: them and compares with that layer's own reference, so a wrong layer
#: index cannot pass
_PAGED_LAYERS = 3


def _paged_case(seed=5, b=3, hkv=2, g=2, d=32, nb=9, bs=16, nbk=4,
                layers=_PAGED_LAYERS, lengths=None, poison=None):
    """q, the STACKED pools per head ([L, nb, bs, hkv, d], what the
    reference reads a layer of), tables, lengths.

    Without ``lengths``: the three slots every kernel test started from
    (empty, ends EXACTLY on a page edge, ragged across one).  With
    ``lengths``: one slot each, its pages dealt out of the pool in a
    scrambled order, NULL (0) in the columns it does not own.  ``poison``
    (a float) fills the NULL block, every page no slot owns AND the rows of
    an owned page past its slot's length: nothing of them may reach the
    output."""
    key = jax.random.PRNGKey(seed)
    if lengths is None:
        assert (b, nbk) == (3, 4)
        tables = np.asarray([[1, 0, 0, 0], [2, 0, 0, 0], [3, 4, 5, 6]])
        lengths = [0, bs, 50]
    else:
        b = len(lengths)
        need = [-(-n // bs) for n in lengths]
        assert max(need) <= nbk and sum(need) < nb, (need, nbk, nb)
        order = np.random.default_rng(seed).permutation(np.arange(1, nb))
        tables, at = np.zeros((b, nbk), np.int64), 0
        for slot, n in enumerate(need):
            tables[slot, :n] = order[at:at + n]
            at += n
    q = jax.random.normal(jax.random.fold_in(key, 0), (b, hkv, g, d),
                          jnp.float32)
    k_pages = np.array(jax.random.normal(
        jax.random.fold_in(key, 1), (layers, nb, bs, hkv, d), jnp.float32))
    v_pages = np.array(jax.random.normal(
        jax.random.fold_in(key, 2), (layers, nb, bs, hkv, d), jnp.float32))
    if poison is not None:
        dead = np.ones((nb, bs), bool)
        for slot, n in enumerate(lengths):
            for col in range(-(-n // bs)):
                dead[tables[slot, col], :min(bs, n - col * bs)] = False
        k_pages[:, dead] = poison
        v_pages[:, dead] = poison
    return (q, jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32))


def _lanes(pages):
    """The pool as the engine stores it and the kernel reads it: kv heads
    folded into the lane dim, [L, nb, bs, hkv*d]."""
    return pages.reshape(pages.shape[:-2] + (-1,))


def _paged_reference(q, k_pages, v_pages, tables, lengths, scale):
    q, kp, vp = (np.asarray(x, np.float32) for x in (q, k_pages, v_pages))
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    b, hkv, g, d = q.shape
    o = np.zeros((b, hkv, g, d), np.float32)
    lse = np.full((b, hkv, g), -np.inf, np.float32)
    for bb in range(b):
        n = int(lengths[bb])
        if n == 0:
            continue
        rows_k = np.concatenate([kp[t] for t in tables[bb]], axis=0)[:n]
        rows_v = np.concatenate([vp[t] for t in tables[bb]], axis=0)[:n]
        for h in range(hkv):
            s = q[bb, h] @ rows_k[:, h].T * scale
            m = s.max(-1, keepdims=True)
            p = np.exp(s - m)
            l = p.sum(-1, keepdims=True)
            o[bb, h] = (p / l) @ rows_v[:, h]
            lse[bb, h] = (m + np.log(l))[:, 0]
    return o, lse


#: what the block walk can get wrong, one case each.  A compute block is P
#: pages (here the largest power of two <= the table's width: the pages
#: are small, the VMEM budget never binds), so at nbk 4..7 a block is 4
#: pages of 16 rows = 64 rows.
_PAGED_CASES = {
    # the three slots the tests began with: empty, page edge, ragged
    "empty-edge-ragged": dict(),
    # a length that ends inside a block's FIRST page (64 + 5), ON a page
    # edge inside a block (64 + 16), ON a block edge (128), one row past it
    "first-page-of-block": dict(nbk=8, nb=40, lengths=[69, 80, 128, 65]),
    # an empty slot beside a full one, first and last in the batch: the
    # prefetch has to skip it and the flush still writes o = 0
    "empty-beside-full": dict(nbk=4, nb=20, lengths=[0, 64, 0, 64, 0]),
    # a width P does not divide (7 = 4 + a cut block of 3, which holds
    # live pages) and a width of ONE page
    "width-not-divided": dict(nbk=7, nb=30, lengths=[112, 100, 3, 64]),
    "width-one-page": dict(nbk=1, nb=6, lengths=[16, 0, 7]),
    # NaN / inf in everything dead: the NULL block, unowned pages, the
    # rows of an owned page past the length.  Slots of 3 blocks beside
    # slots that end early in a block, so that a cut block's dead pages
    # lie over rows an earlier full block left in BOTH buffers
    "poison-nan": dict(nbk=12, nb=60, poison=np.nan,
                       lengths=[192, 70, 0, 130, 1, 191]),
    "poison-inf": dict(nbk=12, nb=60, poison=np.inf,
                       lengths=[192, 70, 0, 130, 1, 191]),
    # the two serving cells' head geometry, 2 and 3 blocks a slot (P = 8
    # pages of 32 rows): MHA 32/32 at d 64 and GQA 32/8 at d 128
    "mha-32x32-d64": dict(hkv=32, g=1, d=64, bs=32, nbk=24, nb=50, layers=1,
                          lengths=[600, 0, 257, 512], poison=np.nan),
    "gqa-32x8-d128": dict(hkv=8, g=4, d=128, bs=32, nbk=24, nb=50, layers=1,
                          lengths=[600, 0, 257, 512], poison=np.nan),
}


#: one trace a shape: the layer is a run-time scalar
_paged_jit = jax.jit(paged_decode_attention)


def _assert_paged_matches(o, lse, want_o, want_lse, lengths, tol):
    o, lse, lengths = np.asarray(o), np.asarray(lse), np.asarray(lengths)
    assert np.all(np.isfinite(o))
    np.testing.assert_allclose(o, want_o, atol=tol, rtol=tol)
    live = lengths > 0
    np.testing.assert_allclose(lse[live], want_lse[live], atol=tol, rtol=tol)
    # an empty slot's halves are the logsumexp-merge identity: o = 0 and
    # an lse so low that exp(lse - anything) underflows to exactly 0 (the
    # kernel uses a finite -1e30 sentinel, not IEEE -inf, so the merge
    # arithmetic stays NaN-free)
    assert np.all(o[~live] == 0.0)
    assert np.all(lse[~live] <= -1e29)
    assert np.all(np.exp(lse[~live]) == 0.0)


@pytest.mark.parametrize("case", sorted(_PAGED_CASES))
def test_paged_decode_matches_reference(case):
    """Block-table walk vs a dense gather+softmax reference: ragged lengths
    (empty slot -> o=0/lse=-inf, exact-boundary slot, mid-block slot), no
    dense [B, max_len] intermediate on the kernel side; every layer of the
    stacked pool against its own reference."""
    kwargs = _PAGED_CASES[case]
    q, kp, vp, tables, lengths = _paged_case(**kwargs)
    scale = q.shape[-1] ** -0.5
    # the reference may read poison only where it is masked: clean it
    clean = [jnp.nan_to_num(x, nan=0.0, posinf=0.0) for x in (kp, vp)]
    for layer in range(kp.shape[0]):
        o, lse = _paged_jit(q, _lanes(kp), _lanes(vp), jnp.int32(layer),
                            tables, lengths)
        want_o, want_lse = _paged_reference(
            q, clean[0][layer], clean[1][layer], tables, lengths, scale)
        _assert_paged_matches(o, lse, want_o, want_lse, lengths, 1e-5)


def test_paged_decode_layer_index_is_traced():
    """The layer is a run-time scalar (the engine's layer scan carries
    it): one compiled program reads whichever layer it is handed, and the
    layers differ."""
    q, kp, vp, tables, lengths = _paged_case()
    fn = jax.jit(paged_decode_attention)
    outs = [np.asarray(fn(q, _lanes(kp), _lanes(vp), jnp.int32(layer),
                          tables, lengths)[0])
            for layer in range(_PAGED_LAYERS)]
    for layer, o in enumerate(outs):
        want_o, _ = _paged_reference(q, kp[layer], vp[layer], tables,
                                     lengths, q.shape[-1] ** -0.5)
        np.testing.assert_allclose(o, want_o, atol=1e-5, rtol=1e-5)
    assert not np.allclose(outs[0], outs[1], atol=1e-3)


@pytest.mark.parametrize("full,cut,fits", [
    # the same pages a step (P = 4 at both widths): the same numbers, bit
    # for bit, from a walk one block shorter
    (7, 4, 50),
    # an odd width, and one narrower than the full table's block (P = 2
    # against 4): the same attention set summed in other blocks
    (4, 3, 40),
    (4, 2, 30),
    (4, 1, 16),
])
def test_paged_decode_ragged_table_slice_is_exact(full, cut, fits):
    """A table sliced to the ragged bucket (the engine's fast path) walks
    fewer pages but must produce the SAME numbers when every length fits
    the slice."""
    from dstack_tpu.ops.flash_attention import _pages_per_step

    q, kp, vp, tables, lengths = _paged_case(
        nbk=full, nb=20, lengths=[0, 16, min(fits, 50), fits])
    kp, vp = _lanes(kp), _lanes(vp)
    same_block = (_pages_per_step(kp[0, 0].nbytes, full)
                  == _pages_per_step(kp[0, 0].nbytes, cut))
    assert same_block == (cut == 4)
    for layer in range(_PAGED_LAYERS):
        o_full, lse_full = _paged_jit(q, kp, vp, jnp.int32(layer), tables,
                                      lengths)
        o_cut, lse_cut = _paged_jit(q, kp, vp, jnp.int32(layer),
                                    tables[:, :cut], lengths)
        # another block size sums the same rows in another order
        tol = dict(rtol=0, atol=0) if same_block else dict(rtol=2e-6,
                                                            atol=2e-6)
        np.testing.assert_allclose(np.asarray(o_full), np.asarray(o_cut),
                                   **tol)
        np.testing.assert_allclose(np.asarray(lse_full), np.asarray(lse_cut),
                                   **tol)


@pytest.mark.parametrize("case", ["empty-edge-ragged", "first-page-of-block",
                                  "width-not-divided", "poison-nan"])
def test_paged_decode_int8_pages_match_dequantized_reference(case):
    """int8 {"q","s"} pages dequantize IN-KERNEL (per-row f32 scales) —
    against the float reference computed on the dequantized pool the only
    difference is float association, not quantization handling.  Poison
    goes into the SCALES of everything dead (an int8 page holds no NaN)."""
    from dstack_tpu.serving.quant import dequantize_kv, quantize_kv

    kwargs = dict(_PAGED_CASES[case])
    poison = kwargs.pop("poison", None)
    q, kp, vp, tables, lengths = _paged_case(**kwargs)
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    k_deq = dequantize_kv(kq, ks, jnp.float32)
    v_deq = dequantize_kv(vq, vs, jnp.float32)
    if poison is not None:
        marked = _paged_case(**kwargs, poison=poison)[1]
        dead = jnp.isnan(marked[..., 0, 0])[..., None]      # [L, nb, bs, 1]
        ks, vs = jnp.where(dead, poison, ks), jnp.where(dead, poison, vs)
    for layer in range(_PAGED_LAYERS):
        o, lse = _paged_jit(
            q, {"q": _lanes(kq), "s": ks}, {"q": _lanes(vq), "s": vs},
            jnp.int32(layer), tables, lengths)
        want_o, want_lse = _paged_reference(
            q, k_deq[layer], v_deq[layer], tables, lengths,
            q.shape[-1] ** -0.5)
        _assert_paged_matches(o, lse, want_o, want_lse, lengths, 1e-4)


def test_paged_decode_rejects_int4_pages():
    q, kp, vp, tables, lengths = _paged_case()
    fake_int4 = {"q4": jnp.zeros((_PAGED_LAYERS, 9, 16, 2 * 16), jnp.int8),
                 "s": jnp.ones((_PAGED_LAYERS, 9, 16, 2), jnp.float32)}
    with pytest.raises(NotImplementedError):
        paged_decode_attention(q, fake_int4, fake_int4, 0, tables, lengths)


def test_paged_decode_rejects_unfolded_pages():
    """The per-head [.., Hkv, D] pool of before is refused by shape, not
    read as garbage."""
    q, kp, vp, tables, lengths = _paged_case()
    with pytest.raises(ValueError, match="Hkv\\*D"):
        paged_decode_attention(q, kp[0], vp[0], 0, tables, lengths)

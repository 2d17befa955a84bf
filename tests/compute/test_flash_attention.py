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
                layers=_PAGED_LAYERS):
    """q, the STACKED pools per head ([L, nb, bs, hkv, d], what the
    reference reads a layer of), tables, lengths."""
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (b, hkv, g, d),
                          jnp.float32)
    k_pages = jax.random.normal(jax.random.fold_in(key, 1),
                                (layers, nb, bs, hkv, d), jnp.float32)
    v_pages = jax.random.normal(jax.random.fold_in(key, 2),
                                (layers, nb, bs, hkv, d), jnp.float32)
    # slot 0 empty, slot 1 ends EXACTLY on a block boundary, slot 2 ragged
    # across a boundary mid-block; NULL (0) entries pad unused columns
    tables = jnp.asarray([[1, 0, 0, 0],
                          [2, 0, 0, 0],
                          [3, 4, 5, 6]], jnp.int32)
    lengths = jnp.asarray([0, bs, 50], jnp.int32)
    return q, k_pages, v_pages, tables, lengths


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


def test_paged_decode_matches_reference():
    """Block-table walk vs a dense gather+softmax reference: ragged lengths
    (empty slot -> o=0/lse=-inf, exact-boundary slot, mid-block slot), no
    dense [B, max_len] intermediate on the kernel side; every layer of the
    stacked pool against its own reference."""
    q, kp, vp, tables, lengths = _paged_case()
    scale = q.shape[-1] ** -0.5
    for layer in range(_PAGED_LAYERS):
        o, lse = paged_decode_attention(q, _lanes(kp), _lanes(vp), layer,
                                        tables, lengths)
        want_o, want_lse = _paged_reference(q, kp[layer], vp[layer], tables,
                                            lengths, scale)
        np.testing.assert_allclose(np.asarray(o), want_o, atol=1e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(lse)[1:], want_lse[1:],
                                   atol=1e-5, rtol=1e-5)
        # the empty slot's halves are the logsumexp-merge identity: o = 0
        # and an lse so low that exp(lse - anything) underflows to exactly
        # 0 (the kernel uses a finite -1e30 sentinel, not IEEE -inf, so the
        # merge arithmetic stays NaN-free)
        assert np.all(np.asarray(o)[0] == 0.0)
        assert np.all(np.asarray(lse)[0] <= -1e29)
        assert np.all(np.exp(np.asarray(lse)[0]) == 0.0)


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


def test_paged_decode_ragged_table_slice_is_exact():
    """A table sliced to the ragged bucket (the engine's fast path) walks
    fewer pages but must produce the SAME numbers when every length fits
    the slice."""
    q, kp, vp, tables, lengths = _paged_case()
    kp, vp = _lanes(kp), _lanes(vp)
    lengths = jnp.minimum(lengths, 30)  # everything fits 2 blocks
    for layer in range(_PAGED_LAYERS):
        o_full, lse_full = paged_decode_attention(q, kp, vp, layer, tables,
                                                  lengths)
        o_cut, lse_cut = paged_decode_attention(q, kp, vp, layer,
                                                tables[:, :2], lengths)
        np.testing.assert_array_equal(np.asarray(o_full), np.asarray(o_cut))
        np.testing.assert_array_equal(np.asarray(lse_full),
                                      np.asarray(lse_cut))


def test_paged_decode_int8_pages_match_dequantized_reference():
    """int8 {"q","s"} pages dequantize IN-KERNEL (per-row f32 scales) —
    against the float reference computed on the dequantized pool the only
    difference is float association, not quantization handling."""
    from dstack_tpu.serving.quant import dequantize_kv, quantize_kv

    q, kp, vp, tables, lengths = _paged_case()
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    k_deq = dequantize_kv(kq, ks, jnp.float32)
    v_deq = dequantize_kv(vq, vs, jnp.float32)
    for layer in range(_PAGED_LAYERS):
        o, lse = paged_decode_attention(
            q, {"q": _lanes(kq), "s": ks}, {"q": _lanes(vq), "s": vs},
            layer, tables, lengths)
        want_o, want_lse = _paged_reference(
            q, k_deq[layer], v_deq[layer], tables, lengths,
            q.shape[-1] ** -0.5)
        np.testing.assert_allclose(np.asarray(o), want_o, atol=1e-4,
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(lse)[1:], want_lse[1:],
                                   atol=1e-4, rtol=1e-4)
        assert np.all(np.asarray(lse)[0] <= -1e29)  # empty slot sentinel


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

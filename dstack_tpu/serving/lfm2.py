"""The LFM2-MoE decoder (``models/lfm2.py``) behind the engine's seam: a
mixed stack whose attention layers keep K and V rows in the Llama family's
paged pool and whose convolution layers keep a fixed tail per slot.
``serving/dense.py`` and ``serving/hybrid.py`` answer the same calls for
their families; ``serving/families.py`` picks among them.

The engine's two donated trees of device state are

* ``pool`` = {"k", "v"}, each [attention layers, blocks, block_size,
  Hkv * head_dim]: the paged pool in the form ``serving/dense.py`` stores
  it (kv heads folded into the lanes), over the ATTENTION layers only,
  addressed through the same block tables and the same allocator;
* ``rec`` [conv layers, slots, conv_L_cache - 1, hidden]: the convolutions'
  tails, the last rows of ``B * X`` of each slot, whatever its length.

A slot's tail is never reset by a program of its own: the prefill that
admits a request (one program for a group of whole prompts, which streams
the held experts once for all of them) starts from zeros and overwrites the
slot, and a prompt's first chunk (``prefix_len == 0``) starts from zeros
instead of reading it; later chunks read and write their slot.  Chunks go
out a group of different slots a program too (``chunk_batch_fn``).  A decode
window leaves slots that are not ``active`` (free, or mid-chunk) untouched.

The decode window is the engine's buffered one (``serving/dense.py``
documents it): the pool read-only, the window's K and V rows in a buffer
carried through the step scan, the cache half read by the block-table
kernel on a TPU and through one gathered view elsewhere, one scatter at the
end (``serving/paged_window.py`` holds what the families share of it).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from dstack_tpu.models import lfm2 as model
from dstack_tpu.ops.pool import scatter_rows
from dstack_tpu.serving import paged_window


class Lfm2Programs:
    #: why prefill/decode disaggregation is refused
    pd_refusal = (
        "prefill/decode disaggregation is not served for this "
        "model: the wire carries K and V rows of every layer, not "
        "convolution tails beside the rows of some")

    def __init__(self, cfg: model.Lfm2MoeConfig, *, batch_size: int,
                 max_len: int, paged: bool, block_size: int, num_blocks: int,
                 prefix_cache: bool, quantize: Optional[str],
                 kv_quantize: Optional[str], mesh: Optional[Any],
                 sharding_policy: Optional[Any], sample: Callable):
        """What ``serving/dense.py`` ``DensePrograms`` takes, of which this
        model is served with the paged pool alone."""
        for refused, needs in (
            (not paged, "paged=False: the attention layers' rows live in "
             "the paged pool, a dense row per slot is not written"),
            (prefix_cache, "prefix_cache: a cached block would need a "
             "snapshot of the convolution tails at its boundary"),
            (kv_quantize, "kv_quantize: the window buffer and the "
             "end-of-window scatter write plain rows, not packed ones "
             "with scales"),
            (quantize, "quantize: the grouped expert product would need "
             "int8 forms of the expert stacks"),
            (mesh is not None, "a mesh: it would need the expert "
             "exchange and sharding rules for the tails"),
        ):
            if refused:
                raise ValueError(
                    f"{type(cfg).__name__} is not served with {needs}")
        self.cfg = cfg
        self.batch_size = batch_size
        self.max_len = max_len
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.blocks_per_slot = max_len // block_size
        #: Pallas block-table decode kernel (resolved once at init)
        self._paged_kernel = paged_window.paged_kernel_default()
        #: the engine's on-device sampler (logits, temps, top_ps, top_ks, rng)
        self._sample = sample

    def prepare_params(self, params: Optional[model.Params], rng_seed: int):
        """The weights, initialised from ``rng_seed`` when ``params`` is
        None, committed to the device (what an uncommitted tree does to a
        compile-cache key: ``DensePrograms.prepare_params``)."""
        if params is None:
            params = model.init_params(jax.random.PRNGKey(rng_seed), self.cfg)
        return jax.device_put(params, jax.devices()[0])

    # -- state ---------------------------------------------------------------
    def init_state(self):
        """``(pool, rec)``, all zeros."""
        cfg = self.cfg
        leaf = (cfg.attention_layers, self.num_blocks, self.block_size,
                cfg.kv_lanes)
        pool = {"k": jnp.zeros(leaf, cfg.dtype),
                "v": jnp.zeros(leaf, cfg.dtype)}
        rec = jnp.zeros((cfg.conv_layers, self.batch_size, cfg.conv_reach,
                         cfg.hidden_size), cfg.dtype)
        return pool, rec

    def recurrent_state_bytes(self) -> int:
        """Bytes of ``rec``: the tails the slots hold whatever their
        lengths (the ``recurrent_state_bytes`` gauge)."""
        return self.cfg.recurrent_state_bytes(self.batch_size)

    def kv_geometry(self) -> tuple:
        """(cache layers, bytes one token holds over them): a K and a V row
        in each ATTENTION layer."""
        cfg = self.cfg
        return cfg.attention_layers, (
            2 * cfg.attention_layers * cfg.kv_lanes
            * jnp.dtype(cfg.dtype).itemsize)

    @staticmethod
    def record_window_counts(telemetry, counts) -> None:
        """A drained window's last output: its expert load."""
        if telemetry is None:
            return
        telemetry.record_expert_load(*counts.tolist())

    @staticmethod
    def record_prompt_program(telemetry, bucket: int) -> None:
        """A prefill or chunk program of ``bucket`` positions ran: nothing
        this family counts (``serving/nemotron_h.py`` counts its scans)."""

    @staticmethod
    def slot_target(slot_id: int, pages):
        """Where a prefill or chunk program writes: the slot's pages and
        the slot, whose tails it starts or carries."""
        return pages, jnp.int32(slot_id)

    # -- prefill -------------------------------------------------------------
    def prefill_batch_fn(self, bucket: int):
        """A group of whole prompts into empty slots, in one program:
        ``fn(params, tokens [P, bucket], lengths [P], pool, rec, block ids
        [P, bucket / block_size], slots [P])`` returns ``(logits [P, V],
        pool, rec)``.  An unused entry (length 0, slot ``batch_size``, block
        ids past the pool) writes no page and no slot."""
        cfg = self.cfg

        def fn(params, tokens, lengths, pool, rec, bids, slots):
            def attend(m, q, k, v):
                nonlocal pool
                pool, out = paged_window.attend_prompts(pool, m, bids, q, k,
                                                        v)
                return out

            logits, tails = model.prompts_forward(params, cfg, tokens,
                                                  lengths, attend)
            return logits, pool, rec.at[:, slots].set(
                tails.astype(rec.dtype), mode="drop")

        return fn

    def chunk_fn(self, cbucket: int):
        """One chunk of a long prompt: ``fn(params, tokens [cbucket],
        chunk_len, prefix_len, pool, rec, (table row, slot))``.  The K and V
        rows go into the slot's pages and the chunk attends the slot's whole
        span; the tails come from the slot (zeros at ``prefix_len`` 0) and
        go back to it.  The engine sends :meth:`chunk_batch_fn`'s groups;
        this form is what a group's entry must leave."""
        cfg, bs, nb = self.cfg, self.block_size, self.num_blocks
        span = self.blocks_per_slot * self.block_size
        heads = (span, cfg.num_key_value_heads, cfg.head_dim)

        def fn(params, tokens, chunk_len, prefix_len, pool, rec, target):
            tables_row, slot = target
            mine = paged_window.slot_rows(rec, slot)
            mine = jnp.where(prefix_len == 0, jnp.zeros_like(mine), mine)
            blk, off = paged_window.chunk_pages(prefix_len, cbucket,
                                                tables_row, bs, span)
            positions = prefix_len + jnp.arange(cbucket)[None, :]
            kv_pos = jnp.arange(span)[None, :]

            def attend(m, q, k, v):
                nonlocal pool
                idx = paged_window.flat_rows(m, blk, off, nb, bs)
                with jax.named_scope("kv_insert"):
                    # (scatter_rows folds the heads into the pool's lanes)
                    pool = {"k": scatter_rows(pool["k"], idx, k),
                            "v": scatter_rows(pool["v"], idx, v)}
                mine_k, mine_v = (
                    paged_window.slot_span(pool[key], m, tables_row,
                                           nb).reshape(heads)
                    for key in ("k", "v"))
                return paged_window.masked_attention(
                    q[None], mine_k[None], mine_v[None], positions,
                    kv_pos)[0]

            logits, tails = model.sequence_forward(
                params, cfg, tokens[None], chunk_len[None], prefix_len[None],
                mine[:, None], attend)
            return logits[0], pool, rec.at[:, slot].set(
                tails[:, 0].astype(rec.dtype))

        return fn

    def chunk_batch_fn(self, cbucket: int):
        """A group of chunks, each of its own slot, in one program:
        ``fn(params, tokens [G, cbucket], chunk_lens [G], prefix_lens [G],
        pool, rec, (table rows [G, blocks a slot], slots [G]))`` returns
        ``(logits [G, V], pool, rec)``.  Each entry is what
        :meth:`chunk_fn` does with it: its rows into its slot's pages, its
        attention over its slot's span (one entry at a time), its tails
        from its slot (zeros at ``prefix_len`` 0) and back to it; the
        experts' grouped product runs once for the group.  An unused entry
        (length 0, slot ``batch_size``, a table row past the pool's blocks)
        writes no page and no slot."""
        cfg = self.cfg

        def fn(params, tokens, chunk_lens, prefix_lens, pool, rec, target):
            tables_rows, slots = target
            mine = jnp.where((prefix_lens == 0)[:, None, None], 0,
                             rec[:, slots])

            def attend(m, q, k, v):
                nonlocal pool
                pool, out = paged_window.attend_chunks(
                    pool, m, tables_rows, prefix_lens, chunk_lens, q, k, v)
                return out

            logits, tails = model.sequence_forward(
                params, cfg, tokens, chunk_lens, prefix_lens, mine, attend)
            return logits, pool, rec.at[:, slots].set(
                tails.astype(rec.dtype), mode="drop")

        return fn

    # -- the PD wire ---------------------------------------------------------
    def export_fn(self, bucket: int):
        raise ValueError(self.pd_refusal)

    def insert_rows(self, pool, rec, prefill: dict, n: int, target):
        raise ValueError(self.pd_refusal)

    # -- decode --------------------------------------------------------------
    def decode_window_fn(self, window: int, sampling: bool,
                         kv_blocks: Optional[int]):
        """``window`` tokens for every active slot in one program: the
        engine's buffered window over the attention layers' pool, with the
        tails carried through the steps in place.  Returns what the Llama
        window returns and, last, the window's expert load (float32
        [``model.LOAD_FIELDS``], :func:`model.moe_ffn`) for the telemetry."""
        cfg, b, w, bs = self.cfg, self.batch_size, window, self.block_size
        layers = cfg.attention_layers
        hkv, hd = cfg.num_key_value_heads, cfg.head_dim
        group = cfg.num_attention_heads // hkv
        nbk = kv_blocks or self.blocks_per_slot
        span = nbk * bs
        max_len = self.max_len
        use_kernel = self._paged_kernel
        if use_kernel:
            from dstack_tpu.ops.flash_attention import paged_decode_attention

        def fn(params, last_token, lengths, active, pool, rec, temps, top_ps,
               top_ks, tables, rng):
            base_len = jnp.minimum(lengths, max_len - 1)
            if not use_kernel:
                # one gather for the whole window: every slot's pages laid
                # end to end, [L_attn, B, span, Hkv, D]
                view_k, view_v = (
                    pool[key][:, tables].reshape(layers, b, span, hkv, hd)
                    for key in ("k", "v"))
                cache_mask = (jnp.arange(span)[None, :]
                              < base_len[:, None])[:, None, None, :]
            # row (m, i) is step i's K (or V) at attention layer m, carried
            # through the step scan and written in place
            win0 = jnp.zeros((layers, w, b, hkv, hd), cfg.dtype)
            win_j = jnp.arange(w)

            def one_step(carry, inputs):
                last_token, step_lengths, win_k, win_v, tails, load = carry
                i, step_rng = inputs
                positions = jnp.minimum(step_lengths, max_len - 1)
                x = params["embed"].astype(cfg.dtype)[last_token]
                win_mask = (win_j[None, :] <= i)[:, None, None, :]

                def attend(m, q, k, v):
                    nonlocal win_k, win_v
                    win_k = jax.lax.dynamic_update_slice(
                        win_k, k[None, None], (m, i, 0, 0, 0))
                    win_v = jax.lax.dynamic_update_slice(
                        win_v, v[None, None], (m, i, 0, 0, 0))
                    qg = q.reshape(b, hkv, group, hd)
                    if use_kernel:
                        o = paged_window.attend_pages_and_window(
                            paged_decode_attention, qg, pool["k"], pool["v"],
                            jnp.int32(m), tables, base_len, win_k[m],
                            win_v[m], win_mask, x.dtype)
                    else:
                        o = paged_window.attend_view_and_window(
                            qg, view_k[m], view_v[m], cache_mask, win_k[m],
                            win_v[m], win_mask, x.dtype)
                    return o.reshape(b, hkv * group, hd)

                x, tails, step_load = model.decode_step(
                    params, cfg, x, positions, active, tails, attend)
                logits = model.output_logits(params, cfg, x)
                if sampling:
                    tokens = self._sample(logits, temps, top_ps, top_ks,
                                          step_rng)
                else:
                    with jax.named_scope("sample"):
                        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                new_lengths = jnp.where(active, step_lengths + 1,
                                        step_lengths)
                return (tokens, new_lengths, win_k, win_v, tails,
                        load + step_load), tokens

            (last, new_lengths, win_k, win_v, tails, load), tokens_all = \
                jax.lax.scan(
                    one_step,
                    (last_token, lengths, win0, win0, rec,
                     jnp.zeros((model.LOAD_FIELDS,), jnp.float32)),
                    (jnp.arange(w), jax.random.split(rng, w)))

            # the window's rows into each slot's pages (positions base_len +
            # j)
            idx = paged_window.window_rows(layers, base_len, active, tables,
                                           win_j, bs, self.num_blocks)
            with jax.named_scope("kv_window_write"):
                pool = {"k": scatter_rows(pool["k"], idx,
                                          jnp.moveaxis(win_k, 1, 2)),
                        "v": scatter_rows(pool["v"], idx,
                                          jnp.moveaxis(win_v, 1, 2))}
            return tokens_all, last, new_lengths, pool, tails, load

        return fn

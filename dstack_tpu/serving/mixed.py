"""The decoders that keep K and V rows of their attention layers in the
Llama family's paged pool beside a fixed state a slot over their other
layers, behind the engine's seam: one provider, :class:`MixedPrograms`,
built with the model module that computes the config (``serving/families.py``
pairs them: ``models/lfm2.py``, short convolutions' tails;
``models/nemotron_h.py``, Mamba-2 states and tails).  This module names no
model; what differs between the families is their model module's.

The engine's two donated trees of device state are

* ``pool`` = {"k", "v"}, each [attention layers, blocks, block_size,
  Hkv * head_dim]: the paged pool in the form ``serving/dense.py`` stores
  it (kv heads folded into the lanes), over the ATTENTION layers only,
  addressed through the same block tables and the same allocator;
* ``rec``: the model's state of every slot, in the form the model keeps it
  (``init_rec``), read and written here only through the model's
  ``read_slots`` and ``write_slots``.

A slot's state is never reset by a program of its own: the prefill that
admits a request (one program for a group of whole prompts, which streams
the held experts once for all of them) starts from zeros and overwrites the
slot, and a prompt's first chunk (``prefix_len == 0``) starts from zeros
instead of reading it; later chunks read and write their slot.  Chunks go
out a group of different slots a program too (``chunk_batch_fn``).  A decode
window leaves slots that are not ``active`` (free, or mid-chunk) untouched.

The decode window is the engine's buffered one (``serving/dense.py``
documents it; ``serving/paged_window.py`` holds what the families share of
it), with the per-slot state a donated carry of its step scan.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from dstack_tpu.ops.pool import scatter_rows
from dstack_tpu.serving import paged_window


class SlotStatePrograms:
    """The seam's calls that do not depend on a family's programs, for a
    model served with a paged pool beside a fixed state a slot."""
    #: the family's model module (``init_params``, ``LOAD_FIELDS``)
    model: Any
    #: why each engine option the family is not served with is refused
    refusals: dict
    #: why prefill/decode disaggregation is refused
    pd_refusal: str

    def __init__(self, cfg, *, batch_size: int, max_len: int, paged: bool,
                 block_size: int, num_blocks: int, prefix_cache: bool,
                 quantize: Optional[str], kv_quantize: Optional[str],
                 mesh: Optional[Any], sharding_policy: Optional[Any],
                 sample: Callable):
        """What ``serving/dense.py`` ``DensePrograms`` takes, of which these
        models are served with the paged pool alone."""
        asked = {"paged=False": not paged, "prefix_cache": prefix_cache,
                 "kv_quantize": kv_quantize, "quantize": quantize,
                 "a mesh": mesh is not None}
        for option, refused in asked.items():
            if refused:
                raise ValueError(f"{type(cfg).__name__} is not served with "
                                 f"{option}: {self.refusals[option]}")
        self.cfg = cfg
        self.batch_size = batch_size
        self.max_len = max_len
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.blocks_per_slot = max_len // block_size
        #: the engine's on-device sampler (logits, temps, top_ps, top_ks, rng)
        self._sample = sample

    def prepare_params(self, params, rng_seed: int):
        """The weights, initialised from ``rng_seed`` when ``params`` is
        None, committed to the device (what an uncommitted tree does to a
        compile-cache key: ``DensePrograms.prepare_params``)."""
        if params is None:
            params = self.model.init_params(jax.random.PRNGKey(rng_seed),
                                            self.cfg)
        return jax.device_put(params, jax.devices()[0])

    def recurrent_state_bytes(self) -> int:
        """Bytes of ``rec``: what the slots hold whatever their lengths (the
        ``recurrent_state_bytes`` gauge)."""
        return self.cfg.recurrent_state_bytes(self.batch_size)

    def record_window_counts(self, telemetry, counts) -> None:
        """A drained window's last output: its expert load, then what the
        model counts beside it (a state-space model: the slot-layer-steps
        of its updates)."""
        if telemetry is None:
            return
        counts, fields = counts.tolist(), self.model.LOAD_FIELDS
        telemetry.record_expert_load(*counts[:fields])
        if counts[fields:]:
            telemetry.record_ssm_steps(*counts[fields:])

    @staticmethod
    def record_prompt_program(telemetry, bucket: int) -> None:
        """A prefill or chunk program of ``bucket`` positions ran: nothing
        the family counts (:class:`MixedPrograms` asks its model)."""

    @staticmethod
    def slot_target(slot_id: int, pages):
        """Where a prefill or chunk program writes: the slot's pages and
        the slot, whose state it starts or carries."""
        return pages, jnp.int32(slot_id)

    # -- the PD wire ---------------------------------------------------------
    def export_fn(self, bucket: int):
        raise ValueError(self.pd_refusal)

    def insert_rows(self, pool, rec, prefill: dict, n: int, target):
        raise ValueError(self.pd_refusal)


class MixedPrograms(SlotStatePrograms):
    """A paged K and V pool over the attention layers beside the model's
    per-slot state; the model module answers the contract
    ``serving/families.py`` states."""
    refusals = {
        "paged=False": "the attention layers' rows live in the paged "
                       "pool, a dense row per slot is not written",
        "prefix_cache": "a cached block would need a snapshot of the "
                        "slots' state at its boundary (a state-space "
                        "state is 2 MiB a layer a block)",
        "kv_quantize": "the window buffer and the end-of-window scatter "
                       "write plain rows, not packed ones with scales",
        "quantize": "the grouped expert product would need int8 forms of "
                    "the expert stacks",
        "a mesh": "it would need the expert exchange and sharding rules "
                  "for the slots' state",
    }
    pd_refusal = (
        "prefill/decode disaggregation is not served for this "
        "model: the wire carries K and V rows of every layer, not "
        "a per-slot state beside the rows of some")

    def __init__(self, cfg, *, model, **built_with):
        """:class:`SlotStatePrograms`'s keywords, and ``model``: the module
        that computes ``cfg`` (``serving/families.py`` names it)."""
        super().__init__(cfg, **built_with)
        self.model = model
        #: Pallas block-table decode kernel (resolved once at init)
        self._paged_kernel = paged_window.paged_kernel_default()

    # -- state ---------------------------------------------------------------
    def init_state(self):
        """``(pool, rec)``, all zeros."""
        cfg = self.cfg
        leaf = (cfg.attention_layers, self.num_blocks, self.block_size,
                cfg.kv_lanes)
        pool = {"k": jnp.zeros(leaf, cfg.dtype),
                "v": jnp.zeros(leaf, cfg.dtype)}
        return pool, self.model.init_rec(cfg, self.batch_size)

    def kv_geometry(self) -> tuple:
        """(cache layers, bytes one token holds over them): a K and a V row
        in each ATTENTION layer."""
        cfg = self.cfg
        return cfg.attention_layers, (
            2 * cfg.attention_layers * cfg.kv_lanes
            * jnp.dtype(cfg.dtype).itemsize)

    def record_prompt_program(self, telemetry, bucket: int) -> None:
        """A prefill or chunk program of ``bucket`` positions ran: the
        blocks that went through the model's chunked scans, if it has
        any."""
        chunks = self.model.scan_chunks(self.cfg, bucket)
        if telemetry is not None and chunks:
            telemetry.record_ssm_scan_chunks(chunks)

    # -- prefill -------------------------------------------------------------
    def prefill_batch_fn(self, bucket: int):
        """A group of whole prompts into empty slots, in one program:
        ``fn(params, tokens [P, bucket], lengths [P], pool, rec, block ids
        [P, bucket / block_size], slots [P])`` returns ``(logits [P, V],
        pool, rec)``.  An unused entry (length 0, slot ``batch_size``, block
        ids past the pool) writes no page and no slot."""
        cfg, model = self.cfg, self.model

        def fn(params, tokens, lengths, pool, rec, bids, slots):
            def attend(m, q, k, v):
                nonlocal pool
                pool, out = paged_window.attend_prompts(pool, m, bids, q, k,
                                                        v)
                return out

            logits, mine = model.prompts_forward(params, cfg, tokens,
                                                 lengths, attend)
            return logits, pool, model.write_slots(rec, slots, mine)

        return fn

    def chunk_fn(self, cbucket: int):
        """One chunk of a long prompt: ``fn(params, tokens [cbucket],
        chunk_len, prefix_len, pool, rec, (table row, slot))``.  The K and V
        rows go into the slot's pages and the chunk attends the slot's whole
        span; the state comes from the slot (zeros at ``prefix_len`` 0) and
        goes back to it.  The engine sends :meth:`chunk_batch_fn`'s groups;
        this form is what a group's entry must leave."""
        cfg, model = self.cfg, self.model
        bs, nb = self.block_size, self.num_blocks
        span = self.blocks_per_slot * self.block_size
        heads = (span, cfg.num_key_value_heads, cfg.head_dim)

        def fn(params, tokens, chunk_len, prefix_len, pool, rec, target):
            tables_row, slot = target
            slots = slot[None]
            mine = model.read_slots(rec, slots, (prefix_len == 0)[None])
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

            logits, mine = model.sequence_forward(
                params, cfg, tokens[None], chunk_len[None], prefix_len[None],
                mine, attend)
            return logits[0], pool, model.write_slots(rec, slots, mine)

        return fn

    def chunk_batch_fn(self, cbucket: int):
        """A group of chunks, each of its own slot, in one program:
        ``fn(params, tokens [G, cbucket], chunk_lens [G], prefix_lens [G],
        pool, rec, (table rows [G, blocks a slot], slots [G]))`` returns
        ``(logits [G, V], pool, rec)``.  Each entry is what
        :meth:`chunk_fn` does with it: its rows into its slot's pages, its
        attention over its slot's span (one entry at a time), its state
        from its slot (zeros at ``prefix_len`` 0) and back to it; the
        experts' grouped product runs once for the group.  An unused entry
        (length 0, slot ``batch_size``, a table row past the pool's blocks)
        writes no page and no slot."""
        cfg, model = self.cfg, self.model

        def fn(params, tokens, chunk_lens, prefix_lens, pool, rec, target):
            tables_rows, slots = target
            mine = model.read_slots(rec, slots, prefix_lens == 0)

            def attend(m, q, k, v):
                nonlocal pool
                pool, out = paged_window.attend_chunks(
                    pool, m, tables_rows, prefix_lens, chunk_lens, q, k, v)
                return out

            logits, mine = model.sequence_forward(
                params, cfg, tokens, chunk_lens, prefix_lens, mine, attend)
            return logits, pool, model.write_slots(rec, slots, mine)

        return fn

    # -- decode --------------------------------------------------------------
    def decode_window_fn(self, window: int, sampling: bool,
                         kv_blocks: Optional[int]):
        """``window`` tokens for every active slot in one program: the
        engine's buffered window over the attention layers' pool, with the
        per-slot state carried through the steps in place.  Returns what
        the Llama window returns and, last, the window's counts for the
        telemetry (the model's ``window_counts``: its expert load, float32
        [``LOAD_FIELDS``], first)."""
        cfg, model = self.cfg, self.model
        b, w, bs = self.batch_size, window, self.block_size
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
                last_token, step_lengths, win_k, win_v, rec, load = carry
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

                x, rec, step_load = model.decode_step(
                    params, cfg, x, positions, active, rec, attend)
                logits = model.output_logits(params, cfg, x)
                if sampling:
                    tokens = self._sample(logits, temps, top_ps, top_ks,
                                          step_rng)
                else:
                    with jax.named_scope("sample"):
                        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                new_lengths = jnp.where(active, step_lengths + 1,
                                        step_lengths)
                return (tokens, new_lengths, win_k, win_v, rec,
                        load + step_load), tokens

            (last, new_lengths, win_k, win_v, rec, load), tokens_all = \
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
            return (tokens_all, last, new_lengths, pool, rec,
                    model.window_counts(cfg, load, active, w))

        return fn

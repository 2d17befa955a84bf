"""The hybrid decoder (``models/ling_hybrid.py``) behind the engine's seam:
the device state of a model whose memory is not K and V rows, and the
programs the scheduler runs over it (whole-prompt prefill, one chunk, a
decode window).  ``serving/dense.py`` answers the same calls for the Llama
family; ``serving/families.py`` picks between them.

The engine keeps two donated trees of device state and hands both to every
program as they are.  For a Llama-family model they are the K and the V
pool; here they are

* ``pool`` [L_mla, blocks, block_size, lanes]: the paged pool with ONE leaf,
  a latent row a token (``ops/mla.py``), addressed through the same block
  tables and the same allocator;
* ``rec`` = {"state": [L_kda, slots, H, d_k, d_v] float32, "tail": [L_kda,
  slots, kernel - 1, 3 * kda_dim]}: the KDA layers' recurrent state, fixed
  per slot whatever the length, not paged.

A slot's recurrent state is never reset by a program of its own: the
prefill that admits a request starts from zeros and overwrites the slot,
and a prompt's first chunk (``prefix_len == 0``) starts from zeros instead
of reading it.  Later chunks read and write their slot, so chunked prefill
carries the state through the engine's chunk queue as it is.  A decode
window leaves slots that are not ``active`` (free, or mid-chunk) untouched.

MLA decode reads the pool through a gather, once a window (each slot's pages
laid end to end, as the Llama family's XLA path does for K and V), not through
``paged_decode_attention``: that kernel takes K and V pools of one head
width and scales by it, and MLA's key is 576 lanes where its value is 512.
(A variant for the latent pool could keep that kernel's walk, a block of
pages a grid step copied out of the pool by the tables and cut at the
length, but not its products: here every head reads the SAME row.)
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from dstack_tpu.models import ling_hybrid as model
from dstack_tpu.ops import mla
from dstack_tpu.ops.pool import scatter_rows
from dstack_tpu.ops.rmsnorm import rms_norm
from dstack_tpu.serving import paged_window


class HybridPrograms:
    #: why prefill/decode disaggregation is refused
    pd_refusal = (
        "prefill/decode disaggregation is not served for this "
        "model: the wire carries K and V rows, not a recurrent "
        "state and latent rows")

    def __init__(self, cfg: model.LingHybridConfig, *, batch_size: int,
                 max_len: int, paged: bool, block_size: int, num_blocks: int,
                 prefix_cache: bool, quantize: Optional[str],
                 kv_quantize: Optional[str], mesh: Optional[Any],
                 sharding_policy: Optional[Any], sample: Callable):
        """What ``serving/dense.py`` ``DensePrograms`` takes, of which this
        model is served with the paged pool alone."""
        for refused, needs in (
            (not paged, "paged=False: the MLA layers' latent rows live "
             "in the paged pool, a dense latent cache is not written"),
            (prefix_cache, "prefix_cache: a cached block would need a "
             "snapshot of the recurrent state at its boundary"),
            (kv_quantize, "kv_quantize: latent pages would need scales "
             "and an absorbed product over quantized rows"),
            (quantize, "quantize: the grouped expert product would need "
             "int8 forms of the expert stacks"),
            (mesh is not None, "a mesh: it would need the expert "
             "exchange and sharding rules for the recurrent state"),
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
        cfg, b = self.cfg, self.batch_size
        pool = jnp.zeros((cfg.mla_layers, self.num_blocks, self.block_size,
                          cfg.latent_lanes), cfg.dtype)
        rec = {
            "state": jnp.zeros((cfg.kda_layers, b, cfg.num_heads,
                                cfg.head_dim, cfg.head_dim), jnp.float32),
            "tail": jnp.zeros((cfg.kda_layers, b, cfg.conv_kernel - 1,
                               3 * cfg.kda_dim), cfg.dtype),
        }
        return pool, rec

    def recurrent_state_bytes(self) -> int:
        """Bytes of ``rec``: the state the slots hold whatever their
        lengths (the ``recurrent_state_bytes`` gauge)."""
        return self.cfg.recurrent_state_bytes(self.batch_size)

    def kv_geometry(self) -> tuple:
        """(cache layers, bytes one token holds over them): a latent row
        in each MLA layer."""
        cfg = self.cfg
        return cfg.mla_layers, (cfg.mla_layers * cfg.latent_lanes
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
        the slot, whose recurrent state it starts or carries."""
        return pages, jnp.int32(slot_id)

    @staticmethod
    def _put_slot(rec, slot, state, tail):
        return {"state": rec["state"].at[:, slot].set(state),
                "tail": rec["tail"].at[:, slot].set(
                    tail.astype(rec["tail"].dtype))}

    # -- prefill -------------------------------------------------------------
    def prefill_fn(self, bucket: int):
        """A whole prompt into an empty slot: ``fn(params, tokens [bucket],
        length, pool, rec, (block ids [bucket / block_size], slot))``."""
        cfg, bs = self.cfg, self.block_size

        def fn(params, tokens, length, pool, rec, target):
            bids, slot = target
            zero = jax.tree.map(jnp.zeros_like,
                                paged_window.slot_rows(rec, slot))

            def attend(m, rows):
                nonlocal pool
                with jax.named_scope("kv_insert"):
                    pool = pool.at[m, bids].set(
                        rows.reshape(-1, bs, rows.shape[-1]))
                return rows, jnp.arange(bucket)

            logits, state, tail = model.sequence_forward(
                params, cfg, tokens, length, 0, zero["state"], zero["tail"],
                attend)
            return logits, pool, self._put_slot(rec, slot, state, tail)

        return fn

    def chunk_fn(self, cbucket: int):
        """One chunk of a long prompt: ``fn(params, tokens [cbucket],
        chunk_len, prefix_len, pool, rec, (table row, slot))``.  The rows go
        into the slot's pages and the chunk attends the slot's whole span;
        the recurrent state comes from the slot (zeros at ``prefix_len`` 0)
        and goes back to it."""
        cfg, bs, nb = self.cfg, self.block_size, self.num_blocks
        span = self.blocks_per_slot * self.block_size

        def fn(params, tokens, chunk_len, prefix_len, pool, rec, target):
            tables_row, slot = target
            mine = paged_window.slot_rows(rec, slot)
            fresh = prefix_len == 0
            mine = jax.tree.map(
                lambda a: jnp.where(fresh, jnp.zeros_like(a), a), mine)
            blk, off = paged_window.chunk_pages(prefix_len, cbucket,
                                                tables_row, bs, span)

            def attend(m, rows):
                nonlocal pool
                with jax.named_scope("kv_insert"):
                    pool = scatter_rows(
                        pool, paged_window.flat_rows(m, blk, off, nb, bs),
                        rows)
                return (paged_window.slot_span(pool, m, tables_row, nb),
                        jnp.arange(span))

            logits, state, tail = model.sequence_forward(
                params, cfg, tokens, chunk_len, prefix_len, mine["state"],
                mine["tail"], attend)
            return logits, pool, self._put_slot(rec, slot, state, tail)

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
        engine's buffered window (the pool read-only, the window's latent
        rows in a small buffer, one scatter at the end), with the recurrent
        state carried through the steps in place.  Returns what the Llama
        window returns and, last, the window's expert load (float32
        [``model.LOAD_FIELDS``], :func:`model.moe_ffn`) for the telemetry."""
        cfg, b, w, bs = self.cfg, self.batch_size, window, self.block_size
        nbk = kv_blocks or self.blocks_per_slot
        span = nbk * bs
        max_len = self.max_len

        def fn(params, last_token, lengths, active, pool, rec, temps, top_ps,
               top_ks, tables, rng):
            base_len = jnp.minimum(lengths, max_len - 1)
            cache_seen = jnp.arange(span)[None, :] < base_len[:, None]
            # one gather for the whole window: every slot's pages laid end
            # to end, [L_mla, B, span, lanes]
            view = pool[:, tables].reshape(
                cfg.mla_layers, b, span, pool.shape[-1])
            win0 = jnp.zeros((cfg.mla_layers, w, b, pool.shape[-1]),
                             pool.dtype)
            win_j = jnp.arange(w)

            def one_step(carry, inputs):
                last_token, step_lengths, win, state, tail, load = carry
                i, step_rng = inputs
                positions = jnp.minimum(step_lengths, max_len - 1)
                x = params["embed"].astype(cfg.dtype)[last_token]

                def attend(m, q_nope, q_rope, rows, w_ukv):
                    nonlocal win
                    win = win.at[m, i].set(rows)
                    return mla.absorbed(q_nope, q_rope, view[m], cache_seen,
                                        win[m], win_j <= i, w_ukv)

                x, state, tail, step_load = model.decode_step(
                    params, cfg, x, positions, active, state, tail, attend)
                with jax.named_scope("lm_head"):
                    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
                    logits = jnp.matmul(x, params["lm_head"],
                                        preferred_element_type=jnp.float32)
                if sampling:
                    tokens = self._sample(logits, temps, top_ps, top_ks,
                                          step_rng)
                else:
                    with jax.named_scope("sample"):
                        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                new_lengths = jnp.where(active, step_lengths + 1,
                                        step_lengths)
                return (tokens, new_lengths, win, state, tail,
                        load + step_load), tokens

            (last, new_lengths, win, state, tail, load), tokens_all = \
                jax.lax.scan(
                    one_step,
                    (last_token, lengths, win0, rec["state"], rec["tail"],
                     jnp.zeros((model.LOAD_FIELDS,), jnp.float32)),
                    (jnp.arange(w), jax.random.split(rng, w)))

            # the window's rows into each slot's pages (positions base_len +
            # j)
            idx = paged_window.window_rows(cfg.mla_layers, base_len, active,
                                           tables, win_j, bs,
                                           self.num_blocks)
            with jax.named_scope("kv_window_write"):
                pool = scatter_rows(pool, idx, jnp.moveaxis(win, 1, 2))
            return (tokens_all, last, new_lengths, pool,
                    {"state": state, "tail": tail}, load)

        return fn

"""The Nemotron-H decoder (``models/nemotron_h.py``) behind the engine's
seam: a stack whose Mamba-2 layers keep a state-space state and a
convolution tail per slot, whose attention layers keep K and V rows in the
Llama family's paged pool, and whose expert blocks keep nothing.
``serving/dense.py``, ``serving/hybrid.py`` and ``serving/lfm2.py`` answer
the same calls for their families; ``serving/families.py`` picks among them.

The engine's two donated trees of device state are

* ``pool`` = {"k", "v"}, each [attention layers, blocks, block_size,
  Hkv * head_dim]: the paged pool in the form ``serving/dense.py`` stores
  it (kv heads folded into the lanes), over the ATTENTION layers only,
  addressed through the same block tables and the same allocator;
* ``rec`` = {"ssm": [[slots, heads, head_dim, state] float32, ...], "tail":
  [[slots, conv_kernel - 1, conv_dim], ...]}: one array a Mamba layer (not
  one stacked array: a layer's state is 2 MiB a slot, and a decode step
  then rewrites each layer's buffer where it lies, with no slice of a
  larger one to read or to write back).

A slot's state is never reset by a program of its own: the prefill that
admits a request starts from zeros and overwrites the slot, and a prompt's
first chunk (``prefix_len == 0``) starts from zeros instead of reading it;
later chunks read and write their slot.  A decode window leaves slots that
are not ``active`` (free, or mid-chunk) untouched: their step is 0, so
their decay is 1 and their input nothing (``ops/ssd.py``).

The decode window is the engine's buffered one (``serving/dense.py``
documents it): the pool read-only, the window's K and V rows in a buffer
carried through the step scan, the cache half read by the block-table
kernel on a TPU and through one gathered view elsewhere, one scatter at the
end (``serving/paged_window.py`` holds what the families share of it).  The
states ride the same scan as a carry and are donated, so the largest
program holds them once.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from dstack_tpu.models import nemotron_h as model
from dstack_tpu.ops.pool import scatter_rows
from dstack_tpu.serving import paged_window


def _slot(rec, slot):
    """One slot's part of ``rec``."""
    return jax.tree.map(lambda a: a[slot], rec)


def _with_slot(rec, slot, mine):
    """``rec`` with the slot's part replaced by ``mine``."""
    return jax.tree.map(lambda a, m: a.at[slot].set(m.astype(a.dtype)),
                        rec, mine)


class NemotronHPrograms:
    #: why prefill/decode disaggregation is refused
    pd_refusal = (
        "prefill/decode disaggregation is not served for this "
        "model: the wire carries K and V rows of every layer, not "
        "state-space states and convolution tails beside the rows of some")

    def __init__(self, cfg: model.NemotronHConfig, *, batch_size: int,
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
             "snapshot of the state-space states at its boundary (2 MiB a "
             "layer a block)"),
            (kv_quantize, "kv_quantize: the window buffer and the "
             "end-of-window scatter write plain rows, not packed ones "
             "with scales"),
            (quantize, "quantize: the grouped expert product would need "
             "int8 forms of the expert stacks"),
            (mesh is not None, "a mesh: it would need the expert "
             "exchange and sharding rules for the states"),
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
        cfg, b = self.cfg, self.batch_size
        leaf = (cfg.attention_layers, self.num_blocks, self.block_size,
                cfg.kv_lanes)
        pool = {"k": jnp.zeros(leaf, cfg.dtype),
                "v": jnp.zeros(leaf, cfg.dtype)}
        rec = {
            "ssm": [jnp.zeros((b, cfg.mamba_num_heads, cfg.mamba_head_dim,
                               cfg.ssm_state_size), jnp.float32)
                    for _ in range(cfg.mamba_layers)],
            "tail": [jnp.zeros((b, cfg.conv_reach, cfg.conv_dim), cfg.dtype)
                     for _ in range(cfg.mamba_layers)],
        }
        return pool, rec

    def recurrent_state_bytes(self) -> int:
        """Bytes of ``rec``: the states and tails the slots hold whatever
        their lengths (the ``recurrent_state_bytes`` gauge)."""
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
        """A drained window's last output: its expert load, then the
        slot-layer-steps its state-space updates ran."""
        if telemetry is None:
            return
        *load, ssm_steps = counts.tolist()
        telemetry.record_expert_load(*load)
        telemetry.record_ssm_steps(ssm_steps)

    def record_prompt_program(self, telemetry, bucket: int) -> None:
        """A prefill or chunk program of ``bucket`` positions ran: the
        blocks that went through the chunked scan in its Mamba layers."""
        if telemetry is not None:
            chunks = -(-bucket // min(self.cfg.chunk_size, bucket))
            telemetry.record_ssm_scan_chunks(self.cfg.mamba_layers * chunks)

    @staticmethod
    def slot_target(slot_id: int, pages):
        """Where a prefill or chunk program writes: the slot's pages and
        the slot, whose state it starts or carries."""
        return pages, jnp.int32(slot_id)

    # -- prefill -------------------------------------------------------------
    def prefill_fn(self, bucket: int):
        """A whole prompt into an empty slot: ``fn(params, tokens [bucket],
        length, pool, rec, (block ids [bucket / block_size], slot))``."""
        cfg, bs = self.cfg, self.block_size

        def fn(params, tokens, length, pool, rec, target):
            bids, slot = target
            positions = jnp.arange(bucket)[None, :]

            def attend(m, q, k, v):
                nonlocal pool
                with jax.named_scope("kv_insert"):
                    pool = {key: pool[key].at[m, bids].set(
                        rows.reshape(-1, bs, cfg.kv_lanes))
                        for key, rows in (("k", k), ("v", v))}
                return paged_window.masked_attention(
                    q[None], k[None], v[None], positions, positions)[0]

            zeros = jax.tree.map(jnp.zeros_like, _slot(rec, slot))
            logits, mine = model.sequence_forward(
                params, cfg, tokens, length, zeros, attend)
            return logits, pool, _with_slot(rec, slot, mine)

        return fn

    def chunk_fn(self, cbucket: int):
        """One chunk of a long prompt: ``fn(params, tokens [cbucket],
        chunk_len, prefix_len, pool, rec, (table row, slot))``.  The K and V
        rows go into the slot's pages and the chunk attends the slot's whole
        span; the state and the tail come from the slot (zeros at
        ``prefix_len`` 0) and go back to it."""
        cfg, bs, nb = self.cfg, self.block_size, self.num_blocks
        span = self.blocks_per_slot * self.block_size
        heads = (span, cfg.num_key_value_heads, cfg.head_dim)

        def fn(params, tokens, chunk_len, prefix_len, pool, rec, target):
            tables_row, slot = target
            mine = jax.tree.map(
                lambda a: jnp.where(prefix_len == 0, jnp.zeros_like(a), a),
                _slot(rec, slot))
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
                params, cfg, tokens, chunk_len, mine, attend)
            return logits, pool, _with_slot(rec, slot, mine)

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
        states and tails carried through the steps in place.  Returns what
        the Llama window returns and, last, the window's counts for the
        telemetry: its expert load (float32 [``model.LOAD_FIELDS``],
        :func:`model.moe_block`) and the slot-layer-steps of its
        state-space updates (live slots x Mamba layers a step)."""
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
                last_token, win_k, win_v, rec, load = carry
                i, step_rng = inputs
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
                    params, cfg, x, active, rec, attend)
                logits = model.output_logits(params, cfg, x)
                if sampling:
                    tokens = self._sample(logits, temps, top_ps, top_ks,
                                          step_rng)
                else:
                    with jax.named_scope("sample"):
                        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (tokens, win_k, win_v, rec, load + step_load), tokens

            (last, win_k, win_v, rec, load), tokens_all = jax.lax.scan(
                one_step,
                (last_token, win0, win0, rec,
                 jnp.zeros((model.LOAD_FIELDS,), jnp.float32)),
                (jnp.arange(w), jax.random.split(rng, w)))
            new_lengths = jnp.where(active, lengths + w, lengths)
            ssm_steps = (active.sum().astype(jnp.float32)
                         * (w * cfg.mamba_layers))

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
                    jnp.concatenate([load, ssm_steps[None]]))

        return fn

"""A looped decoder (Ouro's LoopLM): the Llama block with sandwich norms,
its whole stack run ``ut_steps`` times over the SAME weights, a shared norm
at the end of every pass and an exit gate that picks the pass whose state
the head reads.

    h = E[token]
    for t in 0..T-1:                    # the same L layers' weights every pass
      for l in 0..L-1:
        a = Attn_l(RMSNorm(h; attn_norm_l))       # keys/values of (t, l) are
        h = h + RMSNorm(a; attn_out_norm_l)       # cache layer t*L + l
        m = SwiGLU_l(RMSNorm(h; mlp_norm_l))
        h = h + RMSNorm(m; mlp_out_norm_l)
      h = RMSNorm(h; final_norm)        # pass t's output s_t AND pass t+1's input
      lam_t = sigmoid(w . s_t + b)      # params["exit_gate"]
    p_t = lam_t * prod_{j<t}(1 - lam_j) for t < T-1; p_{T-1} = the remainder
    exit = first t with sum_{j<=t} p_j >= early_exit_threshold, else T-1
    logits = lm_head(s_exit)

Every pass attends its own keys and values, so a token holds ``cache_layers``
= T x L layers of K/V over L layers of weights, and every pass runs for
every token whatever the gate says (later tokens need its K/V): the gate
only selects.  The serving programs are the Llama family's
(``serving/dense.py``), which read ``ut_steps`` and ``cache_layers`` from the
config and the two extra norms from the layers' weights; this module holds
what is the model's own: the config, the weights, the gate.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dstack_tpu.models import llama
from dstack_tpu.models.llama import LlamaConfig, Params, ShardingPolicy


@dataclasses.dataclass(frozen=True)
class OuroConfig(LlamaConfig):
    #: passes over the layer stack (the published ``total_ut_steps``)
    ut_steps: int = 4
    #: the exit gate's cumulative probability at which a token's state is
    #: taken, in (0, 1]; 1.0 (as published) reads the last pass
    early_exit_threshold: float = 1.0
    #: RMSNorm on each branch's output too (``attn_out_norm``,
    #: ``mlp_out_norm``); False leaves the plain pre-norm block
    sandwich_norm: bool = True

    def __post_init__(self):
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps must be >= 1, got {self.ut_steps}")
        if not 0.0 < self.early_exit_threshold <= 1.0:
            raise ValueError("early_exit_threshold must lie in (0, 1], got "
                             f"{self.early_exit_threshold}")

    @classmethod
    def ouro_2_6b(cls, **kw) -> "OuroConfig":
        """ByteDance/Ouro-2.6B as published: 48 layers run 4 times."""
        return cls(
            vocab_size=49_152, hidden_size=2048, intermediate_size=5632,
            num_layers=48, num_heads=16, num_kv_heads=16, head_dim=128,
            rope_theta=1e6, rms_eps=1e-6, max_seq_len=65_536, **kw)

    @classmethod
    def tiny(cls, **kw) -> "OuroConfig":
        """Test/dry-run config: 3 layers run 3 times."""
        return cls(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=3, num_heads=4, num_kv_heads=4, head_dim=16,
            rope_theta=1e4, rms_eps=1e-6, max_seq_len=256, ut_steps=3, **kw)

    def num_params(self) -> int:
        norms = 2 * self.hidden_size * self.num_layers * self.sandwich_norm
        return super().num_params() + norms + self.hidden_size + 1


def init_params(rng: jax.Array, cfg: OuroConfig) -> Params:
    """The Llama tree plus the two branch-output norms a layer (ones) and
    the exit gate (``hidden -> 1``, bias 0)."""
    params = llama.init_params(rng, cfg)
    d = cfg.hidden_size
    if cfg.sandwich_norm:
        ones = jnp.ones((cfg.num_layers, d), dtype=cfg.dtype)
        params["layers"].update(attn_out_norm=ones, mlp_out_norm=ones)
    params["exit_gate"] = {
        "w": (jax.random.normal(jax.random.fold_in(rng, 98), (d,),
                                dtype=jnp.float32) * d ** -0.5
              ).astype(cfg.dtype),
        "b": jnp.zeros((1,), dtype=cfg.dtype),
    }
    return params


def param_specs(cfg: OuroConfig,
                policy: ShardingPolicy = ShardingPolicy()) -> Params:
    """PartitionSpec pytree matching :func:`init_params`: the Llama specs;
    the extra norms and the gate are replicated."""
    specs = llama.param_specs(cfg, policy)
    if cfg.sandwich_norm:
        norm = P(policy.stage_axis, None)
        specs["layers"].update(attn_out_norm=norm, mlp_out_norm=norm)
    specs["exit_gate"] = {"w": P(None), "b": P(None)}
    return specs


@jax.named_scope("exit_gate")
def exit_select(params: Params, cfg: OuroConfig, states):
    """The state the head reads and the pass it came from.

    ``states`` [T, ..., D]: every pass's normed output.  Returns
    (``states[exit]`` [..., D], ``exit`` [...] int32), the exit chosen per
    leading position by the gate's cumulative probability (float32)."""
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(
        jnp.einsum("t...d,d->t...", states.astype(jnp.float32),
                   gate["w"].astype(jnp.float32))
        + gate["b"].astype(jnp.float32))
    # survive[t] = prod_{j<t} (1 - lam_j): nobody left before pass t
    survive = jnp.concatenate(
        [jnp.ones_like(lam[:1]), jnp.cumprod(1.0 - lam[:-1], axis=0)])
    p = jnp.concatenate([lam[:-1] * survive[:-1], survive[-1:]])
    reached = jnp.cumsum(p, axis=0) >= cfg.early_exit_threshold
    exit_step = jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0),
                          cfg.ut_steps - 1).astype(jnp.int32)
    picked = jnp.take_along_axis(states, exit_step[None, ..., None], axis=0)
    return picked[0], exit_step

"""Parameters, operations and bytes of the looped decoder that
``ouro_looped.py`` computes, counted from its shapes: the numerators of
``mfu.ouro`` and ``decode_bandwidth_share.ouro``.  Nothing here is measured.
``s`` is the configuration's ``Sizes``, ``passes`` its ``total_ut_steps``
(``ouro_looped._loop``): the layers' matrices and the attention count once a
pass, the head once, the gate once a pass; a token's K/V over ``passes`` x
layers cache layers."""

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_matrices(s) -> int:
    """Parameters of one layer that every token is multiplied by."""
    attn = s.hidden * s.q_dim + 2 * s.hidden * s.kv_dim + s.q_dim * s.hidden
    return attn + 3 * s.hidden * s.ffn


def num_params(s) -> int:
    """Embedding and untied head, the layers with their four norms, the
    shared final norm, the exit gate (hidden -> 1 with bias)."""
    return (2 * s.vocab * s.hidden
            + s.layers * (layer_matrices(s) + 4 * s.hidden)
            + s.hidden + s.hidden + 1)


def body_flops(s, passes: int) -> float:
    """One token through every pass's layers and gate, attention over the
    context and the head apart: 2 per multiply-add."""
    return 2.0 * passes * (s.layers * layer_matrices(s) + s.hidden)


def head_flops(s) -> float:
    return 2.0 * s.vocab * s.hidden


def attention_flops(s, passes: int, context: float) -> float:
    """QK^T and PV for one token over ``context`` tokens, in every layer of
    every pass."""
    return 4.0 * passes * s.layers * s.q_dim * context


def prefill_flops(s, passes: int, prompt: int) -> float:
    """A prompt's tokens at causal contexts 1..prompt, the head at the
    last."""
    return (prompt * body_flops(s, passes) + head_flops(s)
            + attention_flops(s, passes, prompt * (prompt + 1) / 2))


def decode_token_flops(s, passes: int, context: float) -> float:
    return (body_flops(s, passes) + head_flops(s)
            + attention_flops(s, passes, context))


def kv_bytes_per_token(s, passes: int) -> int:
    """Keys and values one cached token holds over all cache layers."""
    return 2 * passes * s.layers * s.kv_dim * BYTES[s.dtype]


def decode_step_bytes(s, passes: int, live_tokens: float) -> float:
    """Bytes one decode step has to move: the layers' matrices once a PASS
    (at a handful of slots nothing of 4.9 GB stays on the chip between
    passes), the head once, and the keys and values of the live tokens over
    every cache layer.  Norms, the gate, embedding rows, activations and
    the step's own new rows are left out: a share from this errs low."""
    w = BYTES[s.dtype]
    weights = passes * s.layers * layer_matrices(s) + s.vocab * s.hidden
    return w * weights + live_tokens * kv_bytes_per_token(s, passes)

"""Operations and bytes the model needs, as functions of its sizes and of
the lengths processed: the numerators of ``mfu`` and of the rooflines.
Nothing here is measured; everything is counted from shapes."""

from benchmarks.harness.sizes import Sizes

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_params(s: Sizes) -> int:
    attn = s.hidden * s.q_dim + 2 * s.hidden * s.kv_dim + s.q_dim * s.hidden
    return attn + 3 * s.hidden * s.ffn + 2 * s.hidden


def num_params(s: Sizes) -> int:
    embed = s.vocab * s.hidden
    head = 0 if s.tied else embed
    return embed + head + s.layers * layer_params(s) + s.hidden


def matmul_params(s: Sizes) -> int:
    """Parameters every token is multiplied by: the layers' matrices and the
    output head (tied or not); the embedding is a row lookup."""
    per_layer = layer_params(s) - 2 * s.hidden
    return s.layers * per_layer + s.vocab * s.hidden


def token_flops(s: Sizes, context: float) -> float:
    """FLOPs to process one token that attends ``context`` earlier tokens
    (itself included): 2 per multiply-add through the matrices, and QK^T
    plus PV over the context in every layer."""
    attn = 4.0 * s.layers * s.q_dim * context
    return 2.0 * matmul_params(s) + attn


def sequence_flops(s: Sizes, prompt: int, output: int) -> float:
    """FLOPs one request needs: its prompt's tokens at causal contexts
    1..prompt (the head only at the last), and ``output - 1`` decode steps
    (the first output token comes from the prompt's last position)."""
    body = 2.0 * (matmul_params(s) - s.vocab * s.hidden)
    head = 2.0 * s.vocab * s.hidden
    attn_unit = 4.0 * s.layers * s.q_dim
    prefill = prompt * body + head + attn_unit * prompt * (prompt + 1) / 2
    steps = max(output - 1, 0)
    ctx_sum = steps * prompt + steps * (steps + 1) / 2
    decode = steps * (body + head) + attn_unit * ctx_sum
    return prefill + decode


def kv_bytes_per_token(s: Sizes) -> int:
    """Bytes of keys and values one cached token holds over all layers."""
    return 2 * s.layers * s.kv_dim * BYTES[s.dtype]


def weight_bytes(s: Sizes) -> int:
    return num_params(s) * BYTES[s.dtype]


def paged_attention_call(s: Sizes, live_kv_tokens: float, slots: int) -> dict:
    """One layer's decode attention over the cache for one step: reads the
    live keys and values of that layer and the queries, writes the outputs;
    QK^T and PV for every query head."""
    kv = 2 * live_kv_tokens * s.kv_dim * BYTES[s.dtype]
    qo = 2 * slots * s.q_dim * BYTES[s.dtype]
    return {"bytes": kv + qo, "flops": 4.0 * s.q_dim * live_kv_tokens}

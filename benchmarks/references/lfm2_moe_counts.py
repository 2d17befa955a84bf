"""Parameters, operations and bytes of the mixed decoder that ``lfm2_moe.py``
computes, counted from its shapes: the numerators of ``mfu.lfm2``,
``decode_bandwidth_share.lfm2`` and ``paged_attn_roofline.lfm2``.  Nothing
here is measured.  ``shape`` is ``lfm2_moe._shape(sizes)``: the
configuration file's numbers under short names.  Everything counts what is
computed HERE: the held experts (all 64 in the benchmark's configuration)."""

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def q_dim(s: dict) -> int:
    return s["heads"] * s["hd"]


def kv_dim(s: dict) -> int:
    return s["kv_heads"] * s["hd"]


def mixer_matrices(s: dict, kind: str) -> int:
    """Parameters of a mixer that every token is multiplied by."""
    d = s["d"]
    if kind == "conv":
        return 3 * d * d + d * d
    return 2 * d * q_dim(s) + 2 * d * kv_dim(s)


def mixer_small(s: dict, kind: str) -> int:
    """A mixer's vectors: the convolution's taps, the per-head norms."""
    return s["conv"] * s["d"] if kind == "conv" else 2 * s["hd"]


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["f_expert"]


def ffn_dense_matrices(s: dict, layer: int) -> int:
    """Feed-forward parameters EVERY token meets in ``layer``: the dense
    MLP, or the router (routed experts apart; there is no shared one)."""
    if layer < s["dense"]:
        return 3 * s["d"] * s["ffn"]
    return s["d"] * s["routed"]


def num_params(s: dict) -> int:
    """The embedding once (it is the head too), the final norm, the layers."""
    total = s["vocab"] * s["d"] + s["d"]
    for i, kind in enumerate(s["types"]):
        total += (mixer_matrices(s, kind) + mixer_small(s, kind)
                  + ffn_dense_matrices(s, i) + 2 * s["d"])
        if i >= s["dense"]:
            total += s["routed"] + s["held"] * expert_params(s)
    return total


def expert_layers(s: dict) -> int:
    return len(s["types"]) - s["dense"]


def expected_held_pairs(s: dict) -> float:
    """Pairs a token sends to held experts in one expert layer if routing
    is even: what the prefill programs, which return no count, are
    charged."""
    return s["topk"] * s["held"] / s["routed"]


def pair_flops(s: dict) -> float:
    """One token through one routed expert."""
    return 2.0 * expert_params(s)


def body_flops(s: dict) -> float:
    """One token through everything but the routed experts, the attention
    over the context and the head: 2 per multiply-add of the matrices, and
    the convolution's taps and two gates."""
    total = 0.0
    for i, kind in enumerate(s["types"]):
        total += 2.0 * (mixer_matrices(s, kind) + ffn_dense_matrices(s, i))
        if kind == "conv":
            total += (2.0 * s["conv"] + 2.0) * s["d"]
    return total


def head_flops(s: dict) -> float:
    return 2.0 * s["vocab"] * s["d"]


def attention_flops(s: dict, context: float) -> float:
    """The attention layers' scores and values for one token over
    ``context`` tokens: QK^T and PV for every query head."""
    return s["types"].count("full_attention") * 4.0 * q_dim(s) * context


def prefill_flops(s: dict, prompt: int) -> float:
    """A prompt's tokens at causal contexts 1..prompt, the head at the
    last, the routed experts at the even-routing expectation."""
    experts = expert_layers(s) * expected_held_pairs(s) * pair_flops(s)
    return (prompt * (body_flops(s) + experts) + head_flops(s)
            + attention_flops(s, prompt * (prompt + 1) / 2))


def decode_token_flops(s: dict, context: float) -> float:
    """A decode step's token, WITHOUT its routed experts (the program
    counts those pairs: ``moe_pairs_total{where=held}``)."""
    return body_flops(s) + head_flops(s) + attention_flops(s, context)


def state_bytes_per_slot(s: dict) -> int:
    """The convolution layers' tails of one slot, in the served type."""
    return (s["types"].count("conv") * (s["conv"] - 1) * s["d"]
            * BYTES[s["dtype"]])


def kv_bytes_per_token(s: dict) -> int:
    """Bytes of keys and values one cached token holds over the ATTENTION
    layers."""
    return (2 * s["types"].count("full_attention") * kv_dim(s)
            * BYTES[s["dtype"]])


def decode_step_bytes(s: dict, live_slots: float, live_tokens: float,
                      experts_touched: float) -> float:
    """Bytes one decode step has to move: every matrix outside the routed
    experts and the head (the embedding, transposed) once, the weights of
    the ``experts_touched`` (held experts with at least one pair, summed
    over the expert layers), the tails of the live slots read and written,
    the live tokens' K and V rows read."""
    w = BYTES[s["dtype"]]
    always = sum(mixer_matrices(s, kind) + ffn_dense_matrices(s, i)
                 for i, kind in enumerate(s["types"])) + s["vocab"] * s["d"]
    return (w * (always + experts_touched * expert_params(s))
            + 2.0 * live_slots * state_bytes_per_slot(s)
            + live_tokens * kv_bytes_per_token(s))


def paged_attention_call(s: dict, live_kv_tokens: float, slots: int) -> dict:
    """One attention layer's decode attention over the cache for one step
    (one call of the paged kernel; a step makes one an attention layer):
    reads the live keys and values of that layer and the queries, writes
    the outputs; QK^T and PV for every query head."""
    w = BYTES[s["dtype"]]
    kv = 2 * live_kv_tokens * kv_dim(s) * w
    qo = 2 * slots * q_dim(s) * w
    return {"bytes": kv + qo, "flops": 4.0 * q_dim(s) * live_kv_tokens}

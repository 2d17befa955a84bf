"""Parameters, operations and bytes of the state-space / expert / attention
decoder that ``nemotron_h.py`` computes, counted from its shapes: the
numerators of ``mfu.nemotron``, ``decode_bandwidth_share.nemotron``,
``ssm_step_roofline.nemotron``, ``moe_experts_roofline.nemotron`` and
``paged_attn_roofline.nemotron``.  Nothing here is measured.  ``shape`` is
``nemotron_h._shape(sizes)``: the configuration file's numbers under short
names.  Everything counts what is computed HERE: the held experts (64 of 128
in the benchmark's configuration) and the vocabulary rows held."""

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def q_dim(s: dict) -> int:
    return s["heads"] * s["hd"]


def kv_dim(s: dict) -> int:
    return s["kv_heads"] * s["hd"]


def d_inner(s: dict) -> int:
    return s["m_heads"] * s["m_hd"]


def conv_channels(s: dict) -> int:
    return d_inner(s) + 2 * s["groups"] * s["state"]


def state_elements(s: dict) -> int:
    """One slot's state-space state in one Mamba layer: heads x head_dim x
    state, float32 whatever the served type."""
    return s["m_heads"] * s["m_hd"] * s["state"]


def expert_params(s: dict) -> int:
    """One routed expert: two matrices (up, down), no gate."""
    return 2 * s["d"] * s["f_expert"]


def block_matrices(s: dict, kind: str) -> int:
    """Parameters of a block that EVERY token is multiplied by (routed
    experts apart)."""
    d = s["d"]
    if kind == "mamba":
        return (d * (d_inner(s) + conv_channels(s) + s["m_heads"])
                + d_inner(s) * d)
    if kind == "experts":
        return d * s["routed"] + 2 * d * s["f_shared"]
    return 2 * d * q_dim(s) + 2 * d * kv_dim(s)


def block_small(s: dict, kind: str) -> int:
    """A block's vectors: its norm; the convolution's taps and bias, the
    heads' ``dt_bias``, ``A_log`` and ``D``, the gated norm's weight; the
    router's selection bias."""
    if kind == "mamba":
        return (s["d"] + (s["conv"] + 1) * conv_channels(s)
                + 3 * s["m_heads"] + d_inner(s))
    return s["d"] + (s["routed"] if kind == "experts" else 0)


def num_params(s: dict) -> int:
    """The embedding and the untied head, the final norm, the blocks."""
    total = 2 * s["vocab"] * s["d"] + s["d"]
    for kind in s["types"]:
        total += block_matrices(s, kind) + block_small(s, kind)
        if kind == "experts":
            total += s["held"] * expert_params(s)
    return total


def expected_held_pairs(s: dict) -> float:
    """Pairs a token sends to held experts in one expert block if routing
    is even: what the prefill programs, which return no count, are
    charged."""
    return s["topk"] * s["held"] / s["routed"]


def pair_flops(s: dict) -> float:
    """One token through one routed expert."""
    return 2.0 * expert_params(s)


def body_flops(s: dict) -> float:
    """One token through everything but the routed experts, the attention
    over the context and the head: 2 per multiply-add of the matrices; in a
    Mamba layer the convolution's taps and the recurrence as it is defined
    (decay, input and sum: 3 an element of the state; the read by C: 2)."""
    total = 0.0
    for kind in s["types"]:
        total += 2.0 * block_matrices(s, kind)
        if kind == "mamba":
            total += (2.0 * s["conv"] * conv_channels(s)
                      + 5.0 * state_elements(s))
    return total


def head_flops(s: dict) -> float:
    return 2.0 * s["vocab"] * s["d"]


def attention_flops(s: dict, context: float) -> float:
    """The attention layers' scores and values for one token over
    ``context`` tokens: QK^T and PV for every query head."""
    return s["types"].count("attention") * 4.0 * q_dim(s) * context


def prefill_flops(s: dict, prompt: int) -> float:
    """A prompt's tokens at causal contexts 1..prompt, the head at the
    last, the routed experts at the even-routing expectation."""
    experts = (s["types"].count("experts") * expected_held_pairs(s)
               * pair_flops(s))
    return (prompt * (body_flops(s) + experts) + head_flops(s)
            + attention_flops(s, prompt * (prompt + 1) / 2))


def decode_token_flops(s: dict, context: float) -> float:
    """A decode step's token, WITHOUT its routed experts (the program
    counts those pairs: ``moe_pairs_total{where=held}``)."""
    return body_flops(s) + head_flops(s) + attention_flops(s, context)


def state_bytes_per_slot(s: dict) -> int:
    """The Mamba layers' memory of one slot: the state in float32 and the
    convolution's tail in the served type."""
    layer = (4 * state_elements(s)
             + (s["conv"] - 1) * conv_channels(s) * BYTES[s["dtype"]])
    return s["types"].count("mamba") * layer


def ssm_step_bytes(s: dict, slot_layer_steps: float) -> float:
    """What the decode update of ``slot_layer_steps`` (live slots x Mamba
    layers x steps) cannot avoid moving: each state read once and written
    once, and beside it the step's operands and result (x and y [heads,
    head_dim], B and C [groups, state] in the served type; dt [heads] in
    float32)."""
    w = BYTES[s["dtype"]]
    small = (2 * d_inner(s) + 2 * s["groups"] * s["state"]) * w \
        + 4 * s["m_heads"]
    return slot_layer_steps * (2 * 4 * state_elements(s) + small)


def kv_bytes_per_token(s: dict) -> int:
    """Bytes of keys and values one cached token holds over the ATTENTION
    layers."""
    return 2 * s["types"].count("attention") * kv_dim(s) * BYTES[s["dtype"]]


def expert_matrices_bytes(s: dict) -> int:
    """The two matrices of one routed expert (up, down), as served."""
    return expert_params(s) * BYTES[s["dtype"]]


def touched_experts_bytes(s: dict, experts_touched: float) -> float:
    """What the grouped products of a decode step cannot avoid reading: the
    matrices of the held experts that got a pair (``experts_touched``:
    summed over the step's expert blocks)."""
    return experts_touched * expert_matrices_bytes(s)


def decode_step_bytes(s: dict, live_slots: float, live_tokens: float,
                      experts_touched: float) -> float:
    """Bytes one decode step has to move: every matrix outside the routed
    experts and the head once (the embedding is a row lookup), the matrices
    of the ``experts_touched`` (held experts with at least one pair, summed
    over the expert blocks), the states and tails of the live slots read
    and written, the live tokens' K and V rows read."""
    w = BYTES[s["dtype"]]
    always = (sum(block_matrices(s, kind) for kind in s["types"])
              + s["vocab"] * s["d"])
    return (w * always + touched_experts_bytes(s, experts_touched)
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

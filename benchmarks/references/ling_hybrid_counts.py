"""Parameters, operations and bytes of the hybrid decoder that
``ling_hybrid.py`` computes, counted from its shapes: the numerators of
``mfu.reason`` and ``decode_bandwidth_share.reason``.  Nothing here is
measured.  ``shape`` is ``ling_hybrid._shape(sizes)``: the configuration
file's numbers under short names.  Everything counts what is computed HERE:
the held experts, the slice of the vocabulary."""

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def mixer_matrices(s: dict, kind: str) -> int:
    """Parameters of a mixer that every token is multiplied by."""
    d, h = s["d"], s["heads"]
    if kind == "kda":
        c = h * s["hd"]
        return 3 * d * c + d * c + 2 * d * h + c * d
    return (d * h * (s["dn"] + s["dr"]) + d * (s["r"] + s["dr"])
            + s["r"] * h * (s["dn"] + s["dv"]) + h * s["dv"] * d)


def mixer_small(s: dict, kind: str) -> int:
    """A mixer's vectors: convolution taps, gate constants, norms."""
    if kind == "kda":
        c = s["heads"] * s["hd"]
        return s["conv"] * 3 * c + s["heads"] + c + s["hd"]
    return s["r"]


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["f_expert"]


def ffn_dense_matrices(s: dict, layer: int) -> int:
    """Feed-forward parameters EVERY token meets in ``layer``: the dense
    MLP, or the router and the shared expert (routed experts apart)."""
    if layer < s["dense"]:
        return 3 * s["d"] * s["ffn"]
    return s["d"] * s["routed"] + 3 * s["d"] * s["f_shared"]


def num_params(s: dict) -> int:
    total = 2 * s["vocab"] * s["d"] + s["d"]
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
    the KDA state's three products (decayed state by key, the rank-one
    update, state by query)."""
    total = 0.0
    for i, kind in enumerate(s["types"]):
        total += 2.0 * (mixer_matrices(s, kind) + ffn_dense_matrices(s, i))
        if kind == "kda":
            total += 6.0 * s["heads"] * s["hd"] * s["hd"]
    return total


def head_flops(s: dict) -> float:
    return 2.0 * s["vocab"] * s["d"]


def attention_flops(s: dict, context: float) -> float:
    """The MLA layers' scores and values for one token over ``context``
    tokens, in the expanded form (the absorbed form the decode step runs
    does more: 2 H (2 r + d_r) a token of context)."""
    per = 2.0 * s["heads"] * (s["dn"] + s["dr"] + s["dv"])
    return s["types"].count("mla") * per * context


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
    """The KDA layers' recurrent state of one slot: float32 matrices and
    the convolution's tail in the served type."""
    c = s["heads"] * s["hd"]
    per_layer = (4 * s["heads"] * s["hd"] * s["hd"]
                 + (s["conv"] - 1) * 3 * c * BYTES[s["dtype"]])
    return s["types"].count("kda") * per_layer


def decode_step_bytes(s: dict, live_slots: float, live_tokens: float,
                      experts_touched: float) -> float:
    """Bytes one decode step has to move: every matrix outside the routed
    experts and the head once, the weights of the ``experts_touched`` (held
    experts with at least one pair, summed over the expert layers), the
    recurrent state of the live slots read and written, the live tokens'
    latent rows read."""
    w = BYTES[s["dtype"]]
    always = sum(mixer_matrices(s, kind) + ffn_dense_matrices(s, i)
                 for i, kind in enumerate(s["types"])) + s["vocab"] * s["d"]
    latent = s["types"].count("mla") * (s["r"] + s["dr"]) * w
    return (w * (always + experts_touched * expert_params(s))
            + 2.0 * live_slots * state_bytes_per_slot(s)
            + live_tokens * latent)

"""Bytes of the routed experts' grouped products, counted from the shapes:
the numerator of ``moe_experts_roofline``.  Nothing here is measured.
``shape`` is a reference's ``_shape(sizes)`` (``lfm2_moe``, ``ling_hybrid``:
both name the hidden size ``d`` and an expert's width ``f_expert``)."""

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def expert_matrices_bytes(shape: dict) -> int:
    """The three matrices of one routed expert (gate, up, down), as
    served."""
    return 3 * shape["d"] * shape["f_expert"] * BYTES[shape["dtype"]]


def touched_experts_bytes(shape: dict, experts_touched: float) -> float:
    """What the grouped products of a decode step cannot avoid reading: the
    matrices of the held experts that got a pair (``experts_touched``:
    summed over the step's expert layers).  The rows, the results and the
    group bookkeeping are left out, so a share of the bandwidth computed
    from it errs low and cannot pass 100% while every touched matrix is
    read."""
    return experts_touched * expert_matrices_bytes(shape)

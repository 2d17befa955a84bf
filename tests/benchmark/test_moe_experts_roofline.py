"""``moe_experts_roofline`` on hand-made reduced traces: the reader finds the
routed experts' grouped products whatever implements them (XLA's
``ragged-dot`` custom calls, the repo's ``grouped_matmul`` kernel), counts
those inside the decode-window programs alone, leaves the paged kernel's
calls out, stays at or under 100 while the products take at least the time
their touched matrices need, and reads ``None`` without such events."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.harness.cell import Files
from benchmarks.harness.sizes import load_config, sizes_of
from benchmarks.references import lfm2_moe, ling_hybrid
from benchmarks.references import moe_experts_counts as counts

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
MARK = 'custom_call_target="tpu_custom_call"'
#: the k-th grouped product of a step: an instruction of its own, as in a
#: compiled program
RAGGED = ("%ragged-dot-none.{k} = bf16[1024,1536]{{1,0}} custom-call(...), "
          + MARK)
OURS = ("%grouped_matmul.{k} = bf16[1024,1536]{{1,0}} custom-call(...), "
        + MARK)
PAGED = f"%paged_decode_attention.18 = (f32[256,32,64]) custom-call(...), {MARK}"
#: (cell's metric, configuration, reference, expert layers, held experts)
CELLS = {
    "lfm2": ("moe_experts_roofline.lfm2.reason", "lfm2-24b-a2b-9l", lfm2_moe,
             8, 64),
    "ling": ("moe_experts_roofline.reason", "ling-3.0-flash-vl-7l-ep4",
             ling_hybrid, 6, 128),
}


def _trace(product, step_ns, product_ns, layers, steps=4, calls=2):
    """One chip: a whole ``steps``-step decode window, each step ``calls``
    grouped products an expert layer of ``product_ns`` each, two paged-kernel
    calls and other work; then a prefill program that runs grouped products
    of its own."""
    ops = []
    for i in range(steps):
        t = i * step_ns
        for k in range(2):
            ops.append((PAGED, t + k * 1000, 500))
        for k in range(layers * calls):
            ops.append((product.format(k=k), t + 5000 + k * (product_ns + 10),
                        product_ns))
        ops.append(("%fusion.1 = bf16[8]{0} fusion(...)", t + step_ns - 2000,
                    1000))
    end = steps * step_ns
    for k in range(layers * calls):             # a prefill's: not counted
        ops.append((product.format(k=100 + k), end + 3000 + k * (product_ns + 10),
                    product_ns))
    ops.append(("%copy.1 = s32[1]{0} copy(...)", -5000, 1000))
    modules = [(f"jit_decode_w{steps}_s0_kb64(5)", -1000, end + 2000),
               ("jit_prefill_paged_b256(7)", end + 2500,
                layers * calls * (product_ns + 10) + 1000)]
    return {"devices": [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": modules, "XLA Ops": sorted(ops, key=lambda e: e[1])}}],
        "host": {}}


def _run(trace, config, touched_a_step, steps=4.0):
    sizes = sizes_of(load_config(ROOT / f"benchmarks/configs/{config}.json"))
    names = ("dstack_serving_moe_experts_touched_sum",
             "dstack_serving_decode_steps_total")
    return SimpleNamespace(
        trace=trace, sizes=sizes, chips=1,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"t0": dict.fromkeys(names, 0.0),
                  "t1": dict(zip(names, (touched_a_step * steps, steps)))})


@pytest.mark.parametrize("product", [RAGGED, OURS], ids=["xla", "kernel"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_reads_the_products_of_the_decode_programs(cell, product):
    metric, config, ref, layers, held = CELLS[cell]
    read = Files(ROOT, SPEC).reader("layer_metrics", metric).read
    shape = ref._shape(sizes_of(load_config(
        ROOT / f"benchmarks/configs/{config}.json")))
    one = counts.expert_matrices_bytes(shape)
    assert one == 3 * shape["d"] * shape["f_expert"] * 2
    # every held expert touched in every layer; the products of a step take
    # twice what their matrices need at the bandwidth: half the roofline
    least_ns = layers * held * one / 819e9 * 1e9
    calls = 3 if product is RAGGED else 2
    product_ns = int(2 * least_ns / (layers * calls))
    step_ns = int(2 * least_ns * 1.25)
    trace = _trace(product, step_ns, product_ns, layers, calls=calls)
    run = _run(trace, config, layers * held)
    got = read(run)
    assert got == pytest.approx(50.0, rel=0.01)
    # the prefill's products and the paged kernel's calls changed nothing:
    # without them it reads the same
    for dev in trace["devices"]:
        dev["lines"]["XLA Ops"] = [
            e for e in dev["lines"]["XLA Ops"]
            if e[0] != PAGED and e[1] < 4 * step_ns]
    assert read(_run(trace, config, layers * held)) == pytest.approx(
        got, rel=1e-3)
    # fewer experts touched, the same time: a lower share
    assert read(_run(trace, config, layers * held / 2)) == pytest.approx(
        got / 2, rel=1e-6)


@pytest.mark.parametrize("cell", list(CELLS))
def test_cannot_pass_100_while_the_products_take_their_floor(cell):
    metric, config, ref, layers, held = CELLS[cell]
    read = Files(ROOT, SPEC).reader("layer_metrics", metric).read
    shape = ref._shape(sizes_of(load_config(
        ROOT / f"benchmarks/configs/{config}.json")))
    least_ns = layers * held * counts.expert_matrices_bytes(shape) / 819e9 * 1e9
    for slower in (1.0, 1.2, 3.0):
        product_ns = int(slower * least_ns / (layers * 2)) + 1
        trace = _trace(OURS, int(slower * least_ns * 1.1), product_ns, layers)
        got = read(_run(trace, config, layers * held))
        assert got <= 100.0
        assert got == pytest.approx(100.0 / slower, rel=0.01)


@pytest.mark.parametrize("cell", list(CELLS))
def test_reads_nothing_where_there_is_nothing_to_read(cell):
    metric, config, _, layers, held = CELLS[cell]
    read = Files(ROOT, SPEC).reader("layer_metrics", metric).read
    touched = layers * held
    assert read(_run(None, config, touched)) is None
    assert read(_run({"devices": [], "host": {}}, config, touched)) is None
    # a decode program without grouped products (a dense decoder's), and
    # grouped products outside every decode program
    bare = _trace(OURS, 1_000_000, 1000, layers)
    for dev in bare["devices"]:
        dev["lines"]["XLA Ops"] = [e for e in dev["lines"]["XLA Ops"]
                                   if not e[0].startswith("%grouped_matmul")
                                   or e[1] >= 4 * 1_000_000]
    assert read(_run(bare, config, touched)) is None
    # no counter (the parent of the PR that brought the counter has it; a
    # program without routed experts does not), no peaks
    full = _trace(RAGGED, 1_000_000, 1000, layers)
    assert read(_run(full, config, 0.0)) is None
    no_peaks = _run(full, config, touched)
    no_peaks.peaks = None
    assert read(no_peaks) is None


def test_the_readers_are_found_by_the_metrics_names():
    files = Files(ROOT, SPEC)
    for metric, *_ in CELLS.values():
        reader = Path(files.reader("layer_metrics", metric).__file__).name
        assert reader == ("moe_experts_roofline.lfm2.py" if ".lfm2." in metric
                          else "moe_experts_roofline.py")
    assert files.find("references/moe_experts_counts.py").is_file()
    # the kernel's name in a trace is the one the program gives it
    from benchmarks.layer_metrics.moe_experts_roofline import PRODUCTS
    from dstack_tpu.ops.grouped_matmul import KERNEL_NAME

    assert "%" + KERNEL_NAME in PRODUCTS

"""Train-step telemetry wrapper: step-time/MFU counters advance across
steps; at most one recompile event for a fixed-shape loop (ISSUE 2
acceptance)."""

import pytest


@pytest.fixture(scope="module")
def setup():
    import jax
    from dstack_tpu.models import llama, train

    cfg = llama.LlamaConfig.tiny()
    opt = train.default_optimizer()
    batch = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size)
    }
    return cfg, opt, batch


def test_train_step_counters_advance(setup):
    import jax
    from dstack_tpu.models import train
    from dstack_tpu.telemetry.training import TrainTelemetry

    cfg, opt, batch = setup
    tel = TrainTelemetry(log_every=0)
    step = train.make_train_step(cfg, opt, telemetry=tel)
    state = train.create_state(jax.random.PRNGKey(0), cfg, opt)
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert tel.steps_total.value == 3
    assert tel.tokens_total.value == 3 * 2 * 16
    # at most one recompile (the initial compile); fixed shapes retrace
    # nothing afterwards
    assert tel.recompiles_total.value <= 1
    # the compile step is excluded from the step-time histogram
    assert tel.step_seconds.count >= 2
    assert tel.tokens_per_sec.value > 0
    # the CPU has no entry in the peak table: no MFU, never the v5e's
    assert tel.peak_flops is None and tel.mfu.value == 0
    assert losses[-1] < losses[0]  # the wrapper does not break training


def test_mfu_uses_the_running_devices_peak(setup, monkeypatch):
    """MFU divides by the peak of the device_kind the step runs on."""
    import jax
    from dstack_tpu.models import train
    from dstack_tpu.telemetry import training

    cfg, opt, batch = setup
    kind = jax.devices()[0].device_kind
    assert training.PEAK_BF16_FLOPS["TPU v5 lite"] == 197e12
    assert kind not in training.PEAK_BF16_FLOPS
    monkeypatch.setitem(training.PEAK_BF16_FLOPS, kind, 1e12)
    tel = training.TrainTelemetry(log_every=0)
    step = train.make_train_step(cfg, opt, telemetry=tel)
    state = train.create_state(jax.random.PRNGKey(0), cfg, opt)
    for _ in range(2):
        state, _ = step(state, batch)
    assert tel.peak_flops == 1e12
    assert 0 < tel.mfu.value < 1


def test_wrapping_a_warm_step_records_no_recompile(setup):
    import jax
    from dstack_tpu.models import train
    from dstack_tpu.telemetry.training import TrainTelemetry

    cfg, opt, batch = setup
    bare = train.make_train_step(cfg, opt)
    state = train.create_state(jax.random.PRNGKey(0), cfg, opt)
    state, m = bare(state, batch)  # compile happens un-instrumented
    jax.block_until_ready(m["loss"])
    tel = TrainTelemetry(log_every=0)
    wrapped = tel.wrap(bare, cfg)
    for _ in range(2):
        state, _ = wrapped(state, batch)
    assert tel.recompiles_total.value == 0
    assert tel.step_seconds.count == 2


def test_train_telemetry_exposition_is_valid(setup):
    import jax
    from dstack_tpu.models import train
    from dstack_tpu.server.telemetry.exposition import parse, render
    from dstack_tpu.telemetry.training import TrainTelemetry

    cfg, opt, batch = setup
    tel = TrainTelemetry(log_every=0)
    step = train.make_train_step(cfg, opt, telemetry=tel)
    state = train.create_state(jax.random.PRNGKey(0), cfg, opt)
    state, _ = step(state, batch)
    text = "\n".join(render(tel.prometheus_samples()))
    names = {s.name for s in parse(text, strict=True)}
    for required in ("dstack_train_steps_total", "dstack_train_tokens_total",
                     "dstack_train_recompiles_total",
                     "dstack_train_step_seconds_bucket", "dstack_train_mfu"):
        assert required in names, required


def test_record_step_direct_entry_point():
    """Callers timing steps themselves (bench tails, eval loops) feed
    record_step directly."""
    from dstack_tpu.telemetry.training import TrainTelemetry

    tel = TrainTelemetry(num_params=1_000_000, peak_flops=1e12, log_every=0)
    tel.record_step(0.5, tokens=1024, recompiled=True)
    tel.record_step(0.1, tokens=1024)
    assert tel.steps_total.value == 2
    assert tel.recompiles_total.value == 1
    assert tel.step_seconds.count == 1  # recompile excluded
    assert tel.mfu.value == pytest.approx(
        6 * 1_000_000 * 1024 / 0.1 / 1e12)

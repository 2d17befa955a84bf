"""speclint (dstack_tpu/analysis/spec) — violating/conforming fixture
pairs for every SP family, pragma suppression, line anchoring, the CLI
``--specs`` surface, mixed DT+SP baselines, and the self-check that keeps
the shipped examples/ tree clean.
"""

from __future__ import annotations

import json
import textwrap
from pathlib import Path

import pytest
import yaml

from dstack_tpu.analysis.spec.driver import (
    analyze_configuration,
    analyze_spec_paths,
    run_spec_rules,
)
from dstack_tpu.analysis.spec.loader import SpecFile, load_spec

REPO_ROOT = Path(__file__).resolve().parents[2]


def lint_yaml(src: str, name: str = "spec.yml"):
    """Findings (pragma-suppressed excluded) for one YAML snippet."""
    spec = spec_of(src, name)
    if spec is None:
        return []
    return [f for f in run_spec_rules(spec) if not spec.is_suppressed(f)]


def spec_of(src: str, name: str = "spec.yml"):
    text = textwrap.dedent(src).lstrip()
    data = yaml.safe_load(text)
    if not isinstance(data, dict) or "type" not in data:
        return None
    from dstack_tpu.core.models.configurations import (
        parse_apply_configuration,
    )

    try:
        conf = parse_apply_configuration(data)
    except ValueError as e:
        return SpecFile(None, name, text, data, parse_error=str(e))
    return SpecFile(None, name, text, data, conf=conf)


def codes(src: str):
    return sorted({f.code for f in lint_yaml(src)})


SERVICE_HEAD = """
type: service
name: svc
port: 8000
model:
  name: m
"""


def service(commands: str, tpu: str = "v5e-8", extra: str = "") -> str:
    return (
        SERVICE_HEAD
        + f"commands:\n  - {commands}\n"
        + f"resources:\n  tpu: {tpu}\n"
        + extra
    )


# -- SP001: configuration must validate -------------------------------------


def test_sp001_invalid_configuration():
    out = lint_yaml("""
    type: service
    name: svc
    port: 8000
    """)
    assert [f.code for f in out] == ["SP001"]
    assert out[0].severity == "error"
    assert "commands" in out[0].message


def test_unknown_type_is_sp001():
    out = lint_yaml("""
    type: spaceship
    name: svc
    """)
    assert [f.code for f in out] == ["SP001"]


def test_non_config_yaml_skipped():
    assert spec_of("repos:\n  - local\n") is None


# -- SP1xx: catalog/topology -------------------------------------------------


def test_sp101_wrong_dimensionality():
    out = lint_yaml("""
    type: fleet
    name: flt
    nodes: 1
    resources:
      tpu:
        generation: v5e
        topology: 4x4x8
    """)
    assert [f.code for f in out] == ["SP101"]
    assert "2D ICI torus" in out[0].message
    # the finding anchors to the topology line, not line 1
    assert out[0].line == 7


def test_sp101_non_standard_layout():
    out = lint_yaml("""
    type: fleet
    name: flt
    nodes: 1
    reservation: r
    resources:
      tpu:
        generation: v5p
        topology: 4x4x3
    """)
    assert [f.code for f in out] == ["SP101"]
    assert "48 chips" in out[0].message


def test_sp101_clean_standard_topology():
    assert codes("""
    type: fleet
    name: flt
    nodes: 1
    reservation: r
    resources:
      tpu:
        generation: v5p
        topology: 4x4x8
    """) == []


def test_sp101_rotated_topology_is_standard():
    # tables store sorted dims; a rotation of a standard layout is fine
    assert codes("""
    type: fleet
    name: flt
    nodes: 1
    reservation: r
    resources:
      tpu:
        generation: v5p
        topology: 8x4x4
    """) == []


def test_sp102_odd_cores_suffix_is_error():
    out = lint_yaml("""
    type: task
    name: tsk
    commands: [python train.py]
    resources:
      tpu: v5p-129
    """)
    assert [f.code for f in out] == ["SP102"]
    assert out[0].severity == "error"
    assert "floor-divides to 64 chips" in out[0].message


def test_sp102_valid_cores_suffix_is_informational():
    out = [f for f in lint_yaml("""
    type: task
    name: tsk
    commands: [python train.py]
    reservation: r
    resources:
      tpu: v5p-256
    """) if f.code == "SP102"]
    assert len(out) == 1 and out[0].severity == "warning"
    assert "128 chips" in out[0].message


def test_sp102_not_raised_for_chips_unit_generations():
    assert codes("""
    type: task
    name: tsk
    commands: [python train.py]
    resources:
      tpu: v5e-8
    """) == []


def test_sp103_ring_fallback_chip_count():
    out = lint_yaml("""
    type: task
    name: tsk
    commands: [python train.py]
    resources:
      tpu:
        generation: v5e
        chips: 6
    """)
    assert [f.code for f in out] == ["SP103"]
    assert out[0].severity == "warning"
    assert "1x6" in out[0].message and "4 or 8" in out[0].message


def test_sp104_large_v5p_without_reservation():
    src = """
    type: fleet
    name: flt
    nodes: 1
    resources:
      tpu:
        generation: v5p
        topology: 4x4x8
    """
    out = lint_yaml(src)
    assert [f.code for f in out] == ["SP104"]
    assert out[0].severity == "warning"
    # with a reservation it is clean
    assert codes(src + "reservation: my-resv\n") == []


def test_sp105_spot_without_retry_warns():
    src = """
    type: task
    name: spotty
    commands: [python train.py]
    spot_policy: spot
    resources:
      tpu: v5e-8
    """
    out = lint_yaml(src)
    assert [f.code for f in out] == ["SP105"]
    assert out[0].severity == "warning"
    assert "retry" in out[0].message
    # the finding anchors to the spot_policy line (pragma-suppressible)
    spec = spec_of(src)
    assert spec.lines[out[0].line - 1].startswith("spot_policy")


def test_sp105_spot_with_retry_clean():
    assert codes("""
    type: task
    name: spotty
    commands: [python train.py]
    spot_policy: spot
    retry:
      on_events: [interruption]
      max_attempts: 5
      backoff: 30s
    resources:
      tpu: v5e-8
    """) == []
    # on-demand without retry never warns
    assert codes("""
    type: task
    name: ondemand
    commands: [python train.py]
    resources:
      tpu: v5e-8
    """) == []


def test_sp105_applies_to_spot_fleets_too():
    out = lint_yaml("""
    type: fleet
    name: flt
    nodes: 1
    spot_policy: spot
    resources:
      tpu:
        generation: v5e
        chips: 8
    """)
    assert [f.code for f in out] == ["SP105"]
    assert "spot fleet" in out[0].message


def test_sp105_retry_knob_sanity():
    # max_attempts: 1 = the retry block is inert
    out = lint_yaml("""
    type: task
    name: tt
    commands: [python train.py]
    retry:
      max_attempts: 1
    resources:
      tpu: v5e-8
    """)
    assert [f.code for f in out] == ["SP105"]
    assert "max_attempts: 1" in out[0].message
    # backoff longer than the whole retry window: no retry ever happens
    out = lint_yaml("""
    type: task
    name: tt
    commands: [python train.py]
    retry:
      duration: 60s
      backoff: 5m
    resources:
      tpu: v5e-8
    """)
    assert [f.code for f in out] == ["SP105"]
    assert "exceeds retry.duration" in out[0].message
    # consistent knobs are clean
    assert codes("""
    type: task
    name: tt
    commands: [python train.py]
    retry:
      duration: 1h
      backoff: 30s
      max_attempts: 4
    resources:
      tpu: v5e-8
    """) == []
    # invalid budget is rejected by the model itself (SP001)
    out = lint_yaml("""
    type: task
    name: tt
    commands: [python train.py]
    retry:
      max_attempts: 0
    resources:
      tpu: v5e-8
    """)
    assert out == [] or [f.code for f in out] == ["SP001"]


def test_sp105_pragma_suppression():
    assert lint_yaml("""
    type: task
    name: spotty
    commands: [python train.py]
    spot_policy: spot  # speclint: disable=SP105
    resources:
      tpu: v5e-8
    """) == []


# -- SP2xx: parallelism feasibility ------------------------------------------


def test_sp201_tensor_parallel_exceeds_chips():
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server --tensor-parallel 8 "
        "--port 8000", tpu="v5litepod-4"))
    assert [f.code for f in out] == ["SP201"]
    assert out[0].severity == "error"


def test_sp201_tensor_parallel_fits():
    assert codes(service(
        "python -m dstack_tpu.serving.server --tensor-parallel 4 "
        "--port 8000", tpu="v5litepod-4")) == []


def test_sp201_non_dividing_tp_warns():
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server --tensor-parallel 3 "
        "--port 8000", tpu="v5e-8"))
    assert [f.code for f in out] == ["SP201"]
    assert out[0].severity == "warning"


def test_sp201_mesh_literal_product():
    out = lint_yaml("""
    type: task
    name: tsk
    commands:
      - |
        python -c "
        from dstack_tpu.parallel.mesh import MeshSpec, build_mesh
        mesh = build_mesh(MeshSpec(seq=8, tensor=4))
        "
    resources:
      tpu: v5litepod-16
    """)
    assert [f.code for f in out] == ["SP201"]
    assert "32 devices" in out[0].message


def test_sp201_dynamic_mesh_sizes_ignored():
    # MAY analysis: n // 8 is not a literal, so nothing to check
    assert codes("""
    type: task
    name: tsk
    commands:
      - |
        python -c "
        from dstack_tpu.parallel.mesh import MeshSpec, build_mesh
        mesh = build_mesh(MeshSpec(seq=8, fsdp=n // 8))
        "
    resources:
      tpu: v5litepod-16
    """) == []


def test_sp202_nodes_vs_hosts():
    out = lint_yaml("""
    type: task
    name: tsk
    nodes: 4
    commands: [python train.py]
    resources:
      tpu: v5litepod-16
    """)
    assert [f.code for f in out] == ["SP202"]
    assert "2-host slice" in out[0].message


def test_sp202_nodes_match_hosts():
    assert codes("""
    type: task
    name: tsk
    nodes: 2
    commands: [python train.py]
    resources:
      tpu: v5litepod-16
    """) == []


def test_sp202_hosts_range_conflict():
    out = lint_yaml("""
    type: task
    name: tsk
    nodes: 4
    commands: [python train.py]
    resources:
      tpu:
        hosts: 1..2
    """)
    assert [f.code for f in out] == ["SP202"]
    assert "hosts range" in out[0].message


def test_sp2xx_silent_without_exact_slice():
    # `gpu: tpu` pins nothing — feasibility is the scheduler's problem
    assert codes("""
    type: task
    name: tsk
    nodes: 4
    commands: [python train.py]
    resources:
      gpu: tpu
    """) == []


# -- SP3xx: HBM budget -------------------------------------------------------


def test_sp301_model_cannot_fit():
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server --config llama3-70b "
        "--port 8000", tpu="v5e-8"))
    assert [f.code for f in out] == ["SP301"]
    assert out[0].severity == "error"
    assert "does not fit" in out[0].message


def test_sp302_over_90_percent_warns():
    # int8 8B (7.5 GiB) + bf16 KV at batch=16 len=4096 (8 GiB) on one
    # 16 GiB chip = ~97%
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server --config llama3-8b "
        "--quantize int8 --batch-size 16 --max-len 4096 --port 8000",
        tpu="v5litepod-1"))
    assert [f.code for f in out] == ["SP302"]
    assert out[0].severity == "warning"


def test_sp3xx_tensor_parallel_raises_budget():
    # the same load over a TP=4 group (64 GiB) is comfortable
    assert codes(service(
        "python -m dstack_tpu.serving.server --config llama3-8b "
        "--quantize int8 --kv-quantize int8 --tensor-parallel 4 "
        "--batch-size 16 --max-len 4096 --port 8000",
        tpu="v5litepod-4")) == []


def test_sp3xx_int4_kv_shrinks_budget_to_clean():
    # the SP302 shape above (int8 8B + KV at batch=16 len=4096 ~ 97%)
    # drops to ~60% when the KV cache is int4: 0.5 bytes/value + the f32
    # per-row scale instead of 2 — the estimator must know the flag
    assert codes(service(
        "python -m dstack_tpu.serving.server --config llama3-8b "
        "--quantize int8 --kv-quantize int4 --batch-size 16 "
        "--max-len 4096 --port 8000", tpu="v5litepod-1")) == []


def test_sp3xx_int4_kv_still_errors_when_weights_dominate():
    # bf16 8B weights alone are ~15 GiB; even a quartered KV cache pushes
    # past one 16 GiB chip — int4 must not silence a real overcommit
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server --config llama3-8b "
        "--kv-quantize int4 --batch-size 16 --max-len 4096 --port 8000",
        tpu="v5litepod-1"))
    assert [f.code for f in out] == ["SP301"]
    assert "int4+scales" in out[0].message


def test_sp3xx_scale_overhead_counted():
    # batch=27 len=4096 int8 KV sits at ~90.2% WITH the f32 per-(token,
    # head)-row scales and ~88.9% without them — the warning only fires
    # because the estimator carries the scale term
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server --config llama3-8b "
        "--quantize int8 --kv-quantize int8 --batch-size 27 "
        "--max-len 4096 --port 8000", tpu="v5litepod-1"))
    assert [f.code for f in out] == ["SP302"]
    assert "int8+scales" in out[0].message


def test_sp3xx_checkpoint_path_size_hint():
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server "
        "--checkpoint /ckpts/Llama-3-70B-hf --port 8000", tpu="v5e-8"))
    assert [f.code for f in out] == ["SP301"]
    assert "llama3-70b" in out[0].message


def test_sp3xx_unknown_model_stays_silent():
    assert codes(service(
        "python -m dstack_tpu.serving.server "
        "--checkpoint /ckpts/mystery-model --port 8000",
        tpu="v5litepod-1")) == []


# -- SP4xx: service plane ----------------------------------------------------


def test_sp401_port_mismatch():
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server --config tiny --port 8001",
        tpu="v5e-8"))
    assert [f.code for f in out] == ["SP401"]
    assert "8001" in out[0].message


def test_sp402_inert_scaling_block():
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server --config tiny --port 8000",
        tpu="v5e-8",
        extra="replicas: 2\nscaling:\n  metric: rps\n  target: 10\n"))
    assert [f.code for f in out] == ["SP402"]
    assert out[0].severity == "warning"


def test_sp402_scaling_with_range_is_clean():
    assert codes(service(
        "python -m dstack_tpu.serving.server --config tiny --port 8000",
        tpu="v5e-8",
        extra="replicas: 1..4\nscaling:\n  metric: rps\n  target: 10\n"
              "env:\n  DSTACK_STANDBY_REPLICAS: \"1\"\n",
    )) == []


def test_sp404_scaling_without_warm_pool_warns():
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server --config tiny --port 8000",
        tpu="v5e-8",
        extra="replicas: 1..4\nscaling:\n  metric: rps\n  target: 10\n"))
    assert [f.code for f in out] == ["SP404"]
    assert out[0].severity == "warning"
    # the message must name the consequence: cold-start reaction lag
    assert "cold start" in out[0].message
    assert "DSTACK_STANDBY_REPLICAS" in out[0].message


def test_sp404_standby_env_is_conforming():
    assert codes(service(
        "python -m dstack_tpu.serving.server --config tiny --port 8000",
        tpu="v5e-8",
        extra="replicas: 1..4\nscaling:\n  metric: rps\n  target: 10\n"
              "env:\n  DSTACK_STANDBY_REPLICAS: \"2\"\n",
    )) == []


def test_sp404_standby_flag_is_conforming():
    assert codes(service(
        "python -m dstack_tpu.serving.server --config tiny --port 8000 "
        "--standby",
        tpu="v5e-8",
        extra="replicas: 1..4\nscaling:\n  metric: rps\n  target: 10\n",
    )) == []


def test_sp404_fixed_count_is_sp402_not_sp404():
    """A fixed replica count with `scaling:` is ONE root cause (the
    inert scaling block) — SP402 fires alone, not SP402+SP404."""
    out = lint_yaml(service(
        "python -m dstack_tpu.serving.server --config tiny --port 8000",
        tpu="v5e-8",
        extra="replicas: 2\nscaling:\n  metric: rps\n  target: 10\n"))
    assert [f.code for f in out] == ["SP402"]


def test_sp403_missing_model_block():
    out = lint_yaml("""
    type: service
    name: svc
    port: 8000
    commands:
      - python -m dstack_tpu.serving.server --config tiny --port 8000
    resources:
      tpu: v5e-8
    """)
    assert [f.code for f in out] == ["SP403"]
    assert out[0].severity == "warning"


def test_sp403_non_engine_service_needs_no_model():
    assert codes("""
    type: service
    name: svc
    port: 8000
    commands:
      - python my_server.py --port 8000
    resources:
      tpu: v5e-8
    """) == []


# -- SP107: single replica with SLO machinery --------------------------------


def test_sp107_declared_single_replica_with_probes_warns():
    out = lint_yaml("""
    type: service
    name: svc
    port: 8000
    replicas: 1
    commands:
      - python my_server.py --port 8000
    probes:
      - type: http
        url: /health
    resources:
      tpu: v5e-8
    """)
    sp107 = [f for f in out if f.code == "SP107"]
    assert len(sp107) == 1
    assert sp107[0].severity == "warning"
    assert "hedged" in sp107[0].message
    # anchored to the replicas: line — a pragma there suppresses
    spec = spec_of("""
    type: service
    name: svc
    port: 8000
    replicas: 1
    commands:
      - python my_server.py --port 8000
    probes:
      - type: http
        url: /health
    resources:
      tpu: v5e-8
    """)
    assert spec.lines[sp107[0].line - 1].startswith("replicas")


def test_sp107_silent_without_declared_replicas_or_slo():
    # implicit one-replica default (user never wrote replicas:) — silent
    assert "SP107" not in codes("""
    type: service
    name: svc
    port: 8000
    commands:
      - python my_server.py --port 8000
    probes:
      - type: http
        url: /health
    resources:
      tpu: v5e-8
    """)
    # declared single replica but NO SLO machinery — silent
    assert "SP107" not in codes("""
    type: service
    name: svc
    port: 8000
    replicas: 1
    commands:
      - python my_server.py --port 8000
    resources:
      tpu: v5e-8
    """)
    # replica range: failover target exists — silent
    assert "SP107" not in codes("""
    type: service
    name: svc
    port: 8000
    replicas: 1..4
    scaling:
      metric: rps
      target: 16
    commands:
      - python my_server.py --port 8000
    probes:
      - type: http
        url: /health
    resources:
      tpu: v5e-8
    """)


# -- SP5xx: env collisions ---------------------------------------------------


def test_sp501_reserved_env_reads_from_knob_registry():
    """The runner-injected variable list is sourced from core/knobs.py
    (``injected=True`` entries), not a hand-maintained copy here."""
    from dstack_tpu.analysis.spec.common import RESERVED_RUNNER_ENV
    from dstack_tpu.core.knobs import KNOBS, runner_injected_names

    injected = runner_injected_names()
    assert injected == {k.name for k in KNOBS if k.injected}
    assert injected and injected <= RESERVED_RUNNER_ENV


def test_sp501_reserved_env_entry():
    out = lint_yaml("""
    type: task
    name: tsk
    commands: [python train.py]
    env:
      - TPU_WORKER_ID=3
    resources:
      tpu: v5e-8
    """)
    assert [f.code for f in out] == ["SP501"]
    assert "TPU_WORKER_ID" in out[0].message
    # anchored to the offending entry line (`- TPU_WORKER_ID=3`)
    assert out[0].line == 5


def test_sp501_replica_group_env():
    out = lint_yaml("""
    type: service
    name: svc
    port: 8000
    model:
      name: m
    replica_groups:
      - name: prefill
        role: prefill
        commands: [python -m dstack_tpu.serving.server --port 8000]
        env:
          - JAX_COORDINATOR_ADDRESS=10.0.0.1:1234
      - name: decode
        role: decode
        commands: [python -m dstack_tpu.serving.server --port 8000]
    resources:
      tpu: v5e-8
    """)
    assert [f.code for f in out] == ["SP501"]
    assert "prefill" in out[0].message


def test_sp501_fleet_dict_env():
    out = lint_yaml("""
    type: fleet
    name: flt
    nodes: 1
    env:
      DSTACK_NODE_RANK: "0"
    resources:
      tpu: v5e-8
    """)
    assert [f.code for f in out] == ["SP501"]


def test_sp501_benign_env_clean():
    assert codes("""
    type: task
    name: tsk
    commands: [python train.py]
    env:
      - HF_HOME=/cache
      - TF_CPP_MIN_LOG_LEVEL=1
    resources:
      tpu: v5e-8
    """) == []


# -- pragmas -----------------------------------------------------------------


def test_pragma_same_line():
    assert codes("""
    type: task
    name: tsk
    commands: [python train.py]
    resources:
      tpu:
        generation: v5e
        chips: 6  # speclint: disable=SP103
    """) == []


def test_pragma_line_above():
    assert codes("""
    type: task
    name: tsk
    nodes: 4
    commands: [python train.py]
    resources:
      # speclint: disable=SP202
      tpu: v5litepod-16
    """) != []  # pragma is NOT on the finding's line (nodes:) — stays

    assert codes("""
    type: task
    name: tsk
    # speclint: disable=SP202
    nodes: 4
    commands: [python train.py]
    resources:
      tpu: v5litepod-16
    """) == []


def test_pragma_file_level():
    assert codes("""
    # speclint: disable-file=SP202
    type: task
    name: tsk
    nodes: 4
    commands: [python train.py]
    resources:
      tpu: v5litepod-16
    """) == []


def test_pragma_wrong_code_does_not_suppress():
    assert codes("""
    type: task
    name: tsk
    # speclint: disable=SP101
    nodes: 4
    commands: [python train.py]
    resources:
      tpu: v5litepod-16
    """) == ["SP202"]


# -- server-side (text-less) configurations ----------------------------------


def test_analyze_configuration_without_text():
    from dstack_tpu.core.models.configurations import (
        parse_apply_configuration,
    )

    conf = parse_apply_configuration({
        "type": "task", "name": "tsk", "nodes": 4,
        "commands": ["python train.py"],
        "resources": {"tpu": "v5litepod-16"},
    })
    out = analyze_configuration(conf, path="api.yml")
    assert [f.code for f in out] == ["SP202"]
    assert out[0].path == "api.yml" and out[0].line == 1


def test_env_var_dump_roundtrip_still_flagged():
    # the server sees the model, not the YAML; env collisions must
    # survive the model_dump round-trip
    from dstack_tpu.core.models.configurations import (
        parse_apply_configuration,
    )

    conf = parse_apply_configuration({
        "type": "task", "name": "tsk",
        "commands": ["python train.py"],
        "env": ["TPU_WORKER_ID=0"],
        "resources": {"tpu": "v5e-8"},
    })
    assert [f.code for f in analyze_configuration(conf)] == ["SP501"]


# -- driver / discovery ------------------------------------------------------


def test_analyze_spec_paths_skips_non_configs(tmp_path):
    (tmp_path / "ci.yml").write_text("jobs:\n  build:\n    steps: []\n")
    (tmp_path / "bad.yml").write_text("{unclosed\n")
    (tmp_path / "spec").mkdir()
    (tmp_path / "spec" / ".dstack.yml").write_text(
        "type: task\nname: tsk\nnodes: 4\ncommands: [python t.py]\n"
        "resources:\n  tpu: v5litepod-16\n"
    )
    findings, errors = analyze_spec_paths([tmp_path])
    assert [f.code for f in findings] == ["SP202"]
    assert len(errors) == 1 and "bad.yml" in errors[0]


def test_hidden_dstack_yml_discovered(tmp_path):
    # pathlib glob must pick up the canonical dotfile name
    (tmp_path / ".dstack.yml").write_text(
        "type: task\nname: tsk\ncommands: [echo ok]\n"
        "resources:\n  tpu: v5p-129\n"
    )
    findings, _ = analyze_spec_paths([tmp_path])
    assert [f.code for f in findings] == ["SP102"]


def test_load_spec_reports_relpath(tmp_path):
    p = tmp_path / "svc.yml"
    p.write_text("type: task\nname: tsk\ncommands: [echo ok]\n")
    spec = load_spec(p)
    assert spec is not None and spec.conf is not None


# -- CLI (--specs) -----------------------------------------------------------


def _write_bad_spec(d: Path) -> Path:
    d.mkdir(parents=True, exist_ok=True)
    p = d / "bad.dstack.yml"
    p.write_text(
        "type: task\nname: tsk\nnodes: 4\ncommands: [python t.py]\n"
        "resources:\n  tpu: v5litepod-16\n"
    )
    return p


def test_cli_specs_exit_codes(tmp_path, capsys):
    from dstack_tpu.analysis.__main__ import main

    good = tmp_path / "good"
    good.mkdir()
    (good / "a.dstack.yml").write_text(
        "type: task\nname: tsk\ncommands: [echo ok]\n"
        "resources:\n  tpu: v5e-8\n"
    )
    assert main(["--specs", str(good), "--no-baseline"]) == 0
    capsys.readouterr()

    _write_bad_spec(tmp_path / "bad")
    rc = main(["--specs", str(tmp_path / "bad"), "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1 and "SP202" in out

    broken = tmp_path / "broken"
    broken.mkdir()
    (broken / "x.yml").write_text("type: task\n  bad indent: {\n")
    assert main(["--specs", str(broken), "--no-baseline"]) == 2


def test_cli_specs_json_carries_severity_and_family(tmp_path, capsys):
    from dstack_tpu.analysis.__main__ import main

    d = tmp_path / "specs"
    d.mkdir()
    (d / "ring.yml").write_text(
        "type: task\nname: tsk\ncommands: [echo ok]\n"
        "resources:\n  tpu:\n    generation: v5e\n    chips: 6\n"
    )
    rc = main(["--specs", str(d), "--json", "--no-baseline"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert data["by_family"] == {"SP1xx": 1}
    f = data["findings"][0]
    assert f["code"] == "SP103" and f["severity"] == "warning"


def test_cli_select_sp_prefix(tmp_path, capsys):
    from dstack_tpu.analysis.__main__ import main

    _write_bad_spec(tmp_path / "specs")
    # python finding too, to prove --select SP drops DT
    pkg = tmp_path / "dstack_tpu" / "gateway"
    pkg.mkdir(parents=True)
    (pkg / "snip.py").write_text(
        "import time\nasync def h(r):\n    time.sleep(1)\n"
    )
    rc = main([str(tmp_path), "--specs", str(tmp_path / "specs"),
               "--no-baseline", "--select", "SP"])
    out = capsys.readouterr().out
    assert rc == 1 and "SP202" in out and "DT101" not in out

    rc = main([str(tmp_path), "--specs", str(tmp_path / "specs"),
               "--no-baseline", "--select", "SP2"])
    out = capsys.readouterr().out
    assert rc == 1 and "SP202" in out

    # unknown SP family prefix is a usage error, same as DT9
    assert main(["--specs", str(tmp_path / "specs"),
                 "--select", "SP9"]) == 2


def test_cli_mixed_dt_sp_baseline_roundtrip(tmp_path, capsys):
    """--update-baseline writes DT and SP findings into ONE baseline and
    a plain rerun is clean — the regression the satellite pins."""
    from dstack_tpu.analysis.__main__ import main

    pkg = tmp_path / "dstack_tpu" / "gateway"
    pkg.mkdir(parents=True)
    (pkg / "snip.py").write_text(
        "import time\nasync def h(r):\n    time.sleep(1)\n"
    )
    _write_bad_spec(tmp_path / "specs")
    baseline = tmp_path / ".dtlint-baseline.json"
    assert main([str(tmp_path), "--specs", str(tmp_path / "specs"),
                 "--update-baseline", "--baseline", str(baseline)]) == 0
    capsys.readouterr()
    entries = json.loads(baseline.read_text())["entries"]
    assert {e["code"] for e in entries} == {"DT101", "SP202"}
    # the mixed baseline greens the mixed scan...
    assert main([str(tmp_path), "--specs", str(tmp_path / "specs"),
                 "--baseline", str(baseline)]) == 0
    capsys.readouterr()
    # ...and a NEW violation of either plane still fails
    (tmp_path / "specs" / "new.yml").write_text(
        "type: task\nname: ntask\ncommands: [echo ok]\n"
        "env: [TPU_WORKER_ID=1]\nresources:\n  tpu: v5e-8\n"
    )
    rc = main([str(tmp_path), "--specs", str(tmp_path / "specs"),
               "--baseline", str(baseline)])
    out = capsys.readouterr().out
    assert rc == 1 and "SP501" in out


# -- SP6xx: slo blocks that can never fire (or fire wrong) ------------------


def _slo_service(slo_yaml: str) -> str:
    return service("python -m dstack_tpu.serving.server --port 8000",
                   extra="slo:\n" + textwrap.indent(
                       textwrap.dedent(slo_yaml).strip(), "  ") + "\n")


def test_sp601_unknown_objective_metric():
    src = _slo_service("""
    objectives:
      - metric: p95_ttfb_ms
        target: 200
    """)
    out = lint_yaml(src)
    assert [f.code for f in out] == ["SP601"]
    assert out[0].severity == "error"
    assert "p95_ttfb_ms" in out[0].message
    assert "p95_ttft_ms" in out[0].message  # names the known vocabulary
    # anchored to the offending objective line, not the slo: header
    lines = textwrap.dedent(src).lstrip().splitlines()
    assert "p95_ttfb_ms" in lines[out[0].line - 1]


def test_sp601_millisecond_unit_trap():
    out = lint_yaml(_slo_service("""
    objectives:
      - metric: p95_ttft_ms
        target: 0.2
    """))
    assert [f.code for f in out] == ["SP601"]
    assert "200" in out[0].message  # suggests the ms equivalent


def test_sp601_fraction_unit_trap():
    out = lint_yaml(_slo_service("""
    objectives:
      - metric: availability
        target: 99.9
    """))
    assert [f.code for f in out] == ["SP601"]
    assert "0.999" in out[0].message


def test_sp602_window_below_cadence_warns_naming_cadence():
    from dstack_tpu.server import settings

    cadence = max(settings.SLO_STATS_INTERVAL,
                  settings.CUSTOM_METRICS_SWEEP_SECONDS)
    out = lint_yaml(_slo_service("""
    objectives:
      - metric: availability
        target: 0.999
    fast_window: 5
    """))
    assert [f.code for f in out] == ["SP602"]
    assert out[0].severity == "warning"
    assert f"{cadence:g}s" in out[0].message  # names the actual cadence


def test_sp603_burn_thresholds_out_of_order():
    out = lint_yaml(_slo_service("""
    objectives:
      - metric: p95_ttft_ms
        target: 200
    fast_burn: 2
    slow_burn: 6
    """))
    assert [f.code for f in out] == ["SP603"]
    assert out[0].severity == "error"


def test_slo_conforming_block_clean():
    assert codes(_slo_service("""
    objectives:
      - metric: p95_ttft_ms
        target: 200
      - metric: availability
        target: 0.999
    fast_window: 1h
    slow_window: 6h
    """)) == []


def test_cli_list_rules_names_sp_families(capsys):
    from dstack_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for fam in ("SP1xx", "SP2xx", "SP3xx", "SP4xx", "SP5xx", "SP6xx"):
        assert fam in out


# -- acceptance: the shipped tree ------------------------------------------


def test_shipped_examples_scan_clean():
    findings, errors = analyze_spec_paths([REPO_ROOT / "examples"])
    assert errors == []
    assert findings == [], [f.render() for f in findings]


@pytest.mark.parametrize(
    "example,inject,expect",
    [
        # bad topology on the fleet example
        ("fleet-v5p-256", ("topology: 4x4x8", "topology: 4x4x3"), "SP101"),
        # TP exceeding the slice on the tensor-parallel service
        ("serving-tensor-parallel",
         ("--tensor-parallel 4", "--tensor-parallel 8"), "SP201"),
        # HBM overcommit: the 8B service's bf16 weights on ONE chip of
        # its slice (what the example asked for before it sharded)
        ("serving-llama8b", ("--quantize int8 --tensor-parallel 8", ""),
         "SP301"),
        # port mismatch on the serving example
        ("serving-llama8b", ("port: 8000", "port: 9000"), "SP401"),
        # reserved env var on the distributed task
        ("distributed-training", ("env:\n  - TF_CPP_MIN_LOG_LEVEL=1",
                                  "env:\n  - TPU_WORKER_ID=0"), "SP501"),
    ],
    ids=["topology", "tensor-parallel", "hbm", "port", "env"],
)
def test_injected_violation_per_family(tmp_path, capsys, example, inject,
                                       expect):
    """A copy of each family's example with one injected violation exits
    1 with the matching SP code (the ISSUE acceptance matrix)."""
    from dstack_tpu.analysis.__main__ import main

    src = (REPO_ROOT / "examples" / example / ".dstack.yml").read_text()
    old, new = inject
    assert old in src, f"fixture drift: {old!r} not in {example}"
    d = tmp_path / example
    d.mkdir()
    (d / ".dstack.yml").write_text(src.replace(old, new))
    rc = main(["--specs", str(d), "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1, out
    assert expect in out, out


def test_sp203_unknown_mesh_axis():
    out = lint_yaml("""
    type: task
    name: tsk
    commands:
      - |
        python -c "
        from dstack_tpu.parallel.mesh import MeshSpec, build_mesh
        mesh = build_mesh(MeshSpec(tenosr=4))
        "
    resources:
      tpu: v5litepod-8
    """)
    assert [f.code for f in out] == ["SP203"]
    assert "tenosr" in out[0].message and "tensor" in out[0].message


def test_sp203_valid_axes_clean():
    assert codes("""
    type: task
    name: tsk
    commands:
      - |
        python -c "
        from dstack_tpu.parallel.mesh import MeshSpec, build_mesh
        mesh = build_mesh(MeshSpec(seq=2, fsdp=4))
        "
    resources:
      tpu: v5litepod-8
    """) == []


def test_mesh_axis_names_read_from_real_mesh_py():
    """speclint's axis vocabulary is read from parallel/mesh.py at scan
    time, so a new axis teaches speclint exactly as it teaches shardlint
    — drift-locked against the callgraph's pinned default."""
    from dstack_tpu.analysis.callgraph import DEFAULT_AXIS_NAMES
    from dstack_tpu.analysis.spec.common import mesh_axis_names

    assert mesh_axis_names() == DEFAULT_AXIS_NAMES


def test_sp101_unsorted_table_entry_accepted():
    # the 3D table's "2x2x1" is not ascending; sorted-tuple comparison
    # must still accept the literal and any rotation of it
    for topo in ("2x2x1", "1x2x2"):
        assert codes(f"""
        type: fleet
        name: flt
        nodes: 1
        resources:
          tpu:
            generation: v5p
            topology: {topo}
        """) == [], topo


class TestReviewRegressions:
    """Anchoring and CLI regressions from code review."""

    def test_sp501_anchor_survives_name_echo_in_commands(self):
        # the var name echoed in `commands:` must not steal the anchor —
        # the pragma on the real env entry has to keep suppressing
        src = """
        type: task
        name: tsk
        commands:
          - echo $TPU_WORKER_ID
        env:
          - TPU_WORKER_ID=7{pragma}
        resources:
          tpu: v5e-8
        """
        out = lint_yaml(src.format(pragma=""))
        assert [f.code for f in out] == ["SP501"]
        assert out[0].line == 6  # the env entry, not the command
        assert lint_yaml(
            src.format(pragma="  # speclint: disable=SP501")) == []

    def test_sp401_anchor_survives_nested_port_key(self):
        # a nested `metrics: port:` earlier in the file must not shadow
        # the top-level `port:` for anchoring/suppression
        src = """
        type: service
        name: svc
        metrics:
          port: 9100
        port: 8000{pragma}
        model:
          name: m
        commands:
          - python -m dstack_tpu.serving.server --config tiny --port 8001
        resources:
          tpu: v5e-8
        """
        out = lint_yaml(src.format(pragma=""))
        assert [f.code for f in out] == ["SP401"]
        assert out[0].line == 5  # the top-level port line
        assert lint_yaml(
            src.format(pragma="  # speclint: disable=SP401")) == []

    def test_cli_select_sp001_is_valid(self, tmp_path, capsys):
        from dstack_tpu.analysis.__main__ import main

        d = tmp_path / "specs"
        d.mkdir()
        (d / "broken.yml").write_text("type: service\nname: sv\nport: 1\n")
        rc = main(["--specs", str(d), "--no-baseline", "--select", "SP001"])
        out = capsys.readouterr().out
        assert rc == 1 and "SP001" in out
        # and --ignore SP001 drops the validation noise
        assert main(["--specs", str(d), "--no-baseline",
                     "--ignore", "SP001"]) == 0

    def test_sp101_mixed_dims_message_names_per_generation_dims(self):
        out = lint_yaml("""
        type: task
        name: tsk
        commands: [python t.py]
        resources:
          tpu:
            topology: "16"
        """)
        assert [f.code for f in out] == ["SP101"]
        # no generation pinned: the message must not claim every
        # generation shares one dimensionality
        assert "v4: 3D" in out[0].message and "v5e: 2D" in out[0].message

    def test_speclint_alias_passes_value_flags_through(self, tmp_path):
        import subprocess
        import sys as _sys

        d = tmp_path / "specs"
        d.mkdir()
        (d / "ok.yml").write_text(
            "type: task\nname: ok-task\ncommands: [python t.py]\n"
            "resources:\n  tpu: v5e-8\n"
        )
        report = tmp_path / "out.json"
        r = subprocess.run(
            [_sys.executable, str(REPO_ROOT / "scripts" / "speclint.py"),
             "--no-baseline", "--report", str(report), str(d)],
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        assert json.loads(report.read_text())["total"] == 0

    def test_plain_dtlint_stays_stdlib_only(self):
        """A plain (no --specs/--select) dtlint run must not import the
        spec package's yaml/pydantic dependencies — CI lints before
        `pip install -e .`.  Run in a subprocess with both blocked."""
        import subprocess
        import sys as _sys

        probe = (
            "import sys\n"
            "class B:\n"
            "    def find_spec(self, n, path=None, target=None):\n"
            "        if n.partition('.')[0] in ('yaml', 'pydantic'):\n"
            "            raise ModuleNotFoundError('blocked: '+n, name=n)\n"
            "        return None\n"
            "sys.meta_path.insert(0, B())\n"
            "from dstack_tpu.analysis.__main__ import main\n"
            "rc = main(['dstack_tpu/analysis/core.py', '--no-baseline'])\n"
            "assert rc == 0, rc\n"
            "rc = main(['--specs', 'examples', '--no-baseline'])\n"
            "assert rc == 2, rc\n"
            "print('OK')\n"
        )
        r = subprocess.run(
            [_sys.executable, "-c", probe], cwd=str(REPO_ROOT),
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr

    def test_explicit_file_any_suffix_is_linted(self, tmp_path):
        p = tmp_path / "run.dstack.yaml.bak"
        p.write_text(
            "type: task\nname: bak-task\nnodes: 4\n"
            "commands: [python t.py]\nresources:\n  tpu: v5litepod-16\n"
        )
        findings, errors = analyze_spec_paths([p])
        assert [f.code for f in findings] == ["SP202"]
        # directory scans still take only *.yml/*.yaml
        findings, errors = analyze_spec_paths([tmp_path])
        assert findings == [] and errors == []

    def test_speclint_alias_accepts_explicit_specs_flag(self, tmp_path):
        import subprocess
        import sys as _sys

        d = tmp_path / "specs"
        d.mkdir()
        (d / "ok.yml").write_text(
            "type: task\nname: ok-task\ncommands: [python t.py]\n"
            "resources:\n  tpu: v5e-8\n"
        )
        r = subprocess.run(
            [_sys.executable, str(REPO_ROOT / "scripts" / "speclint.py"),
             "--no-baseline", "--specs", str(d)],
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stdout + r.stderr

    def test_replica_group_resources_override_scopes_tp_and_hbm(self):
        # the provisioning pipeline applies a group's own `resources:`,
        # so TP/HBM feasibility must judge the group command against the
        # GROUP's slice, not the service-level one
        src = """
        type: service
        name: svc
        port: 8000
        model:
          name: m
        commands:
          - python -m dstack_tpu.serving.server --config tiny --port 8000
        replica_groups:
          - name: big
            commands:
              - python -m dstack_tpu.serving.server --config tiny
                --tensor-parallel 16 --port 8000
            resources:
              tpu: v5e-16
        resources:
          tpu: v5e-8
        """
        assert codes(src) == []
        # and the group's own slice still gates its command
        assert codes(src.replace("tpu: v5e-16", "tpu: v5e-4")) == ["SP201"]

    def test_replica_group_port_override_scopes_sp401(self):
        src = """
        type: service
        name: svc
        port: 8000
        model:
          name: m
        replica_groups:
          - name: prefill
            role: prefill
            port: {gport}
            commands:
              - python -m dstack_tpu.serving.server --config tiny --port 8001
          - name: decode
            role: decode
            commands:
              - python -m dstack_tpu.serving.server --config tiny --port 8000
        resources:
          tpu: v5e-8
        """
        # group binds its own overridden port: valid PD shape
        assert codes(src.format(gport=8001)) == []
        # group port override that the command does NOT bind still fires
        out = lint_yaml(src.format(gport=8002))
        assert [f.code for f in out] == ["SP401"]
        assert "prefill" in out[0].message

    def test_explicit_file_without_type_key_is_an_error(self, tmp_path):
        p = tmp_path / "typo.yml"
        p.write_text("tpye: task\nname: oops\n")
        findings, errors = analyze_spec_paths([p])
        assert findings == []
        assert len(errors) == 1 and "no `type:` key" in errors[0]
        # the same file inside a directory scan stays quietly skipped
        findings, errors = analyze_spec_paths([tmp_path])
        assert findings == [] and errors == []

    def test_cli_lint_and_gate_honor_shared_baseline(self, tmp_path,
                                                     monkeypatch):
        # a baselined SP finding must not fail `dstack-tpu lint` (nor the
        # apply gate) when CI's --specs run is green for the same tree
        from dstack_tpu.analysis.__main__ import main
        from dstack_tpu.cli.main import _baseline_filter

        d = tmp_path / "specs"
        d.mkdir()
        (d / "old.yml").write_text(
            "type: task\nname: old-task\nnodes: 4\n"
            "commands: [python t.py]\nresources:\n  tpu: v5litepod-16\n"
        )
        baseline = tmp_path / ".dtlint-baseline.json"
        assert main(["--specs", str(d), "--update-baseline",
                     "--baseline", str(baseline)]) == 0
        monkeypatch.chdir(tmp_path)
        findings, _ = analyze_spec_paths([d])
        assert [f.code for f in findings] == ["SP202"]
        assert _baseline_filter(findings) == []

    def test_sp401_group_override_anchors_to_group_port_line(self):
        src = """
        type: service
        name: svc
        port: 8000
        model:
          name: m
        replica_groups:
          - name: prefill
            role: prefill
            port: 9000{pragma}
            commands:
              - python -m dstack_tpu.serving.server --config tiny --port 8000
          - name: decode
            role: decode
            commands:
              - python -m dstack_tpu.serving.server --config tiny --port 8000
        resources:
          tpu: v5e-8
        """
        out = lint_yaml(src.format(pragma=""))
        assert [f.code for f in out] == ["SP401"]
        assert out[0].line == 9  # the group's port: line, not line 3
        assert lint_yaml(
            src.format(pragma="  # speclint: disable=SP401")) == []

    def test_apply_gate_baseline_keys_are_repo_relative(self, tmp_path,
                                                        monkeypatch):
        # `apply -f /abs/path` must hit the same baseline key CI's
        # repo-relative scan wrote
        from dstack_tpu.analysis.core import Baseline
        from dstack_tpu.cli.main import _lint_spec_file

        repo = tmp_path / "proj"
        repo.mkdir()
        (repo / "pyproject.toml").write_text("")  # repo marker
        spec = repo / "bad.yml"
        text = (
            "type: task\nname: old-task\nnodes: 4\n"
            "commands:\n  - python t.py\nresources:\n  tpu: v5litepod-16\n"
        )
        spec.write_text(text)
        import yaml as _yaml

        from dstack_tpu.core.models.configurations import (
            parse_apply_configuration,
        )

        data = _yaml.safe_load(text)
        conf = parse_apply_configuration(data)
        monkeypatch.chdir(repo)
        errors, warnings = _lint_spec_file(str(spec), text, data, conf)
        assert [f.code for f in errors] == ["SP202"]
        Baseline.from_findings(errors).save(repo / ".dtlint-baseline.json")
        # absolute -f path AND a relative one both match the baseline now
        for p in (str(spec), "bad.yml"):
            errors, warnings = _lint_spec_file(p, text, data, conf)
            assert errors == [] and warnings == [], p

    def test_update_baseline_single_plane_preserves_other_plane(self,
                                                                tmp_path,
                                                                capsys):
        """A spec-only --update-baseline must not wipe grandfathered DT
        entries (and vice versa): the unscanned plane carries over."""
        from dstack_tpu.analysis.__main__ import main

        pkg = tmp_path / "dstack_tpu" / "gateway"
        pkg.mkdir(parents=True)
        (pkg / "snip.py").write_text(
            "import time\nasync def h(r):\n    time.sleep(1)\n"
        )
        specs = tmp_path / "specs"
        _write_bad_spec(specs)
        baseline = tmp_path / ".dtlint-baseline.json"
        # write the mixed baseline, then regenerate from a spec-only scan
        assert main([str(tmp_path), "--specs", str(specs),
                     "--update-baseline", "--baseline", str(baseline)]) == 0
        capsys.readouterr()
        assert main(["--specs", str(specs), "--update-baseline",
                     "--baseline", str(baseline)]) == 0
        out = capsys.readouterr().out
        assert "preserved" in out
        entries = json.loads(baseline.read_text())["entries"]
        assert {e["code"] for e in entries} == {"DT101", "SP202"}
        # ...and a code-only regeneration preserves the SP entry
        assert main([str(tmp_path), "--update-baseline",
                     "--baseline", str(baseline)]) == 0
        entries = json.loads(baseline.read_text())["entries"]
        assert {e["code"] for e in entries} == {"DT101", "SP202"}
        # the merged baseline still greens the mixed scan
        capsys.readouterr()
        assert main([str(tmp_path), "--specs", str(specs),
                     "--baseline", str(baseline)]) == 0

    def test_sp401_second_group_anchor_and_pragma(self):
        """Each group's port mismatch anchors to ITS port line; a pragma
        on a sibling group's port must not cross-suppress."""
        src = """
        type: service
        name: svc
        port: 8000
        model:
          name: m
        replica_groups:
          - name: prefill
            role: prefill
            port: 8100
            commands:
              - python -m dstack_tpu.serving.server --config tiny --port 8100
          - name: decode
            role: decode
            port: 8200{pragma}
            commands:
              - python -m dstack_tpu.serving.server --config tiny --port 9999
        resources:
          tpu: v5e-8
        """
        out = lint_yaml(src.format(pragma=""))
        assert [f.code for f in out] == ["SP401"]
        assert "decode" in out[0].message
        assert out[0].line == 14  # decode's port line, not prefill's
        assert lint_yaml(
            src.format(pragma="  # speclint: disable=SP401")) == []

    def test_multi_document_yaml_is_skipped_not_fatal(self, tmp_path):
        # a k8s manifest is VALID multi-doc YAML, not a dstack config —
        # it must not exit-2 the whole directory scan
        (tmp_path / "k8s.yml").write_text(
            "apiVersion: v1\nkind: Service\n---\napiVersion: v1\nkind: Pod\n"
        )
        (tmp_path / "spec.yml").write_text(
            "type: task\nname: tsk2\nnodes: 4\ncommands: [python t.py]\n"
            "resources:\n  tpu: v5litepod-16\n"
        )
        findings, errors = analyze_spec_paths([tmp_path])
        assert errors == []
        assert [f.code for f in findings] == ["SP202"]

    def test_virtualenv_trees_not_scanned(self, tmp_path):
        bad = ("type: task\nname: vendored\nnodes: 4\n"
               "commands: [python t.py]\nresources:\n  tpu: v5litepod-16\n")
        for d in (".venv/lib", "venv/x", ".tox/py312", "pkg/site-packages"):
            sub = tmp_path / d
            sub.mkdir(parents=True)
            (sub / "fixture.yml").write_text(bad)
        findings, errors = analyze_spec_paths([tmp_path])
        assert findings == [] and errors == []

    def test_sp201_per_group_anchor_no_cross_suppression(self):
        # two scopes with the same violating flag: each finding anchors
        # to its OWN scope, and a pragma in one scope suppresses only it
        src = """
        type: service
        name: svc
        port: 8000
        model:
          name: m
        commands:
          - python -m dstack_tpu.serving.server --config tiny
            --tensor-parallel 16 --port 8000{pragma}
        replica_groups:
          - name: aux
            commands:
              - python -m dstack_tpu.serving.server --config tiny
                --tensor-parallel 16 --port 8000
        resources:
          tpu: v5e-8
        """
        out = lint_yaml(src.format(pragma=""))
        assert [f.code for f in out] == ["SP201", "SP201"]
        assert out[0].line != out[1].line
        # pragma on the TOP-LEVEL command suppresses only that finding
        out = lint_yaml(
            src.format(pragma="  # speclint: disable=SP201"))
        assert len(out) == 1 and out[0].code == "SP201"

"""chip_smoke.py's contract, as far as a machine without a TPU can show it:
the parent stays off JAX, a run without a chip fails loudly and never
prints ``ok: true``, and the checks refuse what they must refuse."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO_ROOT))

import chip_smoke  # noqa: E402


def test_parent_import_stays_off_jax():
    """A parent that touched JAX would hold the chip its children need."""
    probe = (
        "import sys, chip_smoke\n"
        "heavy = sorted(m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'numpy', 'dstack_tpu'))\n"
        "assert not heavy, heavy\n"
        "print('OK')\n"
    )
    r = subprocess.run([sys.executable, "-c", probe], cwd=str(REPO_ROOT),
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr


def test_without_a_tpu_the_smoke_fails_loudly():
    """JAX_PLATFORMS=tpu is forced into the child whatever the parent
    inherited (here: cpu), so with no chip JAX itself refuses — exit 1, the
    failing phase named, its output shown, and no ``ok: true`` anywhere."""
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=str(REPO_ROOT),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["phase"] == "serve-dense"
    assert '"ok": true' not in r.stdout
    assert any("tpu" in line.lower() for line in lines[:-1]), r.stdout


def test_byte_prompts_have_the_asked_token_count():
    import random

    text = chip_smoke.text_of(random.Random(0), 256)
    assert len(text.encode()) == 255  # + BOS = 256 byte-tokenizer ids


@pytest.mark.parametrize("result,why", [
    ({"losses": [11.8, 11.2, 10.9, 10.1], "tpu_custom_call": False},
     "tpu_custom_call"),
    ({"losses": [11.8, 11.9, 12.0, 12.1], "tpu_custom_call": True},
     "did not fall"),
    ({"losses": [11.8, float("nan"), 1.0, 1.0], "tpu_custom_call": True},
     "losses"),
    ({"losses": [11.8, 11.0], "tpu_custom_call": True}, "2 losses"),
])
def test_train_check_refuses(result, why):
    with pytest.raises(chip_smoke.SmokeFailure, match=why):
        chip_smoke.check_train(result)


def test_train_check_accepts_falling_finite_losses():
    chip_smoke.check_train({"losses": [11.8, 11.2, 10.9, 10.1],
                            "tpu_custom_call": True})


@pytest.mark.parametrize("result", [
    {"logit_rel_diff": 0.2, "agree_tokens": [32, 32, 32, 32]},
    {"logit_rel_diff": 0.001, "agree_tokens": [32, 0, 32, 32]},
])
def test_tp_parity_check_refuses(result):
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_tp_parity(result)

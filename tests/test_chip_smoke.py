"""CPU rehearsal of chip_smoke.py: the same body the chip runs, at test
sizes, on the virtual mesh with interpreted kernels. Every check the chip
run makes (all responses arrive, region outputs stay device arrays and
match the in-process forward, a fused dispatch and a prefix-cache hit are
counted, float32 engine == generate_scan, tp=4 == tp=1) raises inside
``run`` — so a pass here is those assertions passing. It says nothing about
the device."""

import json
import os
import subprocess
import sys

import jax

import chip_smoke
from tritonclient_tpu import _compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rehearsal_runs_every_phase_and_builds_the_result_line():
    result = chip_smoke.run(chip_smoke.tiny_config(), require_tpu=False)
    # The last line holds exactly these keys; the driver refuses any other.
    assert json.loads(chip_smoke.result_line(result)) == {
        "ok": True,
        "device": {
            "platform": "cpu",
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        },
    }
    # The detail line before it carries the rest.
    # conftest's 8 virtual devices: the multichip phase runs too.
    assert {name: p["status"] for name, p in result["phases"].items()} == {
        "device": "pass", "kernels": "pass", "encoder": "pass",
        "llm": "pass", "multichip": "pass",
    }
    assert result["compilations"] > 0
    assert json.loads(json.dumps(result)) == result


def test_command_line_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    # No phase ran and no result line was printed.
    assert "[kernels]" not in proc.stdout
    assert '"ok"' not in proc.stdout


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    placed = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: placed.append((name, value)))
    monkeypatch.setenv(_compile_cache.ENV, str(tmp_path))
    assert _compile_cache.configure() == str(tmp_path)
    assert placed == []  # JAX reads the variable; no code sets another
    monkeypatch.delenv(_compile_cache.ENV)
    fixed = os.path.join(REPO, ".jax_cache")
    assert _compile_cache.configure() == fixed
    assert placed == [("jax_compilation_cache_dir", fixed)]

    assert _compile_cache.entry_count(str(tmp_path / "missing")) == 0
    (tmp_path / "jit_f-abc-cache").write_bytes(b"x")
    (tmp_path / "jit_f-abc-atime").write_bytes(b"x")
    assert _compile_cache.entry_count(str(tmp_path)) == 1

"""chip_smoke.py on the CPU: its train and serve phases at a tiny size,
and the device phase's refusal to run anywhere but on a TPU."""

import importlib.util
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_and_serve_phases_run_on_cpu():
    cs = _load_chip_smoke()
    tr = cs.train_phase("OP", scale=0.05, clients=2, rounds=1,
                        min_accuracy=0.0)
    assert len(tr.acc_history) == 1
    stats = cs.serve_phase(tr, queries=64)
    assert stats["served"] == 64


def test_device_phase_refuses_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "device phase: jax found no TPU" in proc.stderr
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("device: platform=cpu")
    assert '"ok"' not in proc.stdout

"""chip_smoke.py on the CPU: its tune and serve phases at a tiny size with
the interpreted kernels, and its refusal to stand in for the chip."""
import importlib.util
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_rehearse_on_cpu():
    """The tune phase (kernel-vs-jnp parity of the first steps, then the
    service to idle) and the serve phase (prefill parity, every request
    answered) pass on a tiny stablelm with mixed ranks."""
    smoke = _smoke()
    cfg = smoke.model_config(rehearse=True)
    size = smoke.REHEARSAL
    engine, task, result = smoke.tune_phase(cfg, size, 0, "pallas_interpret")
    assert result.best_job is not None
    smoke.serve_phase(cfg, engine.base_params(cfg, 0), task, result, size, 0,
                      "pallas_interpret")


def test_smoke_fails_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stdout
    assert '"ok"' not in p.stdout

"""Shared fixtures + pytest hardening. NOTE: no XLA_FLAGS here — tests run
on the single real CPU device; only launch/dryrun.py fakes the 512-device
platform."""
import dataclasses
import importlib.util
import pathlib
import sys

import jax
import pytest

jax.config.update("jax_platform_name", "cpu")

# ---- hypothesis fallback ---------------------------------------------------
# CI installs the real package via `pip install -e .[test]`; bare containers
# fall back to the deterministic stub so property tests still collect + run.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    _stub_path = pathlib.Path(__file__).with_name("_hypothesis_stub.py")
    _spec = importlib.util.spec_from_file_location("hypothesis", _stub_path)
    _stub = importlib.util.module_from_spec(_spec)
    sys.modules["hypothesis"] = _stub
    _spec.loader.exec_module(_stub)
    sys.modules["hypothesis.strategies"] = _stub.strategies


@pytest.fixture(scope="session")
def tiny_cfg():
    from repro.configs.registry import get_arch
    return dataclasses.replace(
        get_arch("paper-llama-tiny").reduced(num_layers=2, d_model=128,
                                             vocab=256),
        dtype="float32")


def reduced_f32(name: str, **kw):
    from repro.configs.registry import get_arch
    return dataclasses.replace(get_arch(name).reduced(**kw), dtype="float32")

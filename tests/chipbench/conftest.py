"""Fixtures of the chip benchmark's CPU tests."""
from __future__ import annotations

import pytest
from cb_helpers import make_root


@pytest.fixture
def on_cpu(monkeypatch):
    """The harness with its look for a chip skipped and JAX's persistent
    compilation cache left alone."""
    import jax

    from chipbench import harness

    monkeypatch.setattr(harness, "check_devices", lambda chips: jax.devices())
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")
    return harness


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("chipbench"))

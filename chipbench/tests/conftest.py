"""CPU tests of the chip benchmark.  Nothing here describes a topology or
needs a chip; the persistent compilation cache stays off."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def no_cache(monkeypatch):
    import jax
    from chipbench import run as R
    monkeypatch.setattr(R, "enable_cache", lambda: "off")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def tiny_cell(config: str = "tiny-dense"):
    from chipbench import cells as C
    return C.Cell(f"{config}.tiny-oversub",
                  C.load_config(config, os.path.join(DATA, "configs")),
                  C.load_traffic("tiny-oversub", os.path.join(DATA, "traffic")),
                  1)

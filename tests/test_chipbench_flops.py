"""Tier 1 runs the benchmark's own checks of its yardsticks too (``chipbench/tests``)."""
import jax
import pytest

from chipbench.tests.test_flops import *  # noqa: F401,F403
from chipbench.tests.test_pangu_costs import *  # noqa: F401,F403
from chipbench.tests.test_glm_costs import *  # noqa: F401,F403


@pytest.fixture(autouse=True)
def _a_cells_mesh_is_its_one_chip(monkeypatch):
    """``tests/conftest.py`` gives the CPU eight devices; the cells' configurations ask for one."""
    import trlx_tpu.trainer.base as base
    from trlx_tpu.parallel import make_mesh

    monkeypatch.setattr(base, "make_mesh", lambda parallel: make_mesh(parallel, devices=jax.devices()[:1]))

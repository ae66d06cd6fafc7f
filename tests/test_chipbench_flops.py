"""Tier 1 runs the benchmark's own checks of its yardsticks too (``chipbench/tests``)."""
import jax
import pytest

from chipbench.tests.test_flops import *  # noqa: F401,F403
from chipbench.tests.test_pangu_costs import *  # noqa: F401,F403
from chipbench.tests.test_glm_costs import *  # noqa: F401,F403
from chipbench.tests.test_nemotron_costs import *  # noqa: F401,F403


@pytest.fixture(autouse=True)
def _a_cells_mesh_is_its_one_chip(monkeypatch):
    """``tests/conftest.py`` gives the CPU eight devices; the cells' configurations ask for one."""
    import trlx_tpu.trainer.base as base
    from trlx_tpu.parallel import make_mesh

    monkeypatch.setattr(base, "make_mesh", lambda parallel: make_mesh(parallel, devices=jax.devices()[:1]))


# ``python3 -m pytest chipbench/tests`` FAILS one case on this tree, and tier 1 shows it as an
# expected failure, not as a case left out: the benchmark's own check of the forward count adds
# ``flops.attention_mix`` (the score square of ``num_attention_heads`` heads) for EVERY layer of the
# reference, and six of minicpm-sala-9b-l8's eight layers hold a recurrence and no scores (PR 60: and
# eight of lfm2-8b-a1b-l10e8's ten a short convolution). The file
# is a ``benchmark`` PR's to edit (PERF.md section 7 has the edit and the failing numbers); the
# configuration is held to its reference by ``tests/test_minicpm_sala.py`` meanwhile. ``strict``:
# the day the benchmark's test asks the family's costs for a layer's mix, this case passes and
# tier 1 says so.
_benchmarks_own = test_forward_count_is_the_references_matmuls  # noqa: F405
EVERY_LAYER_CHARGED_A_SQUARE = {
    "minicpm-sala-9b-l8": "chipbench/tests/test_flops.py charges each layer an attention square",
    # ... and four of the toy lfm2's six layers hold a short convolution and no scores (14,745,600 expected of the
    # reference's 14,155,776: four squares of 147,456); held by tests/test_lfm2.py::test_forward_count_is_the_references_matmuls_in_both_kinds_of_layer
    "lfm2-8b-a1b-l10e8": "chipbench/tests/test_flops.py charges each layer an attention square",
    # ... and eight of nemotron3-nano-30b-a3b-l9e8's nine layers are a Mamba-2 mixer or experts alone and hold no scores (PR 63);
    # held by chipbench/tests/test_nemotron_costs.py::test_forward_count_is_the_references_matmuls_in_all_three_kinds_of_layer
    "nemotron3-nano-30b-a3b-l9e8": "chipbench/tests/test_flops.py charges each layer an attention square",
}


@pytest.mark.parametrize("config_name", [
    pytest.param(c, marks=pytest.mark.xfail(strict=True, reason=EVERY_LAYER_CHARGED_A_SQUARE[c]))
    if c in EVERY_LAYER_CHARGED_A_SQUARE else c for c in CONFIGS])  # noqa: F405
def test_forward_count_is_the_references_matmuls(config_name):  # noqa: F811
    _benchmarks_own(config_name)

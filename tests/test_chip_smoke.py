"""``chip_smoke.py``'s control flow, walked without a chip and without a
compile: which phases each option runs, the exact last line, that a failing
phase fails the run, and that nothing continues on a device other than the
TPU by itself. Plus the two runtime rules the
script leans on: an unknown TPU ``device_kind`` is an error, and the compile
cache is placed from outside or at ``<checkout>/.jax_cache``.

The slow tier runs the whole script as a subprocess — the CPU rehearsal,
default and ``--chips 4``.
"""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load("chip_smoke", "chip_smoke.py")


def _device(platform="tpu", kind="TPU v5 lite"):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def _json_lines(text):
    return [json.loads(l) for l in text.splitlines() if l.startswith("{")]


@pytest.mark.parametrize(
    "chips, phases",
    [(1, ("device", "train", "engine", "kernels")), (4, ("sharded",))],
)
def test_phase_table(smoke, chips, phases):
    assert smoke.phase_table(chips) == phases
    assert set(phases) <= set(smoke.PHASES)


def test_phase_table_refuses_other_counts(smoke):
    with pytest.raises(ValueError):
        smoke.phase_table(2)


def test_last_line_is_exactly_the_contract(smoke, monkeypatch, capsys):
    ran = []
    monkeypatch.setattr(
        smoke, "PHASES", {p: lambda s, d, p=p: ran.append(p) or {"x": 1} for p in smoke.PHASES}
    )
    rc = smoke.run(smoke.phase_table(1), smoke.FULL, [_device()], rehearsal=False)
    out = capsys.readouterr().out
    assert rc == 0 and ran == list(smoke.DEFAULT_PHASES)
    assert out.splitlines()[-1] == (
        '{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}'
    )
    phases = _json_lines(out)[:-1]
    assert [p["phase"] for p in phases] == ran and all(p["ok"] for p in phases)


def test_four_chip_last_line_counts_four(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "PHASES", {"sharded": lambda s, d: {}})
    rc = smoke.run(smoke.phase_table(4), smoke.FULL, [_device()] * 4, rehearsal=False)
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rc == 0 and last == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
    }


def test_a_raising_phase_fails_the_run(smoke, monkeypatch, capsys):
    ran = []

    def boom(size, devices):
        raise RuntimeError("engine fell over")

    phases = {p: lambda s, d, p=p: ran.append(p) or {} for p in smoke.PHASES}
    phases["engine"] = boom
    monkeypatch.setattr(smoke, "PHASES", phases)
    rc = smoke.run(smoke.phase_table(1), smoke.FULL, [_device()], rehearsal=False)
    lines = _json_lines(capsys.readouterr().out)
    assert rc != 0
    assert lines[-1]["ok"] is False and lines[-1]["failed"] == "engine"
    assert lines[-2]["phase"] == "engine" and lines[-2]["ok"] is False
    assert "engine fell over" in lines[-2]["error"]
    assert ran == ["device", "train"]  # nothing after the failure runs


def test_a_cpu_rehearsal_is_never_ok(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "PHASES", {p: lambda s, d: {} for p in smoke.PHASES})
    rc = smoke.run(smoke.phase_table(1), smoke.TOY, [_device("cpu", "cpu")], rehearsal=True)
    last = _json_lines(capsys.readouterr().out)[-1]
    assert rc != 0 and last["ok"] is False and last["rehearsal"] == "passed"
    assert last["device"]["platform"] == "cpu"


@pytest.mark.parametrize(
    "platform, pinned, expect",
    [("tpu", None, False), ("cpu", "cpu", True), ("cpu", None, None), ("gpu", "cpu", None)],
)
def test_measurement_devices_gate(monkeypatch, platform, pinned, expect):
    """tpu: run; cpu pinned by the caller: rehearsal; anything else —
    including the CPU JAX falls to when it finds no chip — refused."""
    import jax

    from trlx_tpu.trlx import measurement_devices

    monkeypatch.setattr(jax, "devices", lambda: [_device(platform, platform)])
    if pinned is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", pinned)
    if expect is None:
        with pytest.raises(RuntimeError, match="not a TPU"):
            measurement_devices()
    else:
        assert measurement_devices()[1] is expect


def _no_chip(monkeypatch):
    import jax

    import trlx_tpu.trlx as trlx

    monkeypatch.setattr(trlx, "_runtime_initialized", True)
    monkeypatch.setattr(jax, "devices", lambda: [_device("cpu", "cpu")])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)


def test_chip_smoke_refuses_without_a_chip(smoke, monkeypatch, capsys):
    _no_chip(monkeypatch)
    with pytest.raises(RuntimeError, match="not a TPU"):
        smoke.main([])
    assert capsys.readouterr().out == ""  # no result of any kind


def test_kernel_tables_agree_with_the_registry(smoke):
    from trlx_tpu.analysis.kernels import KERNEL_PARITY

    assert not set(smoke.KERNEL_CHECKS) & set(smoke.KERNELS_REFUSED)
    assert set(smoke.KERNEL_CHECKS) | set(smoke.KERNELS_REFUSED) == {
        row[0] for row in KERNEL_PARITY
    }


@pytest.mark.parametrize(
    "platform, kind, peak",
    [("tpu", "TPU v5 lite", 197e12), ("tpu", "TPU v9 mega", None), ("cpu", "cpu", 1e12)],
)
def test_device_peak_flops(monkeypatch, platform, kind, peak):
    from trlx_tpu.observability.metrics import device_peak_flops

    monkeypatch.delenv("TRLX_TPU_PEAK_FLOPS", raising=False)
    if peak is None:  # a TPU the table does not know is an error, not 1e12
        with pytest.raises(ValueError, match="TPU v9 mega".lower()):
            device_peak_flops(_device(platform, kind))
    else:
        assert device_peak_flops(_device(platform, kind)) == peak


class TestCompileCache:
    def _initialize(self, monkeypatch):
        """Run initialize_runtime() afresh, recording what it sets."""
        import jax

        import trlx_tpu.trlx as trlx

        updates = {}
        monkeypatch.setattr(trlx, "_runtime_initialized", False)
        monkeypatch.setattr(jax.config, "update", lambda k, v: updates.__setitem__(k, v))
        for var in ("TRLX_TPU_PLATFORM", "TRLX_TPU_MULTIHOST", "TRLX_TPU_COORDINATOR"):
            monkeypatch.delenv(var, raising=False)
        trlx.initialize_runtime()
        return updates

    def test_placed_from_outside_sets_nothing_in_code(self, monkeypatch):
        from trlx_tpu.trlx import compile_cache_dir

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache_dir() is None
        assert "jax_compilation_cache_dir" not in self._initialize(monkeypatch)

    def test_unset_lands_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        updates = self._initialize(monkeypatch)
        assert updates["jax_compilation_cache_dir"] == os.path.join(ROOT, ".jax_cache")

    def test_path_is_a_pure_function_of_the_checkout(self, monkeypatch, tmp_path):
        from trlx_tpu.trlx import compile_cache_dir

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = compile_cache_dir()
        monkeypatch.chdir(tmp_path)
        monkeypatch.setenv("TMPDIR", str(tmp_path))
        assert compile_cache_dir() == first == os.path.join(ROOT, ".jax_cache")


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_cpu_rehearsal_walks_every_phase(chips, tmp_path):
    """The whole script at toy size on the CPU: a full toy PPO + engine +
    server run (default) and the sharded comparison on four virtual devices
    (``--chips 4``). Every phase passes; the run still ends not-ok."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TRLX_TPU_NO_TQDM="1")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--chips", str(chips)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=1500,
    )
    lines = _json_lines(proc.stdout)
    assert [l["phase"] for l in lines[:-1]] == list(
        ("device", "train", "engine", "kernels") if chips == 1 else ("sharded",)
    ), proc.stderr[-3000:]
    assert all(l["ok"] for l in lines[:-1]), proc.stdout[-3000:]
    assert proc.returncode == 1
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": chips},
        "rehearsal": "passed",
    }

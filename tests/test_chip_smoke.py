"""The contract of ``chip_smoke.py`` that can be held without a chip: it
refuses to run off a TPU, its CPU rehearsal passes, a failing phase fails the
run, and the compile cache sits where the next process finds it."""

import json
import os
import sys

import jax
import pytest

from analytics_zoo_tpu.common import init_zoo_context, reset_zoo_context
from analytics_zoo_tpu.common.compile_cache import CHECKOUT

sys.path.insert(0, CHECKOUT)
import chip_smoke  # noqa: E402


@pytest.fixture()
def clean_state():
    """The rehearsal routes kernels by environment and engages a bf16
    context; neither may reach the next test."""
    names = ("ZOO_PAGED_ATTENTION", "ZOO_INT8_FUSED")
    saved = {name: os.environ.get(name) for name in names}
    yield
    for name, value in saved.items():
        if value is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = value
    reset_zoo_context()


def test_default_run_refuses_cpu_before_any_work(capsys, monkeypatch):
    for phase in chip_smoke.Smoke.PHASES:       # no work means no phase
        monkeypatch.setattr(chip_smoke.Smoke, phase,
                            lambda self: pytest.fail("a phase ran"))
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "platform=cpu" in err


def test_rehearsal_passes_on_cpu_and_says_so(capsys, clean_state):
    assert chip_smoke.main(["--rehearse-on-cpu"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    assert all("platform=cpu" in line for line in lines[:-1])
    for phase in ("0 device", "1 train-lm", "2 serve-gen", "3 serve-int8",
                  "4 train-ncf", "5 kernels"):
        assert any(line.startswith(f"[{phase}]") for line in lines), phase
    assert "synthetic_movielens" in "".join(lines)


def test_a_failing_phase_fails_the_run(capsys, monkeypatch, clean_state):
    def broken(self):
        raise FloatingPointError("injected: loss is nan")

    monkeypatch.setattr(chip_smoke.Smoke, "train_lm", broken)
    with pytest.raises(FloatingPointError):     # uncaught: exit code 1
        chip_smoke.main(["--rehearse-on-cpu"])
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_is_where_the_environment_says_or_in_the_checkout(
        monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        # JAX reads the variable into its config at import; the helper then
        # leaves both alone
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        init_zoo_context()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        init_zoo_context()
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            CHECKOUT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        reset_zoo_context()

"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

Each phase of the chip smoke run goes through the same entry points as on
the chip (Experiment / Session / BatchedSession and the training CLI) and
must train: a finite loss that falls. On the CPU the kernels are not
selected, so the compiled programs hold no Pallas kernel; the kernel
comparison runs the kernels in interpret mode against their XLA forms.
``main()`` itself refuses any backend but the TPU.
"""
import importlib.util
import json
import pathlib
import sys

import pytest

from repro.configs.paper_models import MLPConfig

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # its dataclasses look it up there
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load()

TINY = chip_smoke.Size(
    mlp=MLPConfig(input_dim=64, hidden=8, learning_rate=1e-2),
    paper_nodes=4, city_nodes=16, items=64, degree=4, local_steps=2,
    rounds=2, sweep_seeds=2,
    cli_argv=("--quick", "--rounds", "2", "--local-steps", "1"))


@pytest.mark.parametrize("phase", chip_smoke.PHASES,
                         ids=lambda f: f.__name__)
def test_phase_trains_at_tiny_size(phase, monkeypatch):
    # the CLI entry point turns on the persistent compile cache; keep this
    # test process off it
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    res = phase(TINY)
    loss = res["loss"]
    assert len(loss) >= 2 and loss[-1] < loss[0]
    if "kernels" in res:
        # the XLA forms run off the TPU: no Pallas kernel is compiled in
        assert res["kernels"] == set()
        assert res["compile_s"] > 0


def test_kernels_match_xla_at_tiny_size():
    diffs = dict(chip_smoke.compare_kernels(TINY))
    assert {n.split("_k")[0] for n in diffs} == {
        "flat_mix", "sparse_mix", "cluster_mix", "robust_agg"}
    assert all(d <= chip_smoke.TOLERANCE for d in diffs.values()), diffs


def test_check_loss_rejects_non_finite_or_flat_loss():
    with pytest.raises(chip_smoke.SmokeFailure, match="non-finite"):
        chip_smoke._check_loss("x", [[1.0], [float("nan")]])
    with pytest.raises(chip_smoke.SmokeFailure, match="did not fall"):
        chip_smoke._check_loss("x", [[1.0], [1.0]])


def test_main_refuses_a_backend_that_is_not_tpu(capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""                # no verdict line, no figures
    assert "needs a TPU" in err


def test_verdict_line_has_the_contract_keys(monkeypatch, capsys):
    """With every check stubbed to pass, the last stdout line is exactly
    the verdict JSON, naming the device JAX reports."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(chip_smoke.jax, "devices", lambda: [Dev()])
    monkeypatch.setattr(chip_smoke.jax, "device_count", lambda: 1)
    monkeypatch.setattr(chip_smoke, "compare_kernels", lambda size: [])
    monkeypatch.setattr(chip_smoke, "PHASES", ())
    monkeypatch.setattr(chip_smoke, "enable_compile_cache",
                        lambda: "cache-dir")
    assert chip_smoke.main() == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_compile_cache_dir_is_the_env_var_or_fixed(monkeypatch, tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the cache is JAX's own and
    nothing is configured; without it the cache sits at the fixed
    ``<repo>/.jax_cache``, the same path on every run."""
    import jax
    from repro.launch import compile_cache

    set_to = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: set_to.append((name, val)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert set_to == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = _PATH.parent / ".jax_cache"
    assert compile_cache.enable_compile_cache() == str(fixed)
    assert set_to == [("jax_compilation_cache_dir", str(fixed))]
